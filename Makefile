# Developer entry points (paddle/scripts/paddle_build.sh roles).
#
# The tests run on the CPU backend; `make test` shards test FILES across
# xdist workers where pytest-xdist is importable (file granularity is
# xdist-safe by construction). The driver's tier-1 run (`-m "not slow"`,
# six workers) took 481 s at PR 47 (/root/TESTS_LAST_RUN.json). No target
# here measures speed: that is `benchmark/run.py`'s, on the chip.

NPROC := $(shell nproc 2>/dev/null || echo 1)
# shard only with >1 core AND pytest-xdist importable (pip install -e .[test])
HAS_XDIST := $(shell python -c "import xdist" 2>/dev/null && echo 1 || echo 0)
DIST_FLAGS :=
ifneq ($(NPROC),1)
ifeq ($(HAS_XDIST),1)
DIST_FLAGS := -n auto --dist loadfile
endif
endif

.PHONY: test test-fast test-seq check lint chip-smoke

lint:  # graphlint gate: pure-AST framework lint, waivers must justify every exception
	python tools/graphlint.py --check

test:
	python -m pytest tests/ -q $(DIST_FLAGS)

test-fast:
	python -m pytest tests/ -q -m "not slow" $(DIST_FLAGS)

test-seq:  # force sequential
	python -m pytest tests/ -q

chip-smoke:  # the main path once on the TPU, full width, one process; exits 1 without a chip (no JAX_PLATFORMS here on purpose)
	python chip_smoke.py

check:
	python tools/graphlint.py --check
	python tools/check_op_coverage.py --min-pct 90
	python tools/print_signatures.py --check
	JAX_PLATFORMS=cpu python __graft_entry__.py
