# Developer entry points (paddle/scripts/paddle_build.sh roles).
#
# Test-suite wall time is CPU-bound (the XLA:CPU backend compiles and
# runs every test's programs; user time ~= real time on 1 core). The
# persistent compilation cache (.jax_cache, the package's own default —
# paddle_tpu/runtime/compile_cache.py) cuts repeat-run compile cost; on
# multi-core hosts `make test` shards test FILES across xdist workers
# for near-linear speedup (file granularity is xdist-safe by
# construction).
#
# Measured on the 1-core reference box (warm cache):
#   make test        12m20  (591 tests; floor is compute, not overhead)
#   make test-fast   10m39  (582 tests; skips the 9 subprocess-heavy
#                            "slow" tests)
# Projected at >=4 cores: test ~4-5m, test-fast ~3m.

NPROC := $(shell nproc 2>/dev/null || echo 1)
# shard only with >1 core AND pytest-xdist importable (pip install -e .[test])
HAS_XDIST := $(shell python -c "import xdist" 2>/dev/null && echo 1 || echo 0)
DIST_FLAGS :=
ifneq ($(NPROC),1)
ifeq ($(HAS_XDIST),1)
DIST_FLAGS := -n auto --dist loadfile
endif
endif

.PHONY: test test-fast test-seq check lint trace-smoke debugz-smoke mfu-smoke serve-smoke gen-smoke router-smoke chaos-smoke tracez-smoke kernel-smoke quant-smoke spec-smoke memplan-smoke autotune-smoke ir-opt-smoke slo-smoke goodput-smoke opprof-smoke paged-smoke chip-smoke

lint:  # graphlint gate: pure-AST framework lint, waivers must justify every exception
	python tools/graphlint.py --check

test:
	python -m pytest tests/ -q $(DIST_FLAGS)

test-fast:
	python -m pytest tests/ -q -m "not slow" $(DIST_FLAGS)

test-seq:  # force sequential (timing baselines)
	python -m pytest tests/ -q

trace-smoke:  # 3-step train under the monitor; both exporters must work
	JAX_PLATFORMS=cpu python tools/trace_smoke.py

debugz-smoke:  # run with the debug server on; curl /healthz + /flightrecorder
	JAX_PLATFORMS=cpu python tools/debugz_smoke.py

mfu-smoke:  # cost-model capture + MFU line + /costz /clusterz endpoints
	JAX_PLATFORMS=cpu python tools/utilization_smoke.py

serve-smoke:  # online serving: readiness gating, bounded compiles, 429, drain
	JAX_PLATFORMS=cpu python tools/serving_smoke.py

gen-smoke:  # generative serving: prefill ladder + compile-once decode, parity, streaming, drain
	JAX_PLATFORMS=cpu python tools/generation_smoke.py

router-smoke:  # serving fleet: 2 backend processes + router, kill -9 survival, drain
	JAX_PLATFORMS=cpu python tools/router_smoke.py

chaos-smoke:  # elastic training: kill -9 mid-save + world resizes, loss-curve-identical resume
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py

tracez-smoke:  # distributed tracing: cross-process trace continuity, tail retention of deadline+retry
	JAX_PLATFORMS=cpu python tools/tracez_smoke.py

kernel-smoke:  # fused pallas kernels: numeric parity, zero extra compiles, h2d overlap
	JAX_PLATFORMS=cpu python tools/kernel_smoke.py

quant-smoke:  # int8 end-to-end: kernel parity, int8 serving, int8 KV cache, quantized all-reduce
	JAX_PLATFORMS=cpu python tools/quant_smoke.py

spec-smoke:  # speculative decoding: greedy parity, draft+verify compile counts, 2-process prefill->decode handoff
	JAX_PLATFORMS=cpu python tools/spec_decode_smoke.py

memplan-smoke:  # static peak-HBM planner: accuracy envelope, strict admission, donation-safety golden
	JAX_PLATFORMS=cpu python tools/memplan_smoke.py

autotune-smoke:  # kernel autotuner: parity under tuned schedules, search + cache round-trip, zero re-search warm
	JAX_PLATFORMS=cpu python tools/autotune_smoke.py

ir-opt-smoke:  # program-IR optimizer: fusion counts, numeric goldens, training byte-identity, remat strict admit
	JAX_PLATFORMS=cpu python tools/ir_opt_smoke.py

slo-smoke:  # fleet SLO plane: wedged backend pages via burn rate, /fleetz == pooled golden, scaler sees burn
	JAX_PLATFORMS=cpu python tools/slo_smoke.py

goodput-smoke:  # goodput ledger: >=0.8 steady-state, 2% conservation, kill -9 resume continues lifetime ledger
	JAX_PLATFORMS=cpu python tools/goodput_smoke.py

opprof-smoke:  # per-op attribution: >=0.9 coverage, time-accuracy envelope, measured fusion win, /profilez
	JAX_PLATFORMS=cpu python tools/opprof_smoke.py

paged-smoke:  # paged KV: ring parity at bounded compiles, shared-prefix FLOPs+TTFT win, >=1.3x slots at equal HBM, strict pool admission
	JAX_PLATFORMS=cpu python tools/paged_smoke.py

chip-smoke:  # the main path once on the TPU, full width, one process; exits 1 without a chip (no JAX_PLATFORMS here on purpose)
	python chip_smoke.py

check:
	python tools/graphlint.py --check
	python tools/check_op_coverage.py --min-pct 90
	python tools/print_signatures.py --check
	JAX_PLATFORMS=cpu python __graft_entry__.py
