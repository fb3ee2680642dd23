#!/usr/bin/env bash
# CI driver (paddle/scripts/paddle_build.sh role: cmake_gen/build/run_test
# collapsed to what this runtime needs).
#
# Usage: tools/build_and_test.sh [fast|full|check] [NSHARDS]
#   fast  - unit tests minus slow/subprocess ones
#   full  - entire suite (default); pass NSHARDS>1 to split the test
#           FILES across that many parallel pytest processes (xdist-safe
#           by construction: file granularity, no shared-scope state
#           crosses processes; compile-heavy files dominate wall time so
#           sharding gives near-linear speedup)
#   check - the static gates: graphlint (framework-aware AST lint,
#           waiver-gated), op coverage, the API spec, and the graft
#           entry's self-test. Behaviour is tier-1's (tests/) and speed
#           the benchmark's, on the chip: there is no other CI.
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"
NSHARDS="${2:-1}"

sharded_pytest() {
  # split test files round-robin over NSHARDS pytest processes
  local extra=("$@")
  mapfile -t files < <(ls tests/test_*.py | sort)
  local pids=() rc=0
  for ((s = 0; s < NSHARDS; s++)); do
    local shard=()
    for ((i = s; i < ${#files[@]}; i += NSHARDS)); do
      shard+=("${files[i]}")
    done
    # an empty shard must be a no-op (bare pytest would rediscover the
    # whole suite)
    [ "${#shard[@]}" -eq 0 ] && continue
    python -m pytest "${shard[@]}" -q -p no:cacheprovider "${extra[@]}" &
    pids+=($!)
  done
  for pid in "${pids[@]}"; do
    wait "$pid" || rc=1
  done
  return $rc
}

native_build() {
  # compile the native components into the cache (fails loudly here
  # rather than lazily at first use)
  python - <<'PY'
import jax; jax.config.update("jax_platforms", "cpu")
from paddle_tpu._native import ShmRing
from paddle_tpu._native.capi import build_capi
ShmRing._load()
print("shm_ring OK")
print("capi:", build_capi())
PY
}

case "$MODE" in
  fast)
    native_build
    python -m pytest tests/ -x -q -m "not slow"
    ;;
  full)
    native_build
    if [ "$NSHARDS" -gt 1 ]; then
      sharded_pytest
    else
      python -m pytest tests/ -q
    fi
    ;;
  check)
    # graphlint gate first: pure AST (no jax), fails on any unwaived
    # finding or stale waiver (tools/graphlint_waivers.txt)
    python tools/graphlint.py --check
    python tools/check_op_coverage.py --min-pct 90
    python tools/print_signatures.py --check
    JAX_PLATFORMS=cpu python __graft_entry__.py
    ;;
  *)
    echo "unknown mode: $MODE (fast|full|check)" >&2
    exit 2
    ;;
esac
