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
#   check - static gates: graphlint (framework-aware AST lint, waiver-
#           gated) + op coverage + API spec + graft entry self-test
#           + debugz smoke (debug server endpoints + flight-recorder dump)
#           + mfu smoke (cost-model capture + utilization endpoints)
#           + serving smoke (online batcher/replica/HTTP contracts)
#           + generation smoke (prefill ladder/compile-once decode,
#             KV-cache parity, streaming /generate, drain)
#           + router smoke (fleet tier: backend processes + router,
#             kill -9 mid-burst survival, eviction, clean drain)
#           + chaos smoke (elastic training: kill -9 mid-checkpoint-save,
#             resume resharded at a new world size, identical loss curve)
#           + tracez smoke (distributed tracing: one trace across
#             router->backend processes, tail retention of deadline+retry)
#           + kernel smoke (fused pallas kernels: numeric parity,
#             bounded compiles, prefetch-overlap input-wait drop)
#           + quant smoke (int8 end-to-end: kernel parity, int8 serving
#             programs, int8 KV cache, quantized all-reduce byte cut)
#           + spec smoke (speculative decoding: greedy token parity at
#             exact draft+verify compile counts, self-draft acceptance,
#             2-process prefill->decode fleet through the KV handoff)
#           + memplan smoke (static peak-HBM planner: plan-vs-XLA
#             accuracy envelope on BERT/ResNet/GPT smoke programs,
#             strict pre-compile admission naming the high-water op,
#             donation-safety golden)
#           + autotune smoke (kernel autotuner: pallas-vs-jnp parity on
#             layernorm + conv+bn+relu under default AND tuned
#             schedules, offline search with pre-compile pruning, the
#             JSON cache round-tripping into a fresh process with zero
#             re-search, corrupt cache degrading to defaults)
#           + ir-opt smoke (program-IR optimizer: fused-op counts > 0
#             on BERT/ResNet/GPT smoke programs with numeric goldens,
#             training-program byte-identity at level 1, and remat
#             converting a strict-mode rejection into an admit with
#             >= 20% planned-peak reduction)
#           + slo smoke (fleet SLO plane: labeled /metricz series, a
#             wedged backend paging via multi-window burn rate with a
#             slo_burn flight event, /fleetz quantiles equal to the
#             pooled-histogram golden, the scaler reading the burn)
#           + goodput smoke (training goodput ledger: >= 0.8 goodput
#             steady-state with 2% phase-conservation, kill -9 mid-save
#             resume continuing the lifetime ledger with recomputation
#             charged to lost_work)
#           + opprof smoke (per-op device-time attribution: >= 0.9
#             stamped-scope coverage + time-accuracy envelope on the
#             BERT/ResNet/GPT smokes, measured fused-conv win,
#             /profilez end to end)
#           + paged smoke (paged KV: ring-vs-paged greedy parity at
#             bounded compiles, 90%-shared-prefix burst with the
#             prefill-FLOPs/TTFT win, >= 1.3x slots at equal HBM on a
#             constrained pool, strict memplan refusing an over-budget
#             pool before allocation)
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
    # fault-diagnosis smoke: debug server up, endpoints valid, dump CLI works
    JAX_PLATFORMS=cpu python tools/debugz_smoke.py
    # utilization smoke: cost-model capture, MFU monitor line, /costz+/clusterz
    JAX_PLATFORMS=cpu python tools/utilization_smoke.py
    # serving smoke: warmed-bucket readiness, bounded compiles, 429, drain
    JAX_PLATFORMS=cpu python tools/serving_smoke.py
    # generation smoke: prefill ladder + single decode compile, KV-cache
    # parity over HTTP, streaming round trip, drain leaves no live slots
    JAX_PLATFORMS=cpu python tools/generation_smoke.py
    # router smoke: 2 backend processes + router, kill -9 one mid-burst
    # (zero client-visible failures), eviction counters, clean drain
    JAX_PLATFORMS=cpu python tools/router_smoke.py
    # chaos smoke: elastic training — kill -9 inside a checkpoint save,
    # resume at a DIFFERENT world size with ZeRO-1 state resharded, and
    # a loss curve identical to the uninterrupted run
    JAX_PLATFORMS=cpu python tools/chaos_smoke.py
    # tracez smoke: router + 2 backend processes — one trace_id across the
    # process hop with queue/dispatch stage spans, deadline-missed and
    # retried traces retained while the fast-path bulk is dropped
    JAX_PLATFORMS=cpu python tools/tracez_smoke.py
    # kernel smoke: fused optimizer-update + layernorm/residual numeric
    # parity (pallas interpret vs jnp, flag on/off through real call
    # sites), one-compile steady state, prefetch-overlap input-wait drop
    JAX_PLATFORMS=cpu python tools/kernel_smoke.py
    # quant smoke: int8 matmul kernel parity (pallas interpret == jnp,
    # bit-equal), PTQ -> save_int8_model served through a real
    # InferenceServer within the fp32 envelope at bounded compiles,
    # int8-KV decode == fp32 greedy tokens at >=1.8x slots/HBM, and the
    # quantized all-reduce's >=3.5x wire-byte cut from the ledger +
    # BERT-smoke loss convergence vs fp32
    JAX_PLATFORMS=cpu python tools/quant_smoke.py
    # spec smoke: speculative greedy decode token-identical to the plain
    # engine at exactly len(ladder)+2 compiles (draft + verify), self-
    # draft acceptance at the ceiling, and a real 1-prefill+1-decode
    # two-process fleet serving /generate through the KV-slab handoff
    # with zero unexpected compiles on either tier
    JAX_PLATFORMS=cpu python tools/spec_decode_smoke.py
    # memplan smoke: static liveness planner within the ±25% envelope of
    # XLA memory_analysis on BERT/ResNet/GPT smoke programs, strict mode
    # rejecting an over-budget program BEFORE compile with the
    # high-water op named, and the donated-then-read golden rejected
    JAX_PLATFORMS=cpu python tools/memplan_smoke.py
    # autotune smoke: kernel autotuner — layernorm + conv+bn+relu parity
    # under default and tuned schedules (fwd+bwd), offline search with
    # invalid candidates pruned before compile, the versioned JSON cache
    # round-tripping across a fresh process with zero re-search, and a
    # truncated cache degrading to defaults (one cache_reject, no crash)
    JAX_PLATFORMS=cpu python tools/autotune_smoke.py
    # ir-opt smoke: program-IR optimizer — conv+bn+relu / residual+ln /
    # int8-matmul fusions firing on BERT/ResNet/GPT inference smokes
    # with numeric goldens vs the unrewritten programs, a training
    # program (grad:: ops) passing through byte-identical at level 1,
    # and level-2 rematerialization turning a strict-budget rejection
    # into an admit at >= 20% planned-peak reduction
    JAX_PLATFORMS=cpu python tools/ir_opt_smoke.py
    # slo smoke: fleet SLO plane — labeled per-kind/tenant series on
    # /metricz (text + snapshot modes), one wedged backend driving its
    # fast+slow window burns past the alert threshold (slo_burn flight
    # event) while the healthy backend stays quiet, router /fleetz
    # p50/p99 exactly equal to the hand-merged pooled histogram, and
    # the autoscaler reading the confirmed burn as up-pressure
    JAX_PLATFORMS=cpu python tools/slo_smoke.py
    # goodput smoke: training goodput ledger — uninterrupted run at
    # goodput >= 0.8 with phase seconds summing to wall within 2%
    # (conservation), then a kill -9 inside a checkpoint save with the
    # resume continuing the lifetime ledger from the GOODPUT.json
    # sidecar (lifetime wall > post-restart wall) and the recomputed
    # steps charged to lost_work, not compute
    JAX_PLATFORMS=cpu python tools/goodput_smoke.py
    # opprof smoke: per-op device-time attribution — replay profiles of
    # the BERT/ResNet/GPT smokes with stamped-scope trace coverage
    # >= 0.9 and per-program time-accuracy inside the documented
    # envelope, top-op sanity (matmul/conv family leads by FLOPs), the
    # conv+bn+relu fusion win measured per op (not asserted from
    # theory), and /profilez served end to end
    JAX_PLATFORMS=cpu python tools/opprof_smoke.py
    # paged smoke: paged KV subsystem — ring-vs-paged greedy parity on
    # a mixed 8-prompt burst at exactly ladder+1 compiles, a
    # 90%-shared-prefix burst admitting through the radix index with
    # the prefill-FLOPs saving and a measured TTFT drop, the same
    # mixed short/long workload running token-identically on a pool
    # 1.6x smaller than the ring reservation (>= 1.3x slots at equal
    # HBM), and strict memplan refusing an over-budget pool at engine
    # construction, before any device allocation
    JAX_PLATFORMS=cpu python tools/paged_smoke.py
    ;;
  *)
    echo "unknown mode: $MODE (fast|full|check)" >&2
    exit 2
    ;;
esac
