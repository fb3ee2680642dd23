"""Global FLAGS registry (env-driven runtime configuration).

Reference parity: gflags definitions in paddle/fluid/platform/flags.cc
(~50 flags, e.g. FLAGS_check_nan_inf :44), exported to Python through
global_value_getter_setter.cc as ``core.globals()`` and the
paddle.get_flags/set_flags API; ``init_gflags`` (pybind/pybind.cc:1652)
imports ``FLAGS_*`` environment variables.

TPU-native scope: only flags that change behavior on this runtime are
registered — memory-fraction/allocator/cudnn knobs have no XLA
equivalent and registering silent no-ops is worse than NotFound (the
same contract as DistributedStrategy consumption). Each flag documents
what consumes it.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "globals_view"]


@dataclass
class _Flag:
    name: str
    value: object
    default: object
    type: type
    help: str
    # writable=False mirrors the reference's non-public globals
    # (global_value_getter_setter.cc exposes some read-only)
    writable: bool = True


_REGISTRY: dict[str, _Flag] = {}


def _coerce(value, typ):
    if typ is bool and isinstance(value, str):
        return value.strip().lower() in ("1", "true", "yes", "on")
    return typ(value)


def define_flag(name: str, default, help: str = "", writable: bool = True):
    """Register a flag (DEFINE_bool/int32/double/string equivalent,
    platform/flags.cc). ``FLAGS_<name>`` env overrides the default at
    definition time (init_gflags semantics)."""
    typ = type(default)
    value = default
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        value = _coerce(env, typ)
    _REGISTRY[name] = _Flag(name, value, default, typ, help, writable)
    return value


def flag(name: str):
    """Fast internal read used by the runtime hot paths."""
    try:
        return _REGISTRY[name].value
    except KeyError:
        from .errors import NotFoundError

        raise NotFoundError(
            f"unknown flag {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


def get_flags(names):
    """paddle.get_flags: dict of current values for name or list of names."""
    if isinstance(names, str):
        names = [names]
    return {n: flag(n) for n in names}


def set_flags(flags_map: dict):
    """paddle.set_flags: update flag values with type checking."""
    from .errors import InvalidArgumentError, NotFoundError

    for name, value in flags_map.items():
        f = _REGISTRY.get(name)
        if f is None:
            raise NotFoundError(
                f"unknown flag {name!r}; known: {sorted(_REGISTRY)}"
            )
        if not f.writable:
            raise InvalidArgumentError(f"flag {name!r} is read-only")
        try:
            f.value = _coerce(value, f.type)
        except (TypeError, ValueError) as e:
            raise InvalidArgumentError(
                f"flag {name!r} expects {f.type.__name__}, got {value!r}"
            ) from e
        # flag flips are exactly the kind of breadcrumb a post-mortem
        # needs ("who turned donation off mid-run?") — record each one
        try:
            from .monitor import flight_recorder as _flight

            _flight.record_event("flag_change", flag=name,
                                 value=repr(f.value))
        except Exception:
            pass  # bootstrap import order / partially-initialized package


def globals_view() -> dict:
    """core.globals() equivalent: snapshot of every flag value."""
    return {n: f.value for n, f in _REGISTRY.items()}


# ---------------------------------------------------------------------------
# Registered flags (each consumed somewhere — grep the name to find where)
# ---------------------------------------------------------------------------

# platform/flags.cc:44 — wired into framework/jit.py TrainStepFn (checkify
# per-primitive NaN/Inf localization) and static/executor.py (post-run
# scan of fetches/written vars, naming the variable)
define_flag("check_nan_inf", False,
            "scan step outputs for NaN/Inf and name the producing op")

# static/executor.py + static/program.py Program.verify + analysis/ —
# run the program-IR verifier (def-before-use, write conflicts, kernel
# dtype consistency, control-flow block well-formedness; analysis/passes)
# before each program is planned/lowered, raising a structured
# VerifyError naming the offending op index/type/var instead of an
# opaque XLA trace error. Values: off | on | strict ("strict" promotes
# dead-code findings to errors). The verdict is cached per program
# version, so steady-state dispatch pays a dict lookup.
define_flag("program_verify", "on",
            "verify program IR before lowering: off | on | strict "
            "(strict also fails on dead ops/vars)")

# static/executor.py + analysis/memory.py — static peak-HBM admission:
# before any lower/compile, plan the program's liveness footprint
# (analysis.plan_memory) and compare the predicted peak against the
# device HBM capacity from the cost-model peaks table (hbm_bytes,
# overridable via FLAGS_device_peaks). "strict" rejects over-budget
# programs (MemoryBudgetError naming the high-water op + top tensors)
# and liveness-unsafe donations (DonationError) BEFORE compiling;
# "warn" records the same verdicts as memory_budget flight events and
# a Python warning but admits. Verdicts cache per program version —
# steady-state dispatch pays a dict lookup. The generation engine
# applies the same budget to its slots x cache-len x dtype geometry at
# construction.
define_flag("memory_budget_check", "warn",
            "static peak-HBM admission before compile: off | warn | "
            "strict (strict rejects over-budget programs and unsafe "
            "donations with the high-water op named)")

# static/executor.py Executor.run + inference/predictor.py Predictor +
# analysis/optimizer.py — the program-IR optimizer gate, run ahead of the
# verify/memplan gates and lowering (the switch_ir_optim role of
# inference/api/paddle_pass_builder.cc, generalized to every executed
# program). 0: off (programs run exactly as built). 1: fusion rewrites
# onto the fused registry kernels (conv2d->batch_norm->relu,
# residual-add->layer_norm, dequantized-int8 matmul/mul chains) plus
# side-effect-safe dead-op elimination — a training program with no
# fusible chain comes back byte-identical. 2: level 1 plus liveness-
# driven rematerialization when the memory planner says the program is
# over the device HBM budget (recompute cheap activations at their late
# uses instead of holding them). The optimized clone caches per program
# version (the verifier-cache discipline), so steady-state dispatch pays
# one dict lookup; per-pass stats land on profiler counters and /statz.
define_flag("ir_opt_level", 1,
            "program-IR optimizer level: 0 off, 1 fusion+DCE, "
            "2 +rematerialization under memory pressure")

# platform/flags.cc benchmark — wired into framework/jit.py: synchronous
# dispatch (block until ready each step) so wall-clock timings are exact
define_flag("benchmark", False,
            "synchronous step dispatch for exact per-step timing")

# platform/enforce.h FLAGS_call_stack_level — wired into errors.py
# formatting (0: message only, 1: + op context, 2: + python stack)
define_flag("call_stack_level", 1,
            "error verbosity: 0 message, 1 +op context, 2 +python stack")

# static/executor.py — buffer donation for persistables on the compiled
# whole-block step: parameters/optimizer state update in place (XLA input/
# output aliasing) instead of doubling HBM traffic each step, matching the
# dygraph path's donate_argnums (parallel/train.py). The Scope transfers
# ownership: after a run, donated scope entries point at the NEW arrays and
# the old buffers are dead. Opt out for debugging workflows that hold
# references to pre-step parameter arrays.
define_flag("executor_buffer_donation", True,
            "donate written persistables to the compiled step (in-place "
            "parameter updates); disable to keep pre-step arrays alive")

# monitor/training_monitor.py — steps between TrainingMonitor periodic
# log lines (step wall time, examples/sec, input-wait ratio, cache hit
# rates, HBM watermark). 0 disables the line; aggregation always runs
# (it is a handful of float adds per step).
define_flag("monitor_interval", 100,
            "steps between TrainingMonitor log lines (0: silent)")

# monitor/flight_recorder.py — the structured-event ring buffer every
# subsystem reports into (executor runs, collectives with per-group seq
# numbers, PS RPCs, dataloader lifecycle, flag changes, XLA compiles);
# dumped on unhandled exception / SIGUSR1 / watchdog trip. Recording is
# one flag read, one dict build and one short lock hold an event;
# disable only to rule instrumentation out.
define_flag("flight_recorder", True,
            "record structured runtime events into the in-memory ring "
            "buffer for crash/hang post-mortems")

# monitor/flight_recorder.py — ring capacity, read once at recorder
# construction (import time); resizing a live ring would tear its seq
# accounting
define_flag("flight_recorder_capacity", 4096,
            "flight-recorder ring buffer capacity (events)")

# monitor/flight_recorder.py — where dump files land
# (paddle_tpu_flight_rank<r>_pid<pid>.json); empty: the system temp dir
define_flag("flight_recorder_dump_dir", "",
            "directory for flight-recorder dump files (empty: temp dir)")

# monitor/flight_recorder.py HangWatchdog — trips when no executor step,
# eager collective, or PS reply completes within the deadline; the trip
# dumps the recorder + all thread stacks and runs the cross-rank desync
# exchange. 0 disables. Consumed by install_from_flags (init_parallel_env)
# and start_watchdog().
define_flag("watchdog_timeout_s", 0.0,
            "hang watchdog deadline in seconds (0: disabled); on trip, "
            "dump the flight recorder + thread stacks + desync report")

# monitor/debug_server.py — /healthz /metrics /flightrecorder /threadz
# /flagz on 127.0.0.1:<port + rank> (rank-offset so every process of a
# multi-process host serves). 0 disables.
define_flag("debug_port", 0,
            "base port for the loopback HTTP debug endpoint "
            "(bound at port+rank; 0: disabled)")

# monitor/tracing.py — distributed request tracing: contextvar trace
# context, traceparent propagation router->backend, spans through the
# serving/executor path, step-scoped training traces. Disable only to
# rule the instrumentation out of a measurement.
define_flag("trace_enabled", True,
            "record per-request trace spans (traceparent propagation, "
            "/tracez, /statz slowest table)")

# monitor/tracing.py TraceStore — TAIL sampling: the retention decision
# happens at trace completion, when the outcome is known. Error /
# deadline / retried / timed-out traces are ALWAYS kept; of the boring
# rest, only the slowest K per window survive.
define_flag("trace_sample_slowest_k", 5,
            "retain the K slowest traces per sampling window in "
            "addition to every errored/flagged trace (0: flagged only)")

# monitor/tracing.py TraceStore — the slowest-K competition window; a
# new window forgets the old champions so a quiet hour cannot pin the
# store to stale outliers
define_flag("trace_sample_window_s", 30.0,
            "tail-sampling window in seconds for the slowest-K "
            "retention race")

# monitor/tracing.py TraceStore — bound on RETAINED traces (FIFO
# eviction past it); active (in-flight) traces are bounded at 4x this
define_flag("trace_store_capacity", 256,
            "maximum retained traces in the in-process trace store")

# static/executor.py _scan_nan_inf + framework/jit.py checkify path —
# what detection does: 'raise' (FatalError, the historical behavior),
# 'warn' (bump debug/nan_events, log the first offending variable, keep
# running), 'dump' (write the flight-recorder snapshot, then raise)
define_flag("check_nan_inf_action", "raise",
            "on NaN/Inf detection: raise | warn (count+log, continue) | "
            "dump (flight-recorder snapshot, then raise)")

# monitor/cost_model.py — override the detected device peak-throughput
# table (the MFU / HBM-bandwidth / roofline denominators) for new
# silicon, derated SKUs, or meaningful CPU numbers. Comma-separated
# k=v floats over {flops, hbm_bw, ici_bw} in FLOP/s and B/s, e.g.
# "flops=2.75e14,hbm_bw=1.228e12,ici_bw=3e11"; any subset overrides.
define_flag("device_peaks", "",
            "override device peak throughputs for utilization accounting:"
            " 'flops=<FLOP/s>,hbm_bw=<B/s>,ici_bw=<B/s>' (any subset)")

# monitor/cluster.py — a rank is flagged as a straggler on /clusterz when
# its step time exceeds this multiple of the cluster-median step time;
# the verdict is also recorded into the flight recorder
define_flag("straggler_threshold", 1.5,
            "flag a rank as straggler when its step time exceeds this "
            "multiple of the cluster median (/clusterz)")

# monitor/cluster.py ClusterPublisher — seconds between per-rank metric-
# snapshot publishes over the jax.distributed KV side channel (feeds
# rank-0's /clusterz). 0 disables; single-process worlds never publish.
# Consumed by install_from_flags (init_parallel_env).
define_flag("cluster_metrics_interval_s", 15.0,
            "period for publishing per-rank metric snapshots to the "
            "cluster aggregator (0: disabled)")

# serving/batcher.py — the shape-bucket ladder for the online batcher's
# batch axis. Every assembled batch is padded up to the smallest bucket
# that covers its rows, so the steady-state compile count is bounded by
# the ladder length (asserted after warmup). Powers of two by default:
# each recompile doubles capacity, log2(max) compiles total.
define_flag("serving_batch_buckets", "1,2,4,8",
            "comma-separated ascending batch-axis bucket sizes for the "
            "online serving batcher; each bucket is one compiled shape")

# serving/batcher.py — bounded admission queue. A full queue REJECTS the
# request (QueueFullError -> HTTP 429) instead of queueing unboundedly:
# under sustained overload an unbounded queue converts every request
# into a deadline miss while memory grows without limit.
define_flag("serving_queue_capacity", 256,
            "max requests the serving batcher holds before rejecting "
            "(backpressure: HTTP 429)")

# serving/batcher.py — how long the batch-assembly loop holds an open
# batch waiting for more requests after the first one arrives. The
# latency/throughput knob: 0 dispatches every request immediately.
define_flag("serving_batch_timeout_ms", 2.0,
            "max ms the serving batcher waits to fill a batch beyond "
            "its first request (0: dispatch immediately)")

# serving/replica.py — worker threads in the replica pool; every replica
# is a Predictor.clone() sharing ONE jit/AOT executable cache, so N
# replicas serve with zero extra compiles.
define_flag("serving_replicas", 1,
            "replica worker threads serving the online batcher")

# serving/batcher.py — default per-request deadline; a request that sits
# queued past its deadline completes with ExecutionTimeoutError without
# ever dispatching. 0 disables (requests wait indefinitely).
define_flag("serving_default_deadline_ms", 0.0,
            "default per-request serving deadline in ms (0: none); "
            "expired requests error without dispatch")

# generation/engine.py — capacity (tokens) of the static-shape ring KV
# cache per decode slot. Shapes never change across decode steps, so one
# compiled step serves every sequence length; past the window the ring
# overwrites the oldest token (sliding-window attention of this width —
# the model computes the same function, golden-tested).
define_flag("generation_kv_cache_len", 256,
            "per-slot ring KV cache capacity (tokens) for autoregressive "
            "decoding; also the sliding attention window width")

# generation/engine.py + nn/transformer.py QuantizedStaticCache — storage
# dtype of the ring KV cache. "int8" stores K/V as int8 with per-head
# dynamic scales (quantize on ring write, dequantize inside the
# attention read): ~3.8x fewer KV bytes per token at head_dim 64, so the
# same HBM holds ~1.9x the decode slots — a direct capacity multiplier
# for the continuous batcher, certified against the full-forward parity
# goldens at the envelope documented in README "Quantization".
define_flag("generation_kv_cache_dtype", "float32",
            "KV cache storage dtype for decoding: float32 | bfloat16 "
            "(ring layout only) | int8 (per-head dynamic scales, ~4x "
            "fewer cache bytes)")

# generation/paging.py + nn/transformer.py PagedStaticCache — physical
# layout of the decode KV store. "ring" is the historical per-slot
# contiguous ring; "paged" decomposes the same logical ring into
# fixed-size pages drawn from a shared pool through per-slot page
# tables, enabling copy-on-write prefix sharing across requests and
# capacity as a function of ACTUAL tokens instead of worst-case window.
# Greedy output is token-identical between the two layouts.
define_flag("kv_cache_layout", "ring",
            "decode KV cache layout: ring (per-slot contiguous) | paged "
            "(shared page pool + per-slot page tables with copy-on-write "
            "prefix reuse)")

# generation/paging.py — tokens per KV page under the paged layout.
# Smaller pages share more aggressively (a prefix must fill a whole
# page to be reusable) but widen the page tables; must divide
# generation_kv_cache_len.
define_flag("generation_kv_page_size", 16,
            "tokens per KV page under kv_cache_layout=paged; must "
            "divide generation_kv_cache_len evenly")

# generation/paging.py — physical pages in the shared pool. 0 sizes the
# pool at slots x pages_per_slot (ring-equivalent worst case); smaller
# values bank on prefix sharing / short sequences to overcommit slots
# against HBM (the slots-vs-pages capacity recipe in README).
define_flag("generation_kv_pool_pages", 0,
            "physical KV pages in the paged pool (0: slots x "
            "pages_per_slot, the no-overcommit default)")

# generation/engine.py — the sequence-length bucket ladder for prefill.
# Prompts pad up to the smallest covering bucket, so prefill costs at
# most len(ladder) compiles ever — the serving batch-bucket discipline,
# applied to the sequence axis.
define_flag("generation_prefill_buckets", "16,32,64,128",
            "comma-separated ascending prompt-length buckets for "
            "generation prefill; each bucket is one compiled shape")

# generation/engine.py + serving/continuous.py — concurrent decode slots
# in the continuous-batching step. A finished sequence vacates its slot
# mid-batch and the next queued request is admitted at the next step;
# the decode program's batch axis is always exactly this many rows.
define_flag("generation_decode_slots", 4,
            "decode slots co-batched in the compiled generation step "
            "(continuous batching admits into vacant slots mid-batch)")

# generation/engine.py — default generation budget when the request does
# not set one.
define_flag("generation_max_new_tokens", 64,
            "default max tokens generated per request (requests may "
            "override below the model's position limit)")

# generation/engine.py — default sampling temperature; 0 = greedy
# (argmax). Per-request temperatures are traced values: any mix of
# greedy and sampled requests co-batches in the one compiled step.
define_flag("generation_temperature", 0.0,
            "default sampling temperature (0: greedy argmax); "
            "per-request override is compile-free")

# generation/engine.py — top-k filter width; 0 disables. STATIC: a
# different k is a different compiled program, so it is an engine-level
# knob, not a per-request one (the compile-once guarantee).
define_flag("generation_top_k", 0,
            "top-k sampling filter for generation (0: full distribution); "
            "engine-level — changing it recompiles the decode step")

# serving/continuous.py — bounded admission queue for generation
# requests, same backpressure contract as serving_queue_capacity (full
# queue -> QueueFullError -> HTTP 429).
define_flag("generation_queue_capacity", 128,
            "max generation requests queued for decode slots before "
            "rejecting (backpressure: HTTP 429)")

# generation/engine.py — speculative decoding. When enabled (and a
# draft model is available, e.g. serving/backend.py --draft-dir), every
# decode round runs the draft chain + ONE batched target verify over
# draft_k+1 positions instead of one full-model dispatch per token:
# greedy output stays token-identical to the plain engine, and each
# round emits 1..draft_k+1 tokens for two dispatches.
define_flag("speculative_enabled", False,
            "enable speculative decoding in serving backends that have "
            "a draft model configured (greedy output is token-identical "
            "to the plain engine)")

# generation/engine.py — proposals per speculative round. STATIC: k
# shapes the draft/verify programs (and widens the ring store by k
# scratch entries), so it is an engine-level knob, not per-request.
define_flag("speculative_draft_k", 4,
            "draft tokens proposed per speculative decoding round; "
            "engine-level — changing it recompiles draft+verify")

# serving/backend.py + serving/server.py — role of a generation backend
# in a disaggregated fleet. "generate" serves /generate end to end;
# "prefill" runs only the bucket-ladder forward and ships the KV slab
# (POST /prefill); "decode" admits handed-off slabs into decode slots
# (POST /generate_kv). The router composes prefill -> decode for
# /generate when both tiers are in rotation.
define_flag("backend_kind", "generate",
            "generation backend role: generate | prefill | decode "
            "(disaggregated fleets run distinct prefill/decode tiers)")

# serving/router.py — budget for the prefill leg of a disaggregated
# /generate (prompt -> KV slab). The decode leg keeps the normal
# request timeout: prefill is one bounded forward, decode is an open-
# ended generation.
define_flag("serving_handoff_timeout_s", 30.0,
            "router timeout for the prefill->slab leg of a "
            "disaggregated /generate handoff")

# serving/router.py — period of the router's backend prober (GET
# /healthz + /loadz per backend): drives load-signal freshness AND the
# only re-admission path for an evicted backend (readiness must flip
# back on /healthz before it rejoins rotation).
define_flag("serving_router_probe_interval_s", 1.0,
            "seconds between router health/load probes of each backend; "
            "also the re-admission latency for a recovered backend")

# serving/router.py — how many DISTINCT backends one request may be
# offered before the router gives up with 503. Retries happen only for
# connection-level failures and admission rejections (503) — work a
# backend actually answered is never replayed.
define_flag("serving_router_retries", 3,
            "max distinct backends tried per routed request before 503 "
            "(connection failures / admission rejects only)")

# serving/router.py — TCP connect budget per dispatch attempt. Short on
# purpose: a dead backend must cost the request milliseconds (then the
# next backend is tried), not a full request timeout.
define_flag("serving_router_connect_timeout_ms", 1000.0,
            "router->backend TCP connect timeout per attempt in ms")

# serving/router.py — end-to-end budget for one proxied request once it
# is on a backend (covers queueing + dispatch there).
define_flag("serving_router_request_timeout_s", 120.0,
            "router->backend response timeout once a request is "
            "dispatched (seconds)")

# incubate/auto_checkpoint.py + distributed/checkpoint.py — serialize and
# fsync snapshots in a background thread instead of on the step/epoch
# critical path. The capture itself is a device-side copy (donation-safe)
# dispatched asynchronously; publication stays atomic (tmp -> rename with
# a checksummed manifest) either way, so a crash mid-save can never be
# loaded — only detected and skipped.
define_flag("checkpoint_async", True,
            "serialize + fsync checkpoints in a background thread "
            "(off the training step critical path)")

# incubate/auto_checkpoint.py — minimum seconds between periodic
# snapshots. Negative: defer to the PADDLE_EDL_SAVE_CHECKPOINT_INTER env
# (the reference's knob); >= 0 overrides it at runtime without touching
# the environment.
define_flag("checkpoint_save_inter_s", -1.0,
            "min seconds between auto-checkpoint snapshots "
            "(< 0: use PADDLE_EDL_SAVE_CHECKPOINT_INTER env)")

# incubate/auto_checkpoint.py + distributed/checkpoint.py — rotation
# depth: newest N intact snapshots are kept, older ones deleted after a
# successful publish. 2 = checkpoint_saver.py max_num_checkpoints.
define_flag("checkpoint_keep", 2,
            "intact snapshots kept by checkpoint rotation")

# distributed/elastic.py StragglerTracker — consecutive /clusterz
# straggler verdicts against the same rank before it is marked for
# eviction (checkpointed around + world renegotiated). One slow tick
# must not evict a healthy rank; a persistently slow one must not drag
# the whole job to its pace.
define_flag("eviction_threshold", 3,
            "consecutive straggler verdicts before a rank is evicted "
            "from the training world")

# distributed/chaos.py — fault-injection directives for chaos testing,
# ';'-separated `action:key=val,key=val` (actions kill|exit|delay|raise;
# points step|mid_save). E.g. 'kill:point=step,step=3,rank=1;'
# 'delay:point=step,step=2,ms=250;kill:point=mid_save,n=2'. Empty (the
# default) disables — the hooks are a flag-read when idle. Consumed at
# the train-step boundary (hapi.Model.fit, fixtures) and inside the
# checkpoint writer (between data files and manifest publish).
define_flag("fault_injection", "",
            "chaos directives: 'action:k=v,...;...' with actions "
            "kill|exit|delay|raise at points step|mid_save (empty: off)")

# runtime/compiled.py CompiledStore — ONE bound for every compiled-
# executable LRU cache (executor jit entries, TrainStepFn per-batch-
# signature executables, generation prefill/decode programs). Before the
# shared runtime each site hardcoded its own (executor 128 vs TrainStepFn
# 16 — many batch signatures silently evicted/recompiled under the small
# one). Evictions bump `<label>::cache_evict` so an undersized cache
# shows in the counters instead of as mystery recompiles. Read at insert
# time, so set_flags applies to live stores.
define_flag("compiled_cache_capacity", 128,
            "LRU bound shared by every compiled-executable cache "
            "(executor / train step / generation); evictions counted "
            "per store as <label>::cache_evict")

# optimizer/__init__.py Momentum + ops/pallas/optimizer_update.py — run
# the momentum + L2 weight-decay parameter update as one pallas kernel on
# TPU for parameters whose [rows, 128] view is free (vectors, matrices,
# pointwise conv weights; a predicate on shape and dtype). Weights with a
# spatial extent, and everything off the TPU, take the jnp fallback: the
# identical expression, which XLA fuses into a compiled step in the
# weight's own layout. So the flag is numerically free to leave on; off,
# nothing goes to the kernel.
define_flag("use_fused_optimizer", True,
            "fused pallas momentum/weight-decay parameter update on TPU "
            "for parameters whose [rows, 128] view is free (jnp fallback "
            "for the rest and elsewhere; identical math)")

# nn/transformer.py + ops/pallas/layernorm_residual.py — fuse the
# residual-add + LayerNorm pair (the post-norm transformer's hottest
# pointwise chain) into one pallas kernel on TPU: one VMEM pass computes
# x+residual, the f32 statistics, and the affine output. The jnp
# fallback is the same math XLA fuses today.
define_flag("use_fused_layernorm", True,
            "fused pallas residual-add + LayerNorm on TPU "
            "(jnp fallback elsewhere; identical math)")

# ops/quantize_kernels.py matmul_int8/mul_int8 + ops/pallas/
# int8_matmul.py — run the int8×int8→int32 contraction of deployed int8
# inference programs as a pallas MXU kernel on TPU. The jnp fallback is
# the identical dot_general (integer math: bit-equal), so the flag never
# changes numerics — same discipline as the other pallas gates.
define_flag("use_int8_matmul", True,
            "pallas int8 matmul kernel for deployed int8 programs on TPU "
            "(jnp int8 dot_general fallback elsewhere; bit-equal)")

# framework/jit.py TrainStepFn/ShardedTrainStep + distributed/
# quantized.py — EQuARX-style quantized DP gradient all-reduce: gradients
# cross the wire as int8 with per-block f32 scales (alltoall the
# quantized shards, dequant-accumulate, requantize, all-gather), cutting
# gradient-sync wire bytes ~4x (certified by the collective/<prim>/
# traced_algo_bytes ledger and ici_bus_util gauges). Read at train-step
# CONSTRUCTION (like donate): set it before building the step.
define_flag("quantized_allreduce", False,
            "int8-with-per-block-scales DP gradient all-reduce "
            "(~4x fewer gradient-sync wire bytes; read at step build)")

# io/dataloader.py _DevicePrefetcher — issue the NEXT batches' host
# fetch + jax.device_put from a background thread while the consumer's
# step runs (double-buffered h2d/compute overlap). Off: the legacy
# synchronous refill (the consumer's __next__ pays the upstream parse
# and the device_put enqueue inline).
define_flag("io_prefetch_overlap", True,
            "overlap dataloader H2D transfers with compute via a "
            "background prefetch thread (double-buffered)")

# tuning/ + ops/pallas/* — the kernel autotuner's dispatch policy.
# Every gated pallas kernel resolves its schedule (block rows/cols,
# tile geometry) through tuning.resolve():
#   off    — defaults only, zero tuner work (no cache load, no counters)
#   cached — tuned params on a cache hit, defaults on a miss; NO search
#   search — like cached, plus misses enqueue a background per-
#            device_kind search whose winner applies at the next
#            CompiledStore compile of the signature (never inline)
# Winners live in memory, and in a file only when a path is handed to
# tuning.reset_tuning_cache(path); runtime/compiled.py folds the schedule
# token into every compile identity so a swap is a clean recompile.
define_flag("kernel_autotune", "cached",
            "pallas kernel schedule policy: off | cached | search "
            "(search tunes misses in the background, offline-style)")

# monitor/registry.py — hard per-family cardinality bound for labeled
# metric children (``metric.labels(**dims)``). Once a family holds this
# many distinct label sets, every NEW set collapses into one shared
# series whose label values are all "other" (plus a single
# metric_series_overflow flight event), so an unbounded dimension (a
# hostile tenant header) can never grow registry memory without limit.
# Read at labels() time, so set_flags applies to live families.
define_flag("metrics_max_series", 64,
            "max distinct label sets per metric family before new sets "
            "collapse into the shared 'other' overflow series")

# monitor/slo.py — declarative serving objectives installed by every
# fleet entrypoint (serving/backend.py, serving/router.py) via
# install_from_flags(). ';'-separated entries, '|'-separated fields:
#   name|selector|threshold_ms=250|target=0.99|window_s=3600
#   name|bad_selector|error_ratio=<total_selector>|target=0.999
# selector grammar: metric or metric{k=v,k2=v2} (labels subset-match
# the family's labeled series). Empty (default): no objectives.
define_flag("slo_objectives", "",
            "SLO definitions 'name|selector|k=v|...' joined by ';' "
            "(fields: threshold_ms | error_ratio, target, window_s, "
            "alert_burn); empty disables")

# monitor/slo.py SLOEngine — period of the background good/total
# sampler the burn-rate windows are computed over. Shorter intervals
# sharpen the fast (5m-style) window at the cost of more registry
# snapshots; the engine keeps at most one slow window of samples.
define_flag("slo_sample_interval_s", 10.0,
            "seconds between SLO engine good/total samples of the "
            "metric registry")

# monitor/slo.py + serving/scaler.py — burn-rate alert threshold (the
# Google-SRE multi-window convention: 14.4x burn consumes a 30-day
# budget in ~2 days). An SLO alerts when BOTH its fast and slow
# windows burn at/above this; the autoscaler treats the same
# double-window-confirmed burn as scale-up pressure.
define_flag("slo_burn_alert", 14.4,
            "error-budget burn-rate multiple at which an SLO alerts "
            "(both windows) and the autoscaler sees up-pressure")

# monitor/goodput.py — lifetime training goodput/badput ledger. The
# directory holds the GOODPUT.json sidecar (atomic tmp->rename + CRC,
# the checkpoint publication discipline), so a kill -9 restart CONTINUES
# the same lifetime accounting instead of starting a fresh wall clock.
# Empty (default): ledger off — zero step-path cost.
define_flag("goodput_dir", "",
            "directory for the training goodput ledger's GOODPUT.json "
            "sidecar; empty disables the ledger")

# How often the ledger re-publishes its sidecar, piggybacked on step
# commits (0 = every committed step — what the goodput smoke uses so the
# kill -9 window is one step wide). The ledger also publishes after
# every checkpoint publication, so the sidecar is never staler than the
# newest snapshot a resume could land on.
define_flag("goodput_publish_interval_s", 30.0,
            "seconds between goodput sidecar publications (piggybacked "
            "on step commits; 0 publishes every step)")

# Optional goodput-ratio SLO driven through monitor/slo.py's burn-rate
# engine: error mode over goodput/badput_seconds_total (bad) vs
# goodput/wall_seconds_total (total), i.e. the objective is
# "goodput >= target". 0 (default): no objective installed.
define_flag("goodput_slo_target", 0.0,
            "goodput-ratio SLO target (e.g. 0.9) installed through the "
            "burn-rate engine; 0 disables")

# models/resnet.py + nn/layers.py fused_conv_bn_relu + ops/pallas/
# conv_bn_relu.py — fuse the vision path's POINTWISE conv -> batch_norm
# -> relu triples (1x1, stride 1, no padding: each bottleneck block's
# first conv) into pallas kernels on TPU: the input is the matmul's
# left operand as it stands, the contraction runs as a tiled MXU matmul
# and the BN affine + relu apply in VMEM (eval: one pass; training:
# matmul+stats pass, then normalize+relu pass). A triple whose conv has
# a spatial extent (3x3, 7x7, strided, padded) runs the fallback, flag
# on or off: its im2col traffic cost more than XLA's convolution does
# (PERF.md Findings PR 32). The fallback calls the IDENTICAL
# conv2d/batch_norm/relu op kernels in the same order, so the flag
# never changes numerics off-TPU — the same discipline as the PR-10
# fused kernels.
define_flag("use_fused_conv_bn", True,
            "fused pallas conv+batch_norm+relu on TPU for the vision "
            "path's pointwise (1x1, stride 1, unpadded) convs; every "
            "other conv and every other platform runs the identical "
            "unfused op sequence")

