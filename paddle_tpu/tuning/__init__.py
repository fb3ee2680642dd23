"""paddle_tpu.tuning — per-device kernel schedule search with a
persistent tuning cache (ROADMAP item 3, the TVM-spirit autotuner).

Three pieces, one contract:

- :mod:`.schedule` — every gated pallas kernel registers a declarative
  :class:`ScheduleSpace` (block rows/cols, tile geometry, unroll;
  today's hardcoded geometry as the default point); call sites ask
  :func:`resolve` for their schedule. Miss -> byte-identical defaults;
  hit -> the tuned winner. Never an inline search on a hot path.
- :mod:`.tuner` — :class:`KernelTuner` measures candidates offline
  (best-of-N timed jitted calls, value-fetch barrier, invalid points
  pruned before any compile) per ``device_kind``; under
  ``FLAGS_kernel_autotune=search`` resolve-misses enqueue background
  tuning.
- :mod:`.cache` — winners live in memory, and in a versioned JSON
  file when ``reset_tuning_cache(path)`` names one, keyed by (kernel,
  device_kind, shape-bucket, dtype, schedule-space version); corrupt /
  wrong-version / foreign-device content degrades to defaults with one
  warning + ``autotune::cache_reject``, never a crash.
  :func:`schedule_token` couples the cache to ``runtime/compiled.py``:
  every compile identity embeds it, so a tuned swap-in is a clean
  recompile, not a stale-trace hazard.
"""
from .cache import (  # noqa: F401
    CACHE_FILE_NAME,
    CACHE_SCHEMA_VERSION,
    TuningCache,
    reset_tuning_cache,
    schedule_token,
    tuned_table,
    tuning_cache,
)
from .schedule import (  # noqa: F401
    ScheduleSpace,
    next_pow2,
    register_schedule,
    resolve,
    schedule_space,
    shape_bucket,
    spaces,
)
from .tuner import (  # noqa: F401
    KernelTuner,
    TuneResult,
    drain_background,
    enqueue_search,
    pending_searches,
    tune,
)

__all__ = [
    "CACHE_FILE_NAME",
    "CACHE_SCHEMA_VERSION",
    "KernelTuner",
    "ScheduleSpace",
    "TuneResult",
    "TuningCache",
    "drain_background",
    "enqueue_search",
    "next_pow2",
    "pending_searches",
    "register_schedule",
    "reset_tuning_cache",
    "resolve",
    "schedule_space",
    "schedule_token",
    "shape_bucket",
    "spaces",
    "tune",
    "tuned_table",
    "tuning_cache",
]
