"""KV cache in use against reserved: the time-weighted mean over the
window of the scheduler's `serving::kv_live_tokens` samples (prompt +
tokens so far, summed over the live slots) over slots x `cache_len`
positions, which the ring layout reserves whole."""
import os

from benchmark.lib import common


def read(ctx):
    cell = ctx["cell"]
    here = os.path.join(cell.dir, "layer_metrics")
    tl = common.load_module(os.path.join(here, "host_gap_ms.serve.py"))
    sb = common.load_module(os.path.join(here, "slots_busy_pct.sched.py"))
    live = sb.counter_mean("serving::kv_live_tokens", *tl.window_ns(ctx))
    if live is None:
        return None
    return 100.0 * live / (ctx["res"]["slots"]
                           * cell.cfg["engine"]["cache_len"])
