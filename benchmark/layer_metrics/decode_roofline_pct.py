"""The least time one decode step could take - every weight read once
and the KV of the live slots (opcount/gpt2.py, from shapes), over the
chip's HBM bandwidth: decode at this batch is bound by memory, not by
compute - over the median device time of the decode program's runs in
the trace."""
import os
import statistics

from benchmark.lib import common


def read(ctx):
    cell, res = ctx["cell"], ctx["res"]
    runs = ctx["trace"].module_runs("decode")
    if not runs or ctx["peaks"] is None:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    live_tokens = res.get("mean_live_tokens", 0.0)
    least = max(
        oc.decode_bytes(cell.cfg, live_tokens)
        / ctx["peaks"]["hbm_bytes_per_s"],
        oc.decode_flops(cell.cfg, res["slots"])
        / ctx["peaks"]["flops_per_s"])
    return 100.0 * least / (statistics.median(runs) / 1e9)
