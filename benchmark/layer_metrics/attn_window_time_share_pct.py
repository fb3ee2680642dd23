"""Share of the device's busy time spent on sliding-window attention:
operations on a tensor of a window ring's shape `[slots or 1, 8, 128,
128]`, of a decode step's scores over it, or of the banded prefill's
blocks of 512 queries (opcount/k_exaone.py `is_window_attn_op`); as
`attn_full_time_share_pct`, a lower bound of the program's
`attn_window` scope."""
import os

from benchmark.lib import common


def read(ctx):
    full = common.load_module(os.path.join(
        ctx["cell"].dir, "layer_metrics", "attn_full_time_share_pct.py"))
    return full.read(ctx, "is_window_attn_op")
