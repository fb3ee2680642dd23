"""Roofline share of the decode step's reads of the K/V rings: the rows
a step's attention had to read - the program's `generation::kv_rows_read`
samples in the window, full-length layers' live rows plus the window
layers' `min(pos + 1, 128)` a slot, the mean over the steps - times a
row's bytes (opcount/k_exaone.py `kv_row_bytes`), over the chip's HBM
bandwidth (one query a slot: bound by the rows read, not by
operations), over the device time of the attention operations
(`is_full_attn_op`, `is_window_attn_op`) inside the decode program's
runs, per run. The ring layout reads a ring whole whatever is live, so
this is at most the live share of the rings. Nothing where the program
has no such counter."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    if ctx["peaks"] is None:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    rows = program_time.counter_values("generation::kv_rows_read",
                                       *tl.window_ns(ctx))
    if not rows or not hasattr(oc, "is_full_attn_op"):
        return None
    got = program_time.time_inside(
        ctx["trace"], lambda n, x: oc.is_full_attn_op(x, cell.cfg)
        or oc.is_window_attn_op(x, cell.cfg), "decode")
    if got is None or not got[0]:
        return None
    per_step = sum(sum(r) for r in rows) / len(rows)
    least = per_step * oc.kv_row_bytes(cell.cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (got[0] / got[1] / 1e9)
