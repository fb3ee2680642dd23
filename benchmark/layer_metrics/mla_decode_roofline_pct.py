"""Roofline share of the absorbed latent attention in decode: the least
time a step's attention could take over the latent rows it had to read -
the latent layers' part of the program's `generation::kv_rows_read`
samples in the window (live rows over slots and attentions, the mean
over the steps) - which is the larger of the rows' bytes (1,152 B a row,
read once for all 64 heads) over the chip's HBM bandwidth and their
operations (64 heads x (576 + 512) x 2 a row) over its peak
(opcount/longcat_flash.py `mla_decode_least_s`), over the device time of
the latent-attention operations (`is_mla_op`) inside the decode
program's runs, per run. It counts live rows only: the ring layout reads
a ring whole whatever is live, so this is at most the live share of the
rings. Nothing where the program has no such counter or the
configuration's opcount no such test."""
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
    rows = [r[2] for r in program_time.counter_values(
        "generation::kv_rows_read", *tl.window_ns(ctx)) if len(r) > 2]
    if not rows or not hasattr(oc, "is_mla_op"):
        return None
    got = program_time.time_inside(
        ctx["trace"], lambda n, x: oc.is_mla_op(x, cell.cfg), "decode")
    if got is None or not got[0]:
        return None
    least = oc.mla_decode_least_s(cell.cfg, sum(rows) / len(rows),
                                  ctx["peaks"])
    return 100.0 * least / (got[0] / got[1] / 1e9)
