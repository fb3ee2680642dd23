"""Roofline share of the decode step's reads for block-sparse attention:
the ring rows the traced steps had to read by the selection's own
rules - the chosen blocks' K and V rows and the pooled keys scored, all
live rows for a slot under `dense_len`: the first place of the program's
`generation::kv_rows_read` samples inside the device-traced interval
(the steps whose device time is read; this cell's trace lies behind the
iteration that fills the slots, past the measured window), in K/V rows
(a pooled row counts half), the mean over the steps - times a row's bytes
(opcount/minicpm_sala.py `kv_row_bytes`), over the chip's HBM bandwidth
(one query a slot: bound by the rows read, not by operations), over the
device time of the selection, the pooled ring's update and the block
attention (`is_sparse_select_op`, `is_sparse_attend_op`) inside the
decode program's runs, per run. The count is of the work the
mathematics asks, whatever implements it: a gather that copies the rows
and reads them again reads under 50, a kernel that streams them once can
near 100. Nothing where the program has no such counter or the
configuration no such predicates."""
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
                                       *tl.traced_ns(ctx))
    if not rows or not hasattr(oc, "is_sparse_select_op"):
        return None
    got = program_time.time_inside(
        ctx["trace"], lambda n, x: oc.is_sparse_select_op(x, cell.cfg)
        or oc.is_sparse_attend_op(x, cell.cfg), "decode")
    if got is None or not got[0]:
        return None
    per_step = sum(r[0] for r in rows) / len(rows)
    least = per_step * oc.kv_row_bytes(cell.cfg) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (got[0] / got[1] / 1e9)
