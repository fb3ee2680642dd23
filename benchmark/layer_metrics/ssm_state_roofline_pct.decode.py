"""Roofline share of the decode step's pass over the recurrent states:
the least time to read and write every slot's float32 states once -
slots x the state layers' bytes a slot (opcount/nemotron_h.py
`state_bytes_per_slot`, the convolution tails aside) x 2, over the
chip's HBM bandwidth: one token a slot is bound by the state moved, not
by operations - over the device time of the state operations
(`is_state_op`) inside the decode program's runs, per run. A step that
passes over the state twice (the update, then the read-out) reads at
most 50."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    if ctx["peaks"] is None:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    if not hasattr(oc, "is_state_op"):
        return None
    got = program_time.time_inside(
        ctx["trace"], lambda n, x: oc.is_state_op(x, cell.cfg), "decode")
    if got is None or not got[0]:
        return None
    least = 2.0 * ctx["res"]["slots"] \
        * oc.state_bytes_per_slot(cell.cfg, tail=False) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (got[0] / got[1] / 1e9)
