"""Roofline share of the routed experts' grouped products in decode: the
bytes of expert weights one decode step had to read - per expert layer
the held experts that got a token, the mean of the program's
`moe::experts_hit` samples in the window, times an expert's three
matrices (opcount/solar_open2.py) - over the chip's HBM bandwidth (32
tokens: bound by the weights read, not by operations), over the device
time of the `ragged-dot` kernels inside the decode program's runs, per
run."""
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
    hits = program_time.counter_values("moe::experts_hit",
                                       *tl.window_ns(ctx))
    got = program_time.time_inside(ctx["trace"], oc.is_expert_kernel,
                                   "decode")
    if not hits or got is None or not got[0]:
        return None
    per_step = sum(sum(h) for h in hits) / len(hits)  # over the layers
    least = oc.expert_bytes(cell.cfg, per_step) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (got[0] / got[1] / 1e9)
