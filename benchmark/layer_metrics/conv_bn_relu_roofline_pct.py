"""Roofline share of the five fused conv+bn+relu kernels (`conv_*`, found
by kernel name): per step the least time the fused convolutions need,
forward and backward (opcount/resnet.py: each conv the larger of its
operations over peak and its bytes over bandwidth), times the steps in
the trace (one `conv_bn_relu` call per fused triple per step), over the
kernels' device time."""
import os

from benchmark.lib import common

KERNELS = ("conv_mm_stats", "conv_centered_sumsq", "conv_bn_relu",
           "conv_bn_bwd_partials", "conv_bn_bwd_dco")


def read(ctx):
    cell, tr = ctx["cell"], ctx["trace"]
    if ctx["peaks"] is None:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    triples, least_step = oc.fused_conv_least_seconds(
        cell.cfg, cell.mix, ctx["peaks"])
    # under jvp / transpose the instruction is named around the kernel's
    # name: `jvp_conv_bn_relu_.3`
    fwd = tr.count_by(lambda n, x: "conv_bn_relu" in n)
    spent = tr.time_by(lambda n, x: any(k in n for k in KERNELS)) / 1e9
    if not fwd or not spent:
        return None
    return 100.0 * (fwd / triples) * least_step / spent
