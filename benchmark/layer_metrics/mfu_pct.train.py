"""Model FLOP/s utilisation: the operations the forward and backward
passes need per sample (opcount/, from shapes; recomputation does not
count) x samples per second, over chips x the chip's peak. The rate is
the step's batch over the median step time of this run (`step_ms.train`):
the traced run's own samples/s has the profiler's start and stop in it."""
import os
import statistics

from benchmark.lib import common


def read(ctx):
    cell, res = ctx["cell"], ctx["res"]
    if ctx["peaks"] is None or not res.get("group_ms"):
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    step_s = statistics.median(res["group_ms"]) / res["every"] / 1e3
    rate = res["samples_per_step"] / step_s
    return 100.0 * oc.train_flops_per_sample(cell.cfg, cell.mix) * rate / (
        cell.chips * ctx["peaks"]["flops_per_s"])
