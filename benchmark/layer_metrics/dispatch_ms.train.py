"""Median `train::step_dispatch` span inside the window: signature,
executable lookup and launch of one compiled train step
(`TrainStepFn._dispatch`): the enqueue, not the step."""
import os

from benchmark.lib import common


def read(ctx):
    h2d = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                          "h2d_ms.train.py"))
    return h2d.median_ms(ctx, "train::step_dispatch")
