"""How sparse the decode step is under this traffic: blocks its sparse
layers attend over blocks that hold a live row, the program's
`sparse::blocks_read` samples over its `sparse::blocks_live` samples
(each a sum over slots and sparse layers, once an iteration, counted on
the host from the slots' lengths), mean over mean, over the iterations
of the device-traced interval: the steps whose device time the roofline
beside it reads, and in a cell whose prompts go in whole the first
window-length of a run can be one iteration that fills the slots, with
no step in it. 100 where every slot is under `dense_len`; 64 of 266-470
blocks at 17-30 k live rows. Nothing where the program has no such
counters."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    window = tl.traced_ns(ctx)
    read_, live = (program_time.counter_values("sparse::" + name, *window)
                   for name in ("blocks_read", "blocks_live"))
    if not read_ or not live or not sum(live):
        return None
    return 100.0 * (sum(read_) / len(read_)) / (sum(live) / len(live))
