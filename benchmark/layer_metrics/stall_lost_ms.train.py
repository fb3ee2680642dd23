"""Time the training loop lost to stalls, by the program's own account: the
sum of `lost_ms` over its `train_stall` flight events whose `t_ns` lies
inside the window. A train step leaves one for a call-to-call interval
that passes a second and stands four times clear of the intervals before
it (the regular interval that carries the loss fetch of `sync_every`
steps leaves none), with the phase that held it: `outside` is the
caller's time (here the benchmark's `block_until_ready`), `runtime::launch`
the executable's call. Each event counted goes on a line of its own. The
window of a training kind is on perf_counter already. 0.0 for a run that
left none; None for a program that writes no such record.

A training kind starts and stops the device trace from the loop's own
thread, between two step groups, and `stop_trace` collects for 3-4 s
(my chip runs, PR 38): the step sees that as `outside` and rightly leaves
a record. It is the harness's doing and exists in a traced run only, so
an event whose held phase covers the first or the last device event (the
harness leaves no mark of the two calls themselves: `loop_stall_max_ms`
goes by the same two instants) is printed and not summed."""
import os

from benchmark.lib import common


def read(ctx):
    here = os.path.join(ctx["cell"].dir, "layer_metrics")
    serve = common.load_module(os.path.join(here, "stall_lost_ms.serve.py"))
    tl = common.load_module(os.path.join(here, "host_gap_ms.serve.py"))
    w0, w1 = (t * 1e9 for t in ctx["res"]["window"])
    return serve.lost_ms("train_stall", w0, w1, not_ours=tl.traced_ns(ctx))
