"""Median `train::shard_batch` span inside the window: the host's side of
handing one host batch to the device (`jnp.asarray` of each array) in
`TrainStepFn.__call__`. The window of a training kind is on
perf_counter already."""
import statistics


def median_ms(ctx, name):
    w0, w1 = (t * 1e9 for t in ctx["res"]["window"])
    durs = [e - s for n, s, e in ctx["spans"].host
            if n == name and w0 <= s <= w1]
    return statistics.median(durs) / 1e6 if durs else None


def read(ctx):
    return median_ms(ctx, "train::shard_batch")
