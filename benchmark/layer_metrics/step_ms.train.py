"""Median time of one training step: the benchmark's span around a
group of `sync_every` step calls closed by block_until_ready, divided by
the group's size."""
import statistics


def read(ctx):
    res = ctx["res"]
    if not res.get("group_ms"):
        return None
    return statistics.median(res["group_ms"]) / res["every"]
