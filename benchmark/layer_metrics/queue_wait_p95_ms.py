"""95th percentile of the program's `serving::queue_wait` spans: submit
to the moment the scheduler picks the request for a slot."""
from benchmark.lib import common


def read(ctx):
    waits = [s["dur_ms"] for s in ctx["spans"].requests
             if s["name"] == "serving::queue_wait"]
    return common.pctl(waits, 95) if waits else None
