"""Share of the device's busy time spent in Mosaic (pallas) kernels:
every event whose instruction is a `tpu_custom_call`."""
from benchmark.lib import tracing


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_ns()
    if not busy:
        return None
    return 100.0 * tr.time_by(tracing.is_mosaic) / busy
