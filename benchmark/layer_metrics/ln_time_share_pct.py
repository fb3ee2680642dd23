"""Share of the device's busy time spent in the fused residual-add +
layer-norm kernels (`layernorm_residual_fwd` and `_bwd`, found by the
kernel's name inside the instruction's). Not a roofline share: XLA parks
these kernels' operands in the chip's faster memory space (`S(1)` in the
instruction's layout) and prefetches them with async copies, so the
forward kernel reads 2.0 TB/s of "HBM bytes" and an HBM roofline reads
108 % (PERF.md, Findings, PR 23)."""


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_ns()
    if not busy:
        return None
    return 100.0 * tr.time_by(
        lambda n, x: "layernorm_residual_" in n) / busy
