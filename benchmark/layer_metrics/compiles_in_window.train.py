"""XLA compiles inside the measured window (jax's own compile event,
plus the program's unexpected-compile counters where it has them)."""


def read(ctx):
    return ctx["res"].get("counters", {}).get("compiles_in_window")
