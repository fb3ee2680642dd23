"""Device idle share of the traced window (lib/tracing.py `idle_pct`);
in a serving cell it moves the gaps between tokens (`itl_p95_ms`)."""


def read(ctx):
    return ctx["trace"].idle_pct()
