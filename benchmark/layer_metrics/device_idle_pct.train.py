"""Device idle share of the traced window (lib/tracing.py `idle_pct`);
in a training cell it moves the step rate (`train_samples_per_s`)."""


def read(ctx):
    return ctx["trace"].idle_pct()
