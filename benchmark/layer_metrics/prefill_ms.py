"""Median device time of one run of a prefill program (one prompt through
its bucket), from the trace's `XLA Modules` line; the program's
`generation::prefill` span times only the enqueue."""
import statistics


def read(ctx):
    runs = ctx["trace"].module_runs("prefill")
    return statistics.median(runs) / 1e6 if runs else None
