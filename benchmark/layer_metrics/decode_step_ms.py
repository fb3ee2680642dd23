"""Median device time of one run of the decode program (one token for
every slot), from the trace's `XLA Modules` line. The program's own
`generation::decode` span closes after the dispatch, before the tokens
are fetched, so it times the enqueue (6.5 ms against 66.6 ms on the
device, my chip run, PR 23) and is not read here."""
import statistics


def read(ctx):
    runs = ctx["trace"].module_runs("decode")
    return statistics.median(runs) / 1e6 if runs else None
