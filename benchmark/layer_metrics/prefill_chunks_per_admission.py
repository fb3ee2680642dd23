"""Prefill programs enqueued per admission: the program's
`generation::prefill_chunks` samples in the window (one a prefill program
enqueued, `[the program's number within its prompt, 1 if it is the
prompt's last]`), the numbers of the last ones averaged: each is how many
programs its prompt took. 1.0 where every prompt goes in whole through
its ladder bucket; above it where the engine admits long prompts a chunk
at a time between decode steps (a cache all of whose kinds keep K/V
rings), and then the gap a live stream sees at an admission is a decode
step and ONE of these programs, not the prompt's whole prefill. A
CONTROL reading, not a quantity to maximise (the schema wants a
direction; `higher` says only that the chunks engaged): a prompt's length
over the engine's chunk, so about 4 in `k-exaone-236b.longdoc-overload`
and 1.0 in the cells that bypass the chunks; smaller chunks read higher
and cost capacity, since each reads the weights again (PERF.md, PR 45's
sweep). Nothing where the program takes no such samples (before PR 45),
or where no admission finished inside the window."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    took = [number for number, last in program_time.counter_values(
        "generation::prefill_chunks", *tl.window_ns(ctx)) if last]
    return sum(took) / len(took) if took else None
