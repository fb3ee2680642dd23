"""Two things the readers of `solar-open2-250b` share and
`lib/tracing.py` does not have: the self time of the device events that
match a predicate INSIDE the runs of one program (a decode step's share,
not the window's), and the values of a program counter's samples.

A TPU trace names an `XLA Ops` event by its whole HLO instruction and
carries no `op_name` path (the events' statistics are offsets and
durations only: my chip run, PR 27), so a `jax.named_scope` cannot be
read back from it; predicates go by kernel name and operand shapes
(opcount/<config>.py)."""
from __future__ import annotations

import bisect

from benchmark.lib import tracing


def time_inside(trace, match, module):
    """(self time in ns of the first chip's events that ``match(name,
    text)`` and lie inside runs of programs whose name contains
    ``module``, the number of those runs); None where the trace has no
    such run."""
    if not trace.devices:
        return None
    plane = next(iter(trace.devices))
    runs = sorted((s, s + d) for n, s, d in trace.modules.get(plane, ())
                  if module in n)
    if not runs:
        return None
    starts = [a for a, _ in runs]
    evs = trace.devices[plane]
    # self_times gives its rows in this order
    ordered = sorted(evs, key=lambda e: (e[1], -e[2]))
    total = 0.0
    for (name, self_ns, text), (_, s, d, _) in zip(
            tracing.self_times(evs), ordered):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= runs[i][1] + 1e3 and match(name, text):
            total += self_ns
    return total, len(runs)


def counter_values(name, t0_ns, t1_ns):
    """The values of the program's counter samples of this name inside
    [t0_ns, t1_ns] on perf_counter_ns, in time order; [] where the
    program has no such samples."""
    from paddle_tpu import profiler

    samples = getattr(profiler, "counter_samples", None)
    if samples is None:
        return []
    return [ev["args"]["value"] for ev in samples()
            if ev["name"] == name and t0_ns <= ev["ts"] * 1e3 <= t1_ns]
