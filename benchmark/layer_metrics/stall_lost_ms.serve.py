"""Time the serving loop lost to stalls, by the program's own account: the
sum of `lost_ms` over its `generation_stall` flight events whose `t_ns`
lies between the window's first instant and the end of the drain (the
server is stopped when the drain ends, so none is later). The scheduler
leaves one such event for every iteration over a second, with the longest
single call or host phase in it, what that usually takes, and `lost_ms` =
the time over the usual where the phase took over a second and over four
times its usual (else 0: a first iteration of 32 honest admissions loses
nothing). The profiler's spans are not needed: the events are written
with it off too. Every event counted goes on a line of its own before the
result line, with the evidence of whose time it was. 0.0 for a run that
left none; None for a program that writes no such record.

Also `lost_ms`, which `stall_lost_ms.train` loads from here."""
import os
import time

from benchmark.lib import common


def line(ev, w0_ns):
    alloc = (f"{ev['bytes_in_use_before']} -> {ev['bytes_in_use']}"
             if "bytes_in_use" in ev and "bytes_in_use_before" in ev
             else "-")
    return (f"stall: {(ev['t_ns'] - w0_ns) / 1e9:.3f} s "
            f"{ev.get('program', '-')} {ev['held_phase']} "
            f"{ev['held_ms']} ms (usual {ev['usual_ms']}) lost "
            f"{ev['lost_ms']} held_by {ev['held_by']} cpu "
            f"{ev.get('thread_cpu_ms', '-')}/{ev.get('process_cpu_ms', '-')}"
            f" ms run_delay {ev.get('run_delay_ms', '-')} steal "
            f"{ev.get('steal_ms', '-')} gc {ev.get('gc_ms', '-')} alloc "
            f"{alloc} runs {ev.get('program_runs', '-')} idle "
            f"{ev.get('program_idle_s', '-')} s")


def lost_ms(kind, w0_ns, w1_ns=None, not_ours=()):
    """Sum of `lost_ms` over this process's flight events of ``kind``
    that began in [w0_ns, w1_ns] on perf_counter_ns, each printed. An
    event whose held phase covers an instant of ``not_ours`` is printed
    and not summed (the harness's own doing: `stall_lost_ms.train`)."""
    from paddle_tpu.monitor import flight_recorder

    if not hasattr(flight_recorder, "record_stall"):
        return None  # a program whose stall events name no held phase
    recorder = flight_recorder.get_recorder()
    events = recorder.events()
    # the ring evicts its oldest events: if the oldest it still holds is
    # from inside the window, events of the window are gone
    epoch_w0 = time.time() - (time.perf_counter_ns() - w0_ns) / 1e9
    dropped = recorder.total_recorded - len(events)
    if dropped > 0 and events and events[0]["t"] > epoch_w0:
        print(f"stall_lost_ms: the flight recorder's ring (capacity "
              f"{recorder.capacity}) evicted events of this window "
              f"({dropped} gone in all): stall events may be among them "
              "and the sum may read low", flush=True)
    total = 0.0
    for ev in events:
        if ev["kind"] != kind or "t_ns" not in ev or ev["t_ns"] < w0_ns \
                or (w1_ns is not None and ev["t_ns"] > w1_ns):
            continue
        end_ns = ev["t_ns"] + ev["held_ms"] * 1e6
        if any(ev["t_ns"] <= t <= end_ns for t in not_ours):
            print(line(ev, w0_ns) + " (the trace's own start or stop: "
                  "not counted)", flush=True)
            continue
        total += ev["lost_ms"]
        print(line(ev, w0_ns), flush=True)
    return total


def read(ctx):
    tl = common.load_module(os.path.join(ctx["cell"].dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    return lost_ms("generation_stall", tl.window_ns(ctx)[0])
