"""The traced run's sources, and the reduction from the profiler's
`.xplane.pb` to numbers. The yardstick: later PRs cannot change it.

Device events come from `jax.profiler.ProfileData` (nothing but jax is
needed to read a trace). On a TPU each chip is a plane `/device:TPU:<n>`;
its line `XLA Ops` holds one event per executed HLO instruction (nested
where a while or a call wraps others), `XLA Modules` one per program.
Host spans are the program's own `RecordEvent`s (`profiler.host_events`)
and its request traces (`monitor.tracing` store); a `TraceAnnotation`
written here ties the profiler's clock to `time.perf_counter_ns`."""
from __future__ import annotations

import glob
import os
import shutil
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "bench_clock"


def read_xplane(path, rehearsal=False):
    """{"devices": {plane name: [(name, start_ns, dur_ns, text)]},
    "marks": [(name, start_ns, dur_ns)], "modules": {plane name: [(name,
    start_ns, dur_ns)]}}. On a TPU an op event is named by its whole HLO
    instruction, `%fusion.3 = f32[...] fusion(...)`: `name` is the
    instruction's own name (`fusion.3`; a pallas kernel's is its `name=`,
    `layernorm_residual_fwd.1`), `text` the whole instruction. With ``rehearsal`` (tests off the chip only)
    the CPU backend's op events stand in for a device."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, marks, modules = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE)
            if line is None:
                continue
            if MODULES_LINE in lines:
                modules[plane.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in lines[MODULES_LINE].events]
            devices[plane.name] = [
                (op_name(e.name), float(e.start_ns), float(e.duration_ns),
                 e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == CLOCK_MARK:
                        marks.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    if rehearsal and not devices:
        evs = [(e.name, float(e.start_ns), float(e.duration_ns), "")
               for plane in data.planes if plane.name.startswith("/host:")
               for ln in plane.lines for e in ln.events
               if any(k == "hlo_op" for k, _ in e.stats)]
        if evs:
            devices["/host:CPU (rehearsal)"] = evs
    return {"devices": devices, "marks": marks, "modules": modules}


def op_name(event_name):
    """`fusion.3` from `%fusion.3 = f32[8]{0} fusion(...)`."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0].strip()
    return event_name


def is_mosaic(name, text):
    return 'custom_call_target="tpu_custom_call"' in text


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """[(name, self_ns, text)]: each event's duration less what events
    nested inside it cover, so that a while loop and its body are not
    both counted."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [end, index]
    for name, s, d, text in evs:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0] + 1e-3:
            out[stack[-1][1]][1] -= d
        out.append([name, d, text])
        stack.append([s + d, len(out) - 1])
    return [(n, max(t, 0.0), x) for n, t, x in out]


class DeviceTrace:
    """One traced window, reduced. ``window_ns`` is the span from the
    first to the last device event over all chips."""

    def __init__(self, parsed):
        self.devices = parsed["devices"]
        self.marks = parsed["marks"]
        self.modules = parsed.get("modules", {})
        starts = [e[1] for evs in self.devices.values() for e in evs]
        ends = [e[1] + e[2] for evs in self.devices.values() for e in evs]
        self.t0 = min(starts) if starts else 0.0
        self.t1 = max(ends) if ends else 0.0
        self.window_ns = self.t1 - self.t0

    def busy_ns(self, device=None):
        """Union of device-op intervals, averaged over the chips."""
        per = [union_ns([(s, s + d) for _, s, d, _ in evs])
               for name, evs in self.devices.items()
               if device is None or name == device]
        return sum(per) / len(per) if per else 0.0

    def idle_pct(self):
        """Share of the traced window in which no operation ran on the
        device: 1 - busy over the window, mean of the chips."""
        if not self.window_ns:
            return None
        return 100.0 * (1.0 - self.busy_ns() / self.window_ns)

    def time_by(self, match, device=None):
        """Sum of self times of events that ``match(name, text)``,
        averaged over the chips."""
        per = []
        for name, evs in self.devices.items():
            if device is not None and name != device:
                continue
            per.append(sum(t for n, t, x in self_times(evs) if match(n, x)))
        return sum(per) / len(per) if per else 0.0

    def module_runs(self, part):
        """Durations (ns) of the runs of programs whose name contains
        ``part``, on the first chip."""
        for runs in self.modules.values():
            return [d for n, _, d in runs if part in n]
        return []

    def count_by(self, match):
        per = [sum(1 for n, _, _, x in evs if match(n, x))
               for evs in self.devices.values()]
        return max(per) if per else 0

    def top_ops(self, k=10):
        """[[name, seconds]] of the k device operations that took most
        self time on the busiest chip, instances of one name summed
        (trailing `.123` instance numbers dropped)."""
        best = {}
        for evs in self.devices.values():
            agg = {}
            for n, t, _ in self_times(evs):
                key = n.rstrip("0123456789").rstrip(".") or n
                agg[key] = agg.get(key, 0.0) + t
            if sum(agg.values()) > sum(best.values()):
                best = agg
        rows = sorted(best.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in rows]

    def idle_gaps(self, host_spans, clock_offset_ns, k=10):
        """[[what the host was doing, seconds]]: the idle time of the
        first chip, each gap attributed to the host span (name, start_ns,
        end_ns on perf_counter) that covers at least half of it, summed
        by name."""
        if not self.devices:
            return []
        evs = next(iter(self.devices.values()))
        busy = merged([(s, s + d) for _, s, d, _ in evs])
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
                if b[0] - a[1] > 20e3]  # 20 us and longer
        spans = sorted((s - clock_offset_ns, e - clock_offset_ns, n)
                       for n, s, e in host_spans)
        agg = {}
        for gs, ge in gaps:
            best, cover = "no host span", 0.0
            for s, e, n in spans:
                if s >= ge:
                    break
                c = min(ge, e) - max(gs, s)
                if c > cover and c >= 0.5 * (ge - gs):
                    best, cover = n, c
            agg[best] = agg.get(best, 0.0) + (ge - gs)
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in rows]


class Spans:
    """What a run collects besides the device trace: the program's host
    spans and request spans, the benchmark's own spans, the clock mark."""

    def __init__(self, tmp):
        self.tmp = tmp
        self.trace_dir = os.path.join(tmp, "trace")
        self.host = []        # (name, start_ns, end_ns) on perf_counter
        self.requests = []    # program request spans {"name", "dur_ms"}
        self.mark_ns = None
        self._program = False

    # -- the program's spans (traced run only) --------------------------
    def start_program_spans(self):
        from paddle_tpu import profiler
        from paddle_tpu.flags import set_flags

        set_flags({"trace_sample_slowest_k": 1000000,
                   "trace_store_capacity": 1000000})
        profiler.start_profiler(state="CPU")  # host spans only
        self._program = True

    def reset(self):
        if self._program:
            from paddle_tpu import profiler
            from paddle_tpu.monitor import tracing

            profiler.reset_profiler()
            tracing.reset_store()
        self.host = []

    def collect_program_spans(self):
        from paddle_tpu import profiler
        from paddle_tpu.monitor import tracing

        for ev in profiler.host_events():
            s = ev["ts"] * 1e3
            self.host.append((ev["name"], s, s + ev["dur"] * 1e3))
        st = tracing.store()
        for row in st.summaries():
            got = st.get(row["trace_id"])
            for sp in (got or {}).get("spans", ()):
                self.requests.append({"name": sp["name"],
                                      "dur_ms": sp["dur_ms"],
                                      "attrs": sp.get("attrs", {})})
        profiler.stop_profiler()

    def span(self, name, start_ns, end_ns):
        self.host.append((name, start_ns, end_ns))

    # -- the device trace ------------------------------------------------
    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self.mark_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            time.sleep(0.001)

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def trace_between(self, start_monotonic, seconds):
        """Trace ``seconds`` of a window that another process drives."""
        time.sleep(max(start_monotonic - time.monotonic(), 0.0))
        self.start_trace()
        time.sleep(seconds)
        self.stop_trace()

    def device_trace(self, rehearsal=False):
        path = newest_xplane(self.trace_dir)
        if path is None:
            return None, 0.0
        tr = DeviceTrace(read_xplane(path, rehearsal))
        offset = 0.0
        if tr.marks and self.mark_ns is not None:
            offset = self.mark_ns - tr.marks[0][1]
        return tr, offset
