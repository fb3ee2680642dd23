#!/usr/bin/env python3
"""One run of one benchmark cell.

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. The last line of stdout is one JSON object:
correct, attempted, failed, metrics, device (and with --trace 1
breakdown). Everything a cell is made of is found by the names in
BENCHMARK.json: its configuration (a json file with build.py,
reference.py and check.py beside it), its traffic mix
(traffic/<mix>.json, run by traffic/kinds/<kind>.py) and its per-layer
metrics (layer_metrics/<metric>.py). This file holds no table of names."""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as nearly as python can say

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.lib import common  # noqa: E402


class Cell:
    """What a traffic kind gets: the cell's data and the harness's
    services (window mark, compile count, spans)."""

    def __init__(self, root, bench, workload, seed, seconds, trace, t0):
        self.root, self.bench = root, bench
        self.spec = next((w for w in bench["workloads"]
                          if w["name"] == workload), None)
        if self.spec is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.spec["config"])
        self.cfg_path = os.path.join(root, cfg_entry["file"])
        self.cfg_dir = os.path.dirname(self.cfg_path)
        self.cfg = common.load_json(self.cfg_path)
        self.dir = os.path.join(root, bench["paths"][0])
        self.mix = common.load_json(os.path.join(
            self.dir, "traffic", self.spec["traffic"] + ".json"))
        self.name, self.seed = workload, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.chips = int(self.spec["chips"])
        self.t0 = t0
        self.window_start = None
        self.n_compiles = 0
        self.device = None
        self.devices = None
        self.tmp = None
        self.spans = None

    def mark_window_start(self):
        self.window_start = time.perf_counter()

    def compiles(self):
        """Backend compiles this process has made so far (jax's own
        monitoring event: every XLA compile, whoever asked for it)."""
        return self.n_compiles

    def metric_names(self, kind):
        return [m["name"] for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]


def holds(value, op, limit):
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return False
    return value <= limit if op == "<=" else value >= limit


def run_cell(root, workload, seed, seconds, trace, require_chip=True,
             t0=None, out=sys.stdout):
    """Drive one run and return the result object (also printed as the
    last line of ``out``). Tests call this with require_chip=False."""
    t0 = _T0 if t0 is None else t0
    common.setup_env()
    bench = common.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = Cell(root, bench, workload, seed, seconds, trace, t0)
    import jax
    import jax.monitoring

    cell.device = common.device_info(cell.chips if require_chip else None)
    cell.devices = jax.devices()[:cell.chips]

    def on_event(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            cell.n_compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    from benchmark.lib import tracing

    os.makedirs(os.path.join(root, ".bench_tmp"), exist_ok=True)
    cell.tmp = tempfile.mkdtemp(prefix="run-", dir=os.path.join(
        root, ".bench_tmp"))
    cell.spans = tracing.Spans(cell.tmp)
    try:
        kind = common.load_module(os.path.join(
            cell.dir, "traffic", "kinds", cell.mix["kind"] + ".py"))
        res = kind.run(cell)
        res["setup_s"] = cell.window_start - t0
        correct = True
        for name, value, op, limit in res["rows"]:
            ok = holds(value, op, limit)
            correct = correct and ok
            print(f"check: {name} = {value!r} (limit {op} {limit!r}) "
                  f"{'ok' if ok else 'FAILED'}", file=out)
        for line in res.get("info", ()):
            print(line, file=out)
        result = {"correct": bool(correct),
                  "attempted": int(res["attempted"]),
                  "failed": int(res["failed"]), "metrics": {},
                  "device": dict(cell.device,
                                 memory_peak_bytes=res["memory_peak_bytes"])}
        units = {m["name"]: m["unit"]
                 for m in bench["end_to_end"] + bench["per_layer"]}
        if not cell.trace:
            for name in cell.metric_names("end_to_end"):
                result["metrics"][name] = {"value": float(res[name]),
                                           "unit": units[name]}
        else:
            trace_obj, offset = cell.spans.device_trace(
                rehearsal=cell.device["platform"] != "tpu")
            if trace_obj is None or trace_obj.busy_ns() <= 0:
                raise RuntimeError("the traced run saw no operation on "
                                   "the device")
            ctx = {"trace": trace_obj, "spans": cell.spans, "res": res,
                   "cell": cell, "clock_offset_ns": offset,
                   "peaks": peaks(cell)}
            for name in cell.metric_names("per_layer"):
                reader = common.load_module(os.path.join(
                    cell.dir, "layer_metrics", name + ".py"))
                value = reader.read(ctx)
                if value is not None:
                    result["metrics"][name] = {"value": float(value),
                                               "unit": units[name]}
            result["device"]["busy_s"] = trace_obj.busy_ns() / 1e9
            result["device"]["window_s"] = trace_obj.window_ns / 1e9
            result["breakdown"] = {
                "device_ops": trace_obj.top_ops(10),
                "idle_gaps": trace_obj.idle_gaps(
                    cell.spans.host, offset, 10)}
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(tracing.newest_xplane(cell.spans.trace_dir),
                            os.path.join(keep, workload + ".xplane.pb"))
        print(json.dumps(result), file=out, flush=True)
        return result
    finally:
        shutil.rmtree(cell.tmp, ignore_errors=True)


def peaks(cell):
    table = common.load_json(os.path.join(cell.dir, "peaks.json"))
    kind = cell.device["kind"]
    if kind not in table:
        if cell.device["platform"] == "tpu":
            raise RuntimeError(f"device kind {kind!r} is not in peaks.json")
        return None
    return table[kind]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    root = os.path.dirname(_BENCH)
    run_cell(root, a.workload, a.seed, a.seconds, a.trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
