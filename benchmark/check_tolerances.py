#!/usr/bin/env python3
"""The seeds-and-precision command: a cell's check alone, over many
seeds, in the configuration's stated precision (the program) and in the
control's (the reference one precision lower, put in the program's
place), with both distributions printed. The limits in each config.json
"check" are set from this output, which is kept under evidence/.

  python benchmark/check_tolerances.py --workload <name> --seeds 12
      [--first-seed N] [--window S] [--sweep r1,r2,...]

Training cells need no measured window: the step is built and driven
through its first three steps, per seed. Serving cells run one short
window per seed at the cell's own load on one server (the weights are
swapped by seed, the programs stay), then free it and read every seed's
sample through the reference and the control. `--sweep` first runs one
window at each offered rate and prints what the knee is read from."""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import tempfile
import time

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark.lib import common  # noqa: E402
from benchmark import run as harness  # noqa: E402


def describe(name, values):
    v = sorted(values)
    print(f"  {name}: min {v[0]:.6g} median {statistics.median(v):.6g} "
          f"max {v[-1]:.6g}  all {[float(f'{x:.4g}') for x in values]}",
          flush=True)


def train(cell, seeds):
    import jax
    from benchmark.lib import train_check

    build = common.load_module(os.path.join(cell.cfg_dir, "build.py"))
    ref = common.load_module(os.path.join(cell.cfg_dir, "reference.py"))
    sound, control = [], []
    for seed in seeds:
        t0 = time.perf_counter()
        tr = build.trainer(cell.cfg, cell.mix, seed, cell.devices)
        batches = [tr.feed(i) for i in range(train_check.STEPS)]
        got = train_check.program_readings(tr, batches)
        rng = tr.rng
        del tr
        gc.collect()
        rb = [build.reference_batch(b) for b in batches]
        make = jax.jit(lambda k: ref.weights(cell.cfg, k))
        want = train_check.reference_readings(
            ref, cell.cfg, make(common.seed_key(seed)), rb, rng=rng)
        low = train_check.reference_readings(
            ref, cell.cfg, make(common.seed_key(seed)), rb, control=True,
            rng=rng)
        a, wa = train_check.compare(got, want)
        b, wb = train_check.compare(low, want)
        sound.append(a)
        control.append(b)
        print(f"seed {seed}: program {json.dumps(a)} | control "
              f"{json.dumps(b)} | {wa} | control {wb} | "
              f"{time.perf_counter() - t0:.1f} s | peak bytes on the "
              f"fullest chip {common.memory_peak_bytes(cell.devices)}",
              flush=True)
    for key in sound[0]:
        print(key)
        describe("program (stated precision)", [s[key] for s in sound])
        describe("control (fp8 reference)", [c[key] for c in control])


def wait_idle(srv, limit_s=180.0):
    """Until the server holds no request: one window's backlog must not
    ride into the next."""
    end = time.monotonic() + limit_s
    while time.monotonic() < end and (
            srv.scheduler.queue_depth() or srv.scheduler.live_slots):
        time.sleep(0.2)


def serve(cell, seeds, window_s, sweep, sweep_s):
    kind = common.load_module(os.path.join(
        cell.dir, "traffic", "kinds", cell.mix["kind"] + ".py"))
    build = common.load_module(os.path.join(cell.cfg_dir, "build.py"))
    check = common.load_module(os.path.join(cell.cfg_dir, "check.py"))
    srv = build.server(cell.cfg, cell.mix, seeds[0])
    vocab = cell.cfg["vocab_size"]
    warm = kind.schedule(cell.mix, 1, 1.0, vocab)[:1]
    kind.window(cell, srv, warm, 0.5)
    if sweep:
        print("sweep: rate/s, served tokens/s, completed tokens/s, ttft "
              "p50/p95 ms, gap p95 ms, queue depth at the window's end, "
              "unfinished at the end, failed, generator lag p95 ms",
              flush=True)
        for rate in sweep:
            mix = dict(cell.mix, rate_per_s=rate)
            reqs = kind.schedule(mix, 4242, sweep_s, vocab)
            depth = {}

            def probe(t0, depth=depth):
                time.sleep(max(t0 + sweep_s - time.monotonic(), 0))
                depth["q"] = srv.scheduler.queue_depth()
                depth["busy"] = srv.scheduler.live_slots
            rec, t0 = kind.window(cell, srv, reqs, sweep_s, tracer=probe)
            r = kind.reduce(rec, reqs, sweep_s)
            wait_idle(srv)
            print(f"sweep: {rate:g} {r['serve_tokens_per_s']:.1f} "
                  f"{r['completed_tokens_per_s']:.1f} "
                  f"{r['ttft_p50_ms']:.1f}/{r['ttft_p95_ms']:.1f} "
                  f"{r['itl_p95_ms']:.1f} {depth.get('q')} "
                  f"(busy {depth.get('busy')}) "
                  f"{r['unfinished_at_window_end']} {r['failed']} "
                  f"{r['generator_lag_p95_ms']:.2f}", flush=True)
    samples = {}
    for seed in seeds:
        w = build.program_weights(cell.cfg, seed)
        for name, p in srv.engine.model.named_parameters():
            p._array = w[name]
        del w
        srv.engine.reset()
        reqs = kind.schedule(cell.mix, seed, window_s, vocab)
        rec, _ = kind.window(cell, srv, reqs, window_s)
        wait_idle(srv)
        r = kind.reduce(rec, reqs, window_s)
        done = kind.finished(rec, reqs)
        samples[seed] = check.sample(
            done, seed, int(cell.mix.get("check_requests", 32)))
        print(f"seed {seed}: window {window_s:g} s attempted "
              f"{r['attempted']} failed {r['failed']} ttft p95 "
              f"{r['ttft_p95_ms']:.1f} ms gaps p95 {r['itl_p95_ms']:.1f} "
              f"ms; sample {len(samples[seed])} requests", flush=True)
    srv.stop(drain=True)
    kind.free_server(srv)
    del srv
    sound, control = [], []
    for seed in seeds:
        a, b = check.gaps(cell.cfg, seed, samples[seed], control=True)
        sound.append(a)
        control.append(b)
        print(f"seed {seed}: program {json.dumps(a)} | control "
              f"{json.dumps(b)}", flush=True)
    for key in ("gap_max", "err_scale", "gap_mean", "exact_share"):
        print(key)
        describe("program (stated precision)", [s[key] for s in sound])
        describe("control (bfloat16 reference)", [c[key] for c in control])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--window", type=float, default=15.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-window", type=float, default=20.0)
    ap.add_argument("--root", default=os.path.dirname(_BENCH))
    ap.add_argument("--no-chip", action="store_true",
                    help="tests only: do not insist on a TPU")
    a = ap.parse_args(argv)
    common.setup_env()
    bench = common.load_json(os.path.join(a.root, "BENCHMARK.json"))
    cell = harness.Cell(a.root, bench, a.workload, 0, a.window, False,
                        time.perf_counter())
    import jax

    cell.device = common.device_info(None if a.no_chip else cell.chips)
    cell.devices = jax.devices()[:cell.chips]
    os.makedirs(os.path.join(a.root, ".bench_tmp"), exist_ok=True)
    cell.tmp = tempfile.mkdtemp(prefix="tol-", dir=os.path.join(
        a.root, ".bench_tmp"))
    print(f"check_tolerances: {a.workload} on {json.dumps(cell.device)}; "
          f"stated precision: {cell.cfg['precision']['stated']}; control: "
          f"{cell.cfg['precision']['control']}", flush=True)
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    if cell.mix["kind"] == "train_stream":
        train(cell, seeds)
    else:
        serve(cell, seeds, a.window,
              [float(x) for x in a.sweep.split(",") if x], a.sweep_window)
    return 0


if __name__ == "__main__":
    sys.exit(main())
