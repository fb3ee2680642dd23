"""Traffic kind `open_loop_http`: an open-loop stream of generation
requests over real HTTP to a server in this process, sent by a child
process that never imports jax.

Parameters (the mix's json): rate_per_s, arrivals (`poisson`), prompt_tokens / output_tokens ({median, sigma, min, max} of a
log-normal), context_limit, table_seed, backlog_at_start (requests due
at the window's first instant: a loaded server's queue, so that a cell
above the knee does not spend its first seconds filling slots), drain_s,
check_requests, trace_s. Every seed gets the SAME multiset of (prompt length, output
length) pairs and of gaps between arrivals, drawn once from `table_seed`
at the mix's rate and the run's length, in another order, with its own
token ids: so seeds change what is asked, not how much.

A config that this kind serves has, in its directory: build.py
`server(cfg, mix, seed)` -> a started, warmed server with `.url`,
`.stop(drain=True)`, `.engine`, `.scheduler`; check.py
`decide(cfg, seed, finished, counters, mix)` -> (rows, info)."""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark.lib import common

_CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "http_client.py")


def _lognormal(rng, spec, n):
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(mix, seed, seconds, vocab):
    """The requests of one run: a function of (mix, seed, seconds) only.
    Due times start at 0; nothing is due at or after ``seconds``."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    table = common.host_rng(mix["table_seed"], n)
    plen = _lognormal(table, mix["prompt_tokens"], n)
    olen = np.minimum(_lognormal(table, mix["output_tokens"], n),
                      mix["context_limit"] - plen)
    if mix["arrivals"] != "poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r}")
    gaps = table.exponential(1.0, n)
    gaps = gaps * (seconds / gaps.sum())
    rng = common.host_rng(seed, 1)
    sizes = rng.permutation(n)
    due = np.cumsum(gaps[rng.permutation(n)])
    due = due - due[0]
    due[:min(int(mix.get("backlog_at_start", 0)), n)] = 0.0
    return [{
        "due_s": float(due[i]),
        "prompt": rng.integers(3, vocab, size=int(plen[j])).tolist(),
        "max_new_tokens": int(olen[j]),
        "temperature": float(mix.get("temperature", 0.0)),
    } for i, j in enumerate(sizes)]


def window(cell, srv, requests, seconds, tracer=None):
    """Offer ``requests`` to ``srv`` from a child process and return its
    records with the window's (start, end) on time.monotonic()."""
    os.makedirs(cell.tmp, exist_ok=True)
    plan_path = os.path.join(cell.tmp, "schedule.json")
    out_path = os.path.join(cell.tmp, "results.json")
    with open(plan_path, "w") as f:
        json.dump({"url": srv.url, "window_s": seconds,
                   "drain_s": cell.mix.get("drain_s", 30.0),
                   "request_timeout_s": cell.mix.get("request_timeout_s",
                                                     120.0),
                   "requests": requests}, f)
    proc = subprocess.Popen([sys.executable, _CLIENT, plan_path, out_path],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    try:
        if proc.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not start")
        t0 = time.monotonic() + 0.2  # the window opens when it can send
        proc.stdin.write(f"{t0!r}\n")
        proc.stdin.flush()
        if tracer is not None:
            tracer(t0)
        rc = proc.wait(timeout=seconds + cell.mix.get("drain_s", 30.0) + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise RuntimeError(f"load generator exited with {rc}")
    records = common.load_json(out_path)
    return records, t0


def reduce(records, requests, seconds):
    """End-to-end numbers of one window, from the client's records."""
    ok = [r for r in records if r["status"] == 200 and r["done"]
          and not r["error"] and r["tokens"]]
    ttft = [(r["token_s"][0] - r["due_s"]) * 1e3 for r in ok]
    gaps = [(b - a) * 1e3 for r in ok
            for a, b in zip(r["token_s"], r["token_s"][1:])]
    lag = [(r["sent_s"] - r["due_s"]) * 1e3 for r in records
           if r.get("sent_s") is not None]
    in_window = sum(1 for r in records for t in r["token_s"] if t <= seconds)
    completed = sum(len(r["tokens"]) for r in ok
                    if r.get("end_s", 1e30) <= seconds)
    late = sum(1 for r in ok if r.get("end_s", 1e30) > seconds)
    # the longest stretch of the window in which no client got a token:
    # a stall of the whole server shows here and in no percentile
    marks = [0.0] + sorted(t for r in records for t in r["token_s"]
                           if t <= seconds) + [seconds]
    silence, silence_at = max((b - a, a) for a, b in zip(marks, marks[1:]))
    # slot-seconds and token-seconds held inside the window: a request
    # holds its slot from its first token to its last, and its cache
    # holds prompt + tokens so far
    busy = live = 0.0
    for r in ok:
        a, b = r["token_s"][0], min(r["token_s"][-1], seconds)
        if b > a:
            busy += b - a
            n0 = len(requests[r["i"]]["prompt"])
            live += (b - a) * (n0 + 0.5 * len(r["tokens"]) * (b - a)
                               / max(r["token_s"][-1] - a, 1e-9))
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "serve_tokens_per_s": in_window / seconds,
        "completed_tokens_per_s": completed / seconds,
        "ttft_p95_ms": common.pctl(ttft, 95) if ttft else float("nan"),
        "itl_p95_ms": common.pctl(gaps, 95) if gaps else float("nan"),
        "ttft_p50_ms": common.pctl(ttft, 50) if ttft else float("nan"),
        "itl_p50_ms": common.pctl(gaps, 50) if gaps else float("nan"),
        "generator_lag_p95_ms": common.pctl(lag, 95) if lag else float("nan"),
        "n_ttft": len(ttft), "n_gaps": len(gaps),
        "unfinished_at_window_end": late,
        "longest_silence_s": silence, "longest_silence_at_s": silence_at,
        "busy_slot_seconds": busy, "mean_live_tokens": live / seconds,
    }


def finished(records, requests):
    return [{"prompt": requests[r["i"]]["prompt"], "tokens": r["tokens"],
             "max_new_tokens": requests[r["i"]]["max_new_tokens"],
             "done": r["done"] and r.get("final_tokens") == r["tokens"]}
            for r in records
            if r["status"] == 200 and not r["error"] and r["tokens"]]


def free_server(srv):
    """Drop the cache and the weights, so that the reference has the
    chip's memory to itself."""
    srv.engine._kv = None
    for _, p in srv.engine.model.named_parameters():
        p._array = None
    gc.collect()


def _compiles():
    from paddle_tpu import profiler
    from paddle_tpu.generation import COMPILE_COUNTER
    from paddle_tpu.monitor import counter

    return (profiler.counters().get(COMPILE_COUNTER, 0)
            + counter("serving/gen_unexpected_compiles").value)


def run(cell):
    build = common.load_module(os.path.join(cell.cfg_dir, "build.py"))
    check = common.load_module(os.path.join(cell.cfg_dir, "check.py"))
    requests = schedule(cell.mix, cell.seed, cell.seconds,
                        cell.cfg["vocab_size"])
    if cell.trace:
        cell.spans.start_program_spans()
    srv = build.server(cell.cfg, cell.mix, cell.seed)
    # one request through HTTP, streamed: the handler path and the
    # client's code are warm before the window
    warm = schedule(cell.mix, cell.seed + 1, 1.0 / cell.mix["rate_per_s"],
                    cell.cfg["vocab_size"])[:1]
    warm[0]["max_new_tokens"] = min(warm[0]["max_new_tokens"], 4)
    window(cell, srv, warm, 0.5)
    compiles0 = _compiles()
    cell.spans.reset()
    tracer = None
    if cell.trace:
        tracer = lambda t0: cell.spans.trace_between(  # noqa: E731
            t0 + cell.mix.get("trace_after_s", 3.0),
            cell.mix.get("trace_s", 4.0))
    cell.mark_window_start()
    records, t0 = window(cell, srv, requests, cell.seconds, tracer)
    res = reduce(records, requests, cell.seconds)
    res["window"] = (t0, t0 + cell.seconds)
    counters = {"compiles_in_window": _compiles() - compiles0
                + srv.engine.extra_compiles()}
    if cell.trace:
        cell.spans.collect_program_spans()
    srv.stop(drain=True)
    counters["undrained"] = int(srv.scheduler.live_slots
                                + srv.scheduler.alive)
    res["memory_peak_bytes"] = common.memory_peak_bytes(cell.devices)
    res["slots"] = srv.engine.slots
    done = finished(records, requests)
    free_server(srv)
    del srv
    rows, info = check.decide(cell.cfg, cell.seed, done, counters,
                              cell.mix)
    res.update(rows=rows, info=[
        info,
        f"requests: attempted {res['attempted']} failed {res['failed']} "
        f"unfinished at the window's end {res['unfinished_at_window_end']}",
        f"ttft ms: p50 {res['ttft_p50_ms']:.2f} p95 "
        f"{res['ttft_p95_ms']:.2f} (n={res['n_ttft']}); gaps ms: p50 "
        f"{res['itl_p50_ms']:.2f} p95 {res['itl_p95_ms']:.2f} "
        f"(n={res['n_gaps']}); generator lag p95 "
        f"{res['generator_lag_p95_ms']:.2f} ms; tokens of requests "
        f"completed inside the window {res['completed_tokens_per_s']:.1f}/s; "
        f"tokens delivered inside the window "
        f"{res['serve_tokens_per_s']:.2f}/s; longest stretch with no token "
        f"{res['longest_silence_s'] * 1e3:.0f} ms at "
        f"{res['longest_silence_at_s']:.2f} s",
    ], counters=counters)
    return res
