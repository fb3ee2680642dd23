"""Traffic kind `train_stream`: a training job fed host batches from a
seeded, learnable stream, as the examples feed them.

Parameters (the mix's json): batch, seq / image size and whatever else
the config's feed reads, `pool_batches` (distinct host batches made at
set-up and cycled: making one costs more host time than a step),
`sync_every` (steps between `block_until_ready`s; the loss is fetched
there), `trace_steps`.

A config that this kind trains has, in its directory: build.py
`trainer(cfg, mix, seed, devices)` -> .step (the program's compiled
train step: called with a host batch, holds .state), .feed(i),
.samples_per_step, .accum_names, .first_moment, .first_moment_scale,
.rng (None, or what reference.py's `step_keys` needs to draw the step's
dropout masks again); reference.py `weights`, `value_and_grad`, `leaf_sq_norms`.
`correct` is lib/train_check.py's comparison plus counts."""
from __future__ import annotations

import gc
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import common, train_check


def mosaic_calls(text):
    """Names of the Mosaic custom calls in a compiled module's text (the
    kernel's `name=` is the scope right above `pallas_call`)."""
    names = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?([^/"]+)/pallas_call', line)
            names.add(re.findall(r"\w+", m.group(1))[-1] if m else "?")
    return names


def kernel_faults(step, cfg, on_tpu):
    """How many expected kernels are missing from, or unexpected ones
    present in, the executable the window drove."""
    entry = list(step._exec.entries().values())[-1]
    found = mosaic_calls(entry.aot.as_text())
    want = set(cfg.get("expected_kernels", ())) if on_tpu else set()
    return len(found ^ want)


def run(cell):
    build = common.load_module(os.path.join(cell.cfg_dir, "build.py"))
    ref = common.load_module(os.path.join(cell.cfg_dir, "reference.py"))
    mix, cfg = cell.mix, cell.cfg
    if cell.trace:
        cell.spans.start_program_spans()
    tr = build.trainer(cfg, mix, cell.seed, cell.devices)
    pool = [tr.feed(i) for i in range(max(mix.get("pool_batches", 8),
                                          train_check.STEPS))]
    got = train_check.program_readings(tr, pool)
    every = int(mix.get("sync_every", 10))
    step = tr.step
    # one synced group outside the window: the loop's own code is warm
    for k in range(every):
        out = step(*pool[k % len(pool)])
    jax.block_until_ready(out["loss"])
    compiles0 = cell.compiles()
    losses, groups = [], []
    trace_at = 1 if cell.trace else None
    trace_groups = max(int(mix.get("trace_steps", 20)) // every, 1)
    cell.mark_window_start()
    t0 = time.perf_counter()
    k = n_groups = 0
    while True:
        if trace_at is not None and n_groups == trace_at:
            cell.spans.start_trace()
        g0 = time.perf_counter_ns()
        for _ in range(every):
            out = step(*pool[k % len(pool)])
            losses.append(out["loss"])
            k += 1
        jax.block_until_ready(out["loss"])
        g1 = time.perf_counter_ns()
        groups.append((g0, g1))
        cell.spans.span("bench::step_group", g0, g1)
        n_groups += 1
        if trace_at is not None and n_groups == trace_at + trace_groups:
            cell.spans.stop_trace()
            trace_at = None
        if time.perf_counter() - t0 >= cell.seconds:
            break
    elapsed = time.perf_counter() - t0
    if trace_at is not None and n_groups > trace_at:
        cell.spans.stop_trace()
    compiles = cell.compiles() - compiles0
    if cell.trace:
        cell.spans.collect_program_spans()
    losses = np.asarray(jnp.stack(losses), np.float64)
    tenth = max(len(losses) // 10, 1)
    res = {
        "train_samples_per_s": k * tr.samples_per_step / elapsed,
        "attempted": k, "failed": int((~np.isfinite(losses)).sum()),
        "window": (t0, t0 + elapsed), "steps": k, "every": every,
        "group_ms": [(b - a) / 1e6 for a, b in groups],
        "samples_per_step": tr.samples_per_step,
        "memory_peak_bytes": common.memory_peak_bytes(cell.devices),
    }
    faults = kernel_faults(step, cfg, cell.device["platform"] == "tpu")
    batches = [build.reference_batch(b) for b in pool[:train_check.STEPS]]
    rng = tr.rng
    del tr, step, out, pool
    gc.collect()
    w = jax.jit(lambda key: ref.weights(cfg, key))(common.seed_key(cell.seed))
    want = train_check.reference_readings(ref, cfg, w, batches, rng=rng)
    nums, where = train_check.compare(got, want)
    lim = cfg["check"]
    drop = float(losses[:tenth].mean() - losses[-tenth:].mean())
    res["rows"] = [
        ("loss_gap", nums["loss_gap"], "<=", lim["loss_gap"]),
        ("grad_norm_gap", nums["grad_norm_gap"], "<=", lim["grad_norm_gap"]),
        ("delta_norm_gap", nums["delta_norm_gap"], "<=",
         lim["delta_norm_gap"]),
        ("grad_diff", nums["grad_diff"], "<=", lim["grad_diff"]),
        ("loss_drop", drop, ">=", lim["loss_drop_min"]),
        ("nonfinite_losses", res["failed"], "<=", 0),
        ("compiles_in_window", compiles, "<=", 0),
        ("kernel_faults", faults, "<=", 0),
    ]
    res["info"] = [
        where,
        f"steps {k} in {elapsed:.3f} s; loss first tenth "
        f"{losses[:tenth].mean():.4f} last tenth {losses[-tenth:].mean():.4f}",
    ]
    res["counters"] = {"compiles_in_window": compiles}
    return res
