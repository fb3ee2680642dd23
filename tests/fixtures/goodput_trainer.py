"""Goodput-ledger fixture: a checkpointing trainer with a controlled
phase mix, driven by tests/test_goodput.py.

Unlike dist_elastic.py (whose per-step math is microseconds, so XLA
compile dominates any CPU run), this trainer's step is real busy-work
wall time, so the phase mix is controllable. It still
exercises the REAL machinery end to end: TrainingMonitor step frames,
``record_input_wait_ms``, checkpoint save (sync, so
``chaos.inject("mid_save")`` kills THIS process deterministically),
``restore_train_step`` (which fires ``note_resume``), and the
GOODPUT.json sidecar published with the checkpoint discipline.

Env: GOODPUT_CKPT_DIR (required; snapshots land here — the ledger
sidecar dir comes from FLAGS_goodput_dir), GOODPUT_TOTAL_STEPS (default
30), GOODPUT_STEP_MS (busy-compute per step, default 30),
GOODPUT_WAIT_MS (simulated input wait per step, default 1),
GOODPUT_SAVE_EVERY (checkpoint cadence in steps, default 5).

Prints one JSON line: resume identity + the ledger snapshot fields the
test asserts on.
"""
import json
import os
import sys
import time

import jax

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from paddle_tpu import monitor
from paddle_tpu.distributed import chaos  # noqa: F401  (inject points)
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.monitor import goodput as gp


class _StepObj:
    """Minimal train-step shim: restore_train_step only needs
    ``.state`` (a pytree of arrays)."""

    def __init__(self, state):
        self.state = state


def busy_ms(ms):
    """Real compute wall time (the step's 'productive' share)."""
    a = np.random.rand(96, 96).astype(np.float32)
    deadline = time.perf_counter() + ms / 1e3
    while time.perf_counter() < deadline:
        a = a @ a / np.linalg.norm(a)
    return a


def main():
    ckpt_dir = os.environ["GOODPUT_CKPT_DIR"]
    total = int(os.environ.get("GOODPUT_TOTAL_STEPS", "30"))
    step_ms = float(os.environ.get("GOODPUT_STEP_MS", "30"))
    wait_ms = float(os.environ.get("GOODPUT_WAIT_MS", "1"))
    save_every = int(os.environ.get("GOODPUT_SAVE_EVERY", "5"))

    # the ledger must exist BEFORE the restore so note_resume lands in it
    led = gp.maybe_start_from_flags()
    assert led is not None, "the caller must set FLAGS_goodput_dir"

    lines = []
    mon = monitor.TrainingMonitor("train", interval=10,
                                  log_fn=lines.append)
    step_obj = _StepObj({"w": jnp.zeros((16, 16), jnp.float32),
                         "step": jnp.zeros((), jnp.int32)})

    ckpt.sweep_tmp(ckpt_dir)
    path, _ = ckpt.latest_checkpoint(ckpt_dir)
    resumed_from = -1
    if path is not None:
        manifest = ckpt.restore_train_step(step_obj, path)
        resumed_from = int(manifest["step"])
    start = resumed_from + 1

    for s in range(start, total):
        with mon.step(examples=8, global_step=s):
            # simulated pipeline stall: real slept wall time, fed through
            # the same record_input_wait_ms path the DataLoader uses
            t0 = time.perf_counter()
            time.sleep(wait_ms / 1e3)
            monitor.record_input_wait_ms(
                (time.perf_counter() - t0) * 1e3)
            busy_ms(step_ms)
            step_obj.state = {
                "w": step_obj.state["w"] + 1.0,
                "step": jnp.asarray(s, jnp.int32),
            }
        if s % save_every == save_every - 1:
            # sync save: serialize/publish (and the mid_save chaos
            # point) run on THIS thread — a kill lands deterministically
            ckpt.save(os.path.join(ckpt_dir, f"step_{s}"),
                      step_obj.state, step=s, async_=False, keep=3)
    mon.close()  # flushes the window line + publishes the sidecar

    snap = led.flush_metrics()
    sys.stdout.write(json.dumps({
        "resumed_from": resumed_from,
        "start": start,
        "steps_run": total - start,
        "wall_s": snap["wall_s"],
        "phases": snap["phases"],
        "goodput": snap["goodput"],
        "conservation_error": snap["conservation_error"],
        "lost_steps": snap["lost_steps"],
        "resumes": snap["resumes"],
        "sidecar_loaded": snap["sidecar_loaded"],
        "max_committed_step": snap["max_committed_step"],
        "lost_work_priced_s": snap["lost_work_priced_s"],
        "lifetime": snap["lifetime"],
        "monitor_lines": lines,
    }) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
