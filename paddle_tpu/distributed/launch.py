"""Multi-process launcher.

Reference parity: python/paddle/distributed/launch.py — spawns one process
per GPU, wiring PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS /
PADDLE_CURRENT_ENDPOINT env.

TPU-native: one process drives all chips of a host (single-controller), so
processes == hosts, not devices. ``spawn`` exists for multi-host emulation
and CPU-mesh testing (SURVEY.md §4: subprocess tests on localhost); on a
real pod each host runs the same script and jax.distributed coordinates.

One process for each chip: ``--nproc N`` starts N copies of ONE environment,
and a TPU chip belongs to one process at a time, so ``--nproc`` above 1 on
one TPU host is unsupported (the second rank fails or hangs waiting for the
chip). Use it with ``JAX_PLATFORMS=cpu``, or with one process per host.

Usage: python -m paddle_tpu.distributed.launch --nproc 2 train.py

Fault diagnosis: ``--debug-port 8080`` hands every rank a live debug
endpoint (rank r serves /healthz /metrics /flightrecorder /threadz
/flagz on 127.0.0.1:8080+r via FLAGS_debug_port), and
``--watchdog-timeout 300`` arms each rank's hang watchdog
(FLAGS_watchdog_timeout_s) so a stalled fleet dumps its flight recorder
+ cross-rank desync report instead of hanging silently.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _build_env(rank: int, nproc: int, coordinator: str, base_env=None):
    env = dict(base_env or os.environ)
    env.update(
        PADDLE_TRAINER_ID=str(rank),
        PADDLE_TRAINERS_NUM=str(nproc),
        PADDLE_COORDINATOR=coordinator,
        PADDLE_TRAINER_ENDPOINTS=",".join(
            f"127.0.0.1:{int(coordinator.split(':')[1]) + i}"
            for i in range(nproc)
        ),
        PADDLE_CURRENT_ENDPOINT=f"127.0.0.1:{int(coordinator.split(':')[1]) + rank}",
    )
    return env


def launch_procs(script_args, nproc: int = 1, env=None, debug_port=0,
                 watchdog_timeout=0.0):
    """Spawn nproc copies of `python script args...`; returns Popen list.

    ``debug_port``/``watchdog_timeout`` wire the fault-diagnosis flags
    into every rank's environment (rank r's debug server binds
    ``debug_port + r`` — the +rank offset happens inside
    monitor.flight_recorder.install_from_flags).
    """
    coordinator = f"127.0.0.1:{_free_port()}"
    procs = []
    for rank in range(nproc):
        penv = _build_env(rank, nproc, coordinator, env)
        if debug_port:
            penv["FLAGS_debug_port"] = str(int(debug_port))
        if watchdog_timeout:
            penv["FLAGS_watchdog_timeout_s"] = str(float(watchdog_timeout))
        procs.append(
            subprocess.Popen([sys.executable] + list(script_args), env=penv)
        )
    return procs


def spawn(func=None, args=(), nprocs=1, **kwargs):
    """paddle.distributed.spawn equivalent.

    Single-controller note: with nprocs==1 (the TPU-normal case) the
    function runs inline — device parallelism comes from the mesh, not
    from processes.
    """
    if nprocs == 1:
        from .env import init_parallel_env

        init_parallel_env()
        return func(*args) if func is not None else None
    raise NotImplementedError(
        "multi-host spawn: launch one process per host with "
        "python -m paddle_tpu.distributed.launch (processes are hosts on "
        "TPU, not devices; in-host parallelism uses the mesh)"
    )


def main():
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--nproc", type=int, default=1)
    p.add_argument("--debug-port", type=int, default=0,
                   help="base port for per-rank /debugz endpoints "
                        "(rank r serves on port+r; 0: off)")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help="per-rank hang-watchdog deadline in seconds "
                        "(0: off)")
    p.add_argument("script", nargs=argparse.REMAINDER)
    ns = p.parse_args()
    procs = launch_procs(ns.script, ns.nproc, debug_port=ns.debug_port,
                         watchdog_timeout=ns.watchdog_timeout)
    code = 0
    for proc in procs:
        code |= proc.wait()
    sys.exit(code)


if __name__ == "__main__":
    main()
