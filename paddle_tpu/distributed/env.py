"""Process/device environment.

Reference parity: python/paddle/fluid/dygraph/parallel.py ParallelEnv
(rank/world-size/device from PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS
env) and python/paddle/distributed/parallel.py init_parallel_env.

TPU-native: a single python process drives all local TPU chips (single-
controller); multi-host pods run one process per host, coordinated by
jax.distributed. "rank" therefore means *process* index (host), and
device-level parallelism is expressed with meshes, not ranks.
"""
from __future__ import annotations

import os

import jax

_initialized = False


class ParallelEnv:
    """Mirrors dygraph/parallel.py:ParallelEnv env-variable surface."""

    def __init__(self):
        self.rank = int(os.getenv("PADDLE_TRAINER_ID", os.getenv("RANK", "0")))
        self.world_size = int(
            os.getenv("PADDLE_TRAINERS_NUM", os.getenv("WORLD_SIZE", "1"))
        )
        endpoints = os.getenv("PADDLE_TRAINER_ENDPOINTS", "")
        self.trainer_endpoints = endpoints.split(",") if endpoints else []
        self.current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def nranks(self):
        return self.world_size

    @property
    def local_rank(self):
        return self.rank

    @property
    def dev_id(self):
        return int(os.getenv("FLAGS_selected_tpus", "0").split(",")[0])


def _distributed_client_active() -> bool:
    """Whether jax.distributed.initialize already ran — checked WITHOUT
    touching the XLA backend (jax.process_count() would initialize it,
    which forbids a later jax.distributed.initialize)."""
    return jax.distributed.is_initialized()


def get_rank() -> int:
    if jax.process_count() > 1:
        return jax.process_index()
    return ParallelEnv().rank


def get_world_size() -> int:
    if jax.process_count() > 1:
        return jax.process_count()
    return ParallelEnv().world_size


def init_parallel_env():
    """Initialize multi-host coordination (c_comm_init / init_parallel_env
    equivalent). Single-host: no-op. Multi-host: jax.distributed handshake
    using the coordinator from env (replaces gen_nccl_id RPC rendezvous,
    operators/collective/c_gen_nccl_id_op.cc).

    Must run before any backend-initializing JAX call — like the
    reference, where c_comm_init precedes every collective; fleet.init()
    calls this first thing.
    """
    global _initialized
    if _initialized:
        return ParallelEnv()
    env = ParallelEnv()
    coordinator = os.getenv("PADDLE_COORDINATOR", "")
    if env.world_size > 1 and coordinator and not _distributed_client_active():
        if os.getenv("JAX_PLATFORMS", "").strip() == "cpu":
            # CPU multi-process needs an explicit cross-host collectives
            # transport (the reference's Gloo CPU path,
            # framework/fleet/gloo_wrapper.h:106); TPU rides ICI/DCN and
            # needs nothing here.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=env.world_size,
            process_id=env.rank,
        )
    _initialized = True
    # fault-diagnosis wiring rides the same entry point the reference
    # hung c_comm_init on: every initialized process records the world it
    # joined and arms whatever FLAGS ask for (crash/SIGUSR1 dumps always;
    # hang watchdog behind FLAGS_watchdog_timeout_s; /debugz endpoint
    # behind FLAGS_debug_port, bound at port+rank)
    from ..monitor import flight_recorder as _flight

    _flight.record_event("init_parallel_env", rank=env.rank,
                         world=env.world_size,
                         coordinator=coordinator or None)
    try:
        _flight.install_from_flags()
    except Exception as e:  # diagnosis must never block training startup
        import warnings

        warnings.warn(f"fault-diagnosis install failed: "
                      f"{type(e).__name__}: {e}", RuntimeWarning)
    return env


class DataParallel:
    """paddle.DataParallel (fluid/dygraph/parallel.py:225) on the
    single-controller runtime.

    The reference wraps a Layer so each process all-reduces coalesced
    gradients after backward (parallel.py:386 apply_collective_grads).
    Here one process drives every local device and gradient averaging is
    GSPMD's job inside the sharded step, so the wrapper forwards
    transparently and scale_loss/apply_collective_grads keep the API as
    no-ops with exact semantics (world averaging happens in-step).
    """

    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False):
        self._layers = layers

    def __getattr__(self, name):
        return getattr(self.__dict__["_layers"], name)

    def __call__(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def scale_loss(self, loss):
        return loss  # the compiled step's global-mean loss already scales

    def apply_collective_grads(self):
        pass  # gradient sync is in-program (GSPMD), not a post-hoc pass

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)
