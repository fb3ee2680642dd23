"""Kernel-tuning cache: tuned schedules in memory, persisted as
versioned JSON only when the caller names a file.

Tuned winners live in memory unless an explicit path is handed to
:class:`TuningCache` / :func:`reset_tuning_cache`: which schedule a
kernel compiles with must follow from the committed code and what the
caller asked for, never from a file left behind in a cache directory.
The file holds every tuned winner, keyed by
``(kernel, device_kind, shape-bucket, dtype, schedule-space version)``
— entries for other device kinds coexist in the same file (a cache
tuned on v5e travels to a v4 host without poisoning it: the v4 lookups
simply miss and run on defaults).

Robustness contract (the PR-14 "inconclusive never blocks"
discipline): a truncated file, a wrong-schema file, or a structurally
malformed entry degrades to defaults with ONE warning + a
``autotune_cache_reject`` flight event + the ``autotune::cache_reject``
counter — never a crash, never a retry loop. Stale entries (older
``space_version`` after a kernel's schedule space changed shape) are
rejected the same way at lookup.

``schedule_token()`` is the runtime coupling: ``runtime/compiled.py``
folds it into every compile identity, so any cache mutation (a file
load, a background-search swap-in, ``set_flags`` turning the tuner
off) bumps the token and the next dispatch of an affected signature is
a CLEAN recompile under the new schedule — tuned swaps can never run
against a stale trace.
"""
from __future__ import annotations

import json
import os
import threading
import warnings

from ..flags import flag
from ..profiler import bump_counter

__all__ = ["CACHE_SCHEMA_VERSION", "CACHE_FILE_NAME", "TuningCache",
           "tuning_cache", "reset_tuning_cache", "schedule_token",
           "tuned_table"]

CACHE_SCHEMA_VERSION = 1
CACHE_FILE_NAME = "kernel_tuning_cache.json"


def _flight():
    from ..monitor import flight_recorder

    return flight_recorder


def _device_kind() -> str:
    from ..monitor.cost_model import _device_kind as kind

    return kind()


def _entry_valid(value) -> bool:
    return (isinstance(value, dict)
            and isinstance(value.get("params"), dict)
            and all(isinstance(k, str) and isinstance(v, int)
                    and not isinstance(v, bool)
                    for k, v in value["params"].items())
            and isinstance(value.get("space_version"), int))


class TuningCache:
    """The tuned-schedule store: lazy-loaded, thread-safe, atomic
    persistence, generation-counted for the runtime token."""

    def __init__(self, path=None):
        # path=None: in-memory only; a path loads from and saves to it
        self.path = path
        self._entries: dict[str, dict] = {}
        self._loaded = False
        self._lock = threading.RLock()
        self._generation = 0
        self._stale_warned: set = set()  # one reject per stale key

    # -- identity ------------------------------------------------------------

    @property
    def generation(self) -> int:
        """Bumps on every mutation (load, put, clear) — the
        schedule_token() ingredient that forces clean recompiles."""
        with self._lock:
            return self._generation

    @staticmethod
    def key_of(space, info, device_kind=None) -> str:
        kind = device_kind if device_kind is not None else _device_kind()
        bucket = "/".join(f"{k}={v}" for k, v in space.bucket(info))
        return f"{space.name}|{kind}|{bucket}"

    # -- load / reject -------------------------------------------------------

    def _reject(self, reason, **fields):
        bump_counter("autotune::cache_reject")
        try:
            _flight().record_event("autotune_cache_reject", reason=reason,
                                   path=str(self.path), **fields)
        except Exception:
            pass
        warnings.warn(
            f"kernel tuning cache rejected ({reason}) at {self.path!r}: "
            "continuing on default schedules", RuntimeWarning,
            stacklevel=3)

    def ensure_loaded(self):
        with self._lock:
            if self._loaded:
                return
            self._loaded = True
            self._generation += 1
            path = self.path
            if path is None or not os.path.exists(path):
                return
            try:
                with open(path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
            except Exception as e:  # truncated / not JSON / unreadable
                self._reject(f"unreadable: {type(e).__name__}")
                return
            if (not isinstance(raw, dict)
                    or raw.get("schema") != CACHE_SCHEMA_VERSION
                    or not isinstance(raw.get("entries"), dict)):
                self._reject(
                    "wrong schema "
                    f"{raw.get('schema') if isinstance(raw, dict) else '?'}"
                    f" (want {CACHE_SCHEMA_VERSION})")
                return
            bad = 0
            for key, value in raw["entries"].items():
                if isinstance(key, str) and _entry_valid(value):
                    self._entries[key] = value
                else:
                    bad += 1
            if bad:
                self._reject(f"{bad} malformed entries dropped",
                             kept=len(self._entries))

    # -- lookup / mutate -----------------------------------------------------

    def lookup(self, space, info, device_kind=None) -> dict | None:
        self.ensure_loaded()
        key = self.key_of(space, info, device_kind)
        with self._lock:
            entry = self._entries.get(key)
        if entry is None:
            return None
        if entry.get("space_version") != space.version:
            # schedule space changed shape since this was tuned: stale,
            # degrade to defaults (the tuner will re-search under
            # mode=search; 'cached' just runs defaults). ONE reject per
            # key — lookups repeat per dispatch and must not inflate
            # the counter into a phantom ongoing-corruption signal
            with self._lock:
                first = key not in self._stale_warned
                self._stale_warned.add(key)
            if first:
                self._reject(
                    f"stale space_version "
                    f"{entry.get('space_version')} (want "
                    f"{space.version}) for {key}")
            return None
        return entry

    def put(self, space, info, params, device_kind=None, **meta):
        """Record a tuned winner and persist (atomic tmp+rename when a
        cache path is configured)."""
        self.ensure_loaded()
        entry = {
            "params": {k: int(v) for k, v in params.items()},
            "space_version": space.version,
            "kernel": space.name,
            "device_kind": (device_kind if device_kind is not None
                            else _device_kind()),
            "bucket": dict(space.bucket(info)),
            **meta,
        }
        with self._lock:
            self._entries[self.key_of(space, info, device_kind)] = entry
            self._generation += 1
        self.save()
        return entry

    def entries(self) -> dict:
        self.ensure_loaded()
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._generation += 1

    def save(self):
        path = self.path
        if path is None:
            return
        with self._lock:
            payload = {"schema": CACHE_SCHEMA_VERSION,
                       "entries": dict(self._entries)}
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic publish: readers never see a torn file
        except OSError as e:
            # an unwritable cache dir must not take training down
            warnings.warn(f"kernel tuning cache not persisted: {e}",
                          RuntimeWarning)


_cache = [None]
_cache_lock = threading.Lock()
# bumps on every singleton swap: two different cache INSTANCES can reach
# the same per-instance generation count, and the schedule token must
# never read equal across them (a CompiledStore entry compiled under the
# old cache would otherwise serve under the new one's schedules)
_cache_epoch = [0]


def tuning_cache() -> TuningCache:
    """The process-wide tuning cache singleton."""
    with _cache_lock:
        if _cache[0] is None:
            _cache[0] = TuningCache()
        return _cache[0]


def reset_tuning_cache(path=None) -> TuningCache:
    """Swap in a fresh cache: in memory, or backed by the file at
    ``path`` (loaded at first lookup, rewritten at every put)."""
    with _cache_lock:
        _cache_epoch[0] += 1
        _cache[0] = TuningCache(path)
        return _cache[0]


def schedule_token() -> tuple:
    """The schedule ingredient of every CompiledStore compile identity:
    differs whenever schedule resolution could differ (tuner off vs on,
    any cache mutation), so a tuned swap-in forces a clean recompile of
    affected signatures instead of running under a stale trace."""
    mode = flag("kernel_autotune")
    if mode == "off":
        return ("sched-off",)
    cache = tuning_cache()
    cache.ensure_loaded()  # a pending file load must not split the token
    return ("sched", _cache_epoch[0], cache.generation)


def tuned_table(device_kind=None) -> list:
    """The /statz "tuned kernels" table: every cache entry for this
    device kind with its measured tuned-vs-default microseconds."""
    kind = device_kind if device_kind is not None else _device_kind()
    rows = []
    for key, entry in sorted(tuning_cache().entries().items()):
        if entry.get("device_kind") != kind:
            continue
        best = entry.get("best_us")
        default = entry.get("default_us")
        rows.append({
            "kernel": entry.get("kernel"),
            "bucket": entry.get("bucket"),
            "params": entry.get("params"),
            "space_version": entry.get("space_version"),
            "best_us": best,
            "default_us": default,
            "speedup": (round(default / best, 3)
                        if best and default else None),
            "key": key,
        })
    return rows
