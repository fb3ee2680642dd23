"""Async, reshardable, crash-consistent training checkpoints.

The preemption-tolerance contract (ROADMAP item 5) in three guarantees:

1. **Off the step critical path.** A snapshot *capture* is a device-side
   copy of the train step's state pytree (donation-safe: the copies are
   never fed back to the compiled step) dispatched asynchronously, plus
   an async D2H start; the serialize + fsync + publish work runs on a
   background writer thread (``FLAGS_checkpoint_async``). The training
   loop never blocks on disk.

2. **Crash-consistent publication.** Data is written into ``<path>.tmp``
   and published by one atomic ``rename`` only after a ``MANIFEST.json``
   (global shapes, dtypes, PartitionSpecs, per-file CRC32s) is fsynced.
   A process killed mid-save leaves a manifest-less ``.tmp`` that
   :func:`sweep_tmp` removes and :func:`latest_checkpoint` never
   considers; a corrupted published snapshot fails its checksums and is
   *skipped* in favor of the next-newest — a torn snapshot is detected,
   never half-loaded.

3. **Resume into a different world.** Each rank writes only the array
   shards it owns (``replica_id == 0`` de-dups replicated leaves), with
   the global index of every piece recorded. On load the global arrays
   are reassembled from all ranks' pieces and re-sliced onto the *new*
   mesh via ``jax.make_array_from_callback`` — a 4-rank ZeRO-1
   checkpoint restores onto 2 or 8 ranks with a loss-curve-identical
   continuation (sharding specs come from ``parallel/sharding.py``; the
   wire form in the manifest is mesh-independent).

Layout of one snapshot directory::

    step_12/
      MANIFEST.json      format, step, world, mesh_shape, entries{name:
                         {shape,dtype,spec}}, files{name:{crc32,size}}
      shard_r0.pdshard   rank 0's pieces: {name: [(global_index, data)]}
      shard_r1.pdshard   ...
      rank_0.json        per-rank commit record (crc of its shard file);
                         rank 0 aggregates these into the manifest

``incubate/auto_checkpoint.py`` rides the same low-level writer for its
epoch snapshots; ``tests/test_elastic_checkpoint.py`` kills a writer
inside this pipeline (``kill -9`` between the data files and the
manifest) to prove the recovery path.
"""
from __future__ import annotations

import json
import os
import queue
import re
import shutil
import threading
import time
import zlib

import numpy as np

from ..flags import flag
from ..profiler import RecordEvent

__all__ = [
    "CheckpointError",
    "CheckpointCorruptError",
    "save",
    "save_train_step",
    "restore_train_step",
    "load",
    "validate",
    "latest_checkpoint",
    "sweep_tmp",
    "wait_pending",
    "detach_refs",
    "write_bytes",
    "write_manifest",
    "MANIFEST",
]

MANIFEST = "MANIFEST.json"
FORMAT_VERSION = 1
_PEER_WAIT_S = 120.0  # rank 0's budget for peers' shard commits


class CheckpointError(RuntimeError):
    pass


class CheckpointCorruptError(CheckpointError):
    """A snapshot that must be skipped: torn, checksum-failing, or
    manifest-less. Never propagated past the fallback scan."""


def _flight():
    from ..monitor import flight_recorder

    return flight_recorder


def _goodput():
    from ..monitor import goodput

    return goodput


def _counter(name):
    from ..monitor import registry

    return registry.counter(name)


# ---------------------------------------------------------------------------
# pytree naming / capture
# ---------------------------------------------------------------------------


_NAME_CACHE: dict = {}  # treedef -> leaf names (keystr is the slow part)


def _named_leaves(tree):
    """Flatten a state pytree into ([name, leaf], treedef); names are
    jax keystr paths — stable across processes for identical pytrees.
    Names are cached per treedef: captures run on the step path, and
    re-deriving key strings every save costs more than the capture."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    names = _NAME_CACHE.get(treedef)
    if names is None:
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        names = [jax.tree_util.keystr(path) for path, _ in flat]
        if len(_NAME_CACHE) > 32:
            _NAME_CACHE.clear()
        _NAME_CACHE[treedef] = names
    return list(zip(names, leaves)), treedef


def detach_refs(obj):
    """Replace live Tensor leaves with their current immutable jax
    arrays, recursively — the O(1) capture for eager-object snapshots
    (auto_checkpoint): later training rebinds ``Tensor._array`` to new
    arrays, so the grabbed references stay frozen at capture time."""
    from ..framework.tensor import Tensor

    if isinstance(obj, Tensor):
        return obj._array
    if isinstance(obj, dict):
        return {k: detach_refs(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(detach_refs(v) for v in obj)
    return obj


_COPY_FN = []  # lazily-built jitted whole-tree copy


def _snapshot_leaves(leaves):
    """Device-side copy of every jax leaf (donation-safe: the compiled
    step will donate the *originals*, never these). All array leaves are
    copied by ONE jitted program — a single async dispatch per capture,
    not one per leaf — so the step loop pays microseconds; the writer
    thread's host reads block on the transfer instead."""
    import jax
    import jax.numpy as jnp

    if not _COPY_FN:
        _COPY_FN.append(jax.jit(
            lambda xs: [jnp.copy(x) for x in xs]))
    arrays = [(i, l) for i, l in enumerate(leaves)
              if isinstance(l, jax.Array)]
    out = list(leaves)
    if arrays:
        copies = _COPY_FN[0]([l for _, l in arrays])
        for (i, _), c in zip(arrays, copies):
            out[i] = c
    return out


# ---------------------------------------------------------------------------
# low-level durable writes
# ---------------------------------------------------------------------------


def write_bytes(path, data: bytes):
    """Write + fsync; returns (crc32, size) for the manifest."""
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    return zlib.crc32(data) & 0xFFFFFFFF, len(data)


def _fsync_dir(path):
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems refuse dir fsync; rename is still atomic
    finally:
        os.close(fd)


def write_manifest(dirpath, files, **meta):
    """Write + fsync the manifest that makes a snapshot loadable. The
    caller publishes (renames) only after this returns."""
    manifest = {"format": FORMAT_VERSION, **meta, "files": files}
    write_bytes(os.path.join(dirpath, MANIFEST),
                json.dumps(manifest, sort_keys=True).encode("utf-8"))
    _fsync_dir(dirpath)
    return manifest


# ---------------------------------------------------------------------------
# shard extraction / reassembly
# ---------------------------------------------------------------------------


def _index_wire(idx, shape):
    """Global-index slices -> [[start, stop], ...] (JSON/pickle stable)."""
    out = []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def _leaf_pieces(arr, rank, world):
    """The (global_index, data) pieces THIS rank persists for one leaf.

    Sharded arrays: every addressable shard with ``replica_id == 0`` —
    exactly one global writer per distinct piece, so the union over all
    ranks' files tiles the global array with no duplicate bytes.
    Host/per-process arrays (no global sharding): rank 0 writes the
    whole leaf.
    """
    import jax

    if isinstance(arr, jax.Array):
        try:
            shards = list(arr.addressable_shards)
        except Exception:
            shards = []
        if shards:
            if world > 1 and len(getattr(arr.sharding, "device_set",
                                         ())) == 1:
                # per-PROCESS array (no global placement): every rank
                # holds its own copy with replica_id 0, so without this
                # gate all ranks would write overlapping full pieces and
                # load would silently take an arbitrary writer. Rank 0's
                # copy is canonical — the single-controller convention.
                if rank != 0:
                    return []
            return [
                (_index_wire(sh.index, arr.shape), sh.data)
                for sh in shards
                if getattr(sh, "replica_id", 0) == 0
            ]
    if rank == 0 or world <= 1:
        shape = np.shape(arr)
        full = tuple(slice(0, d) for d in shape)
        return [(_index_wire(full, shape), arr)]
    return []


def _np_dtype(name):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bfloat16 & friends (jax always ships it)

        return np.dtype(getattr(ml_dtypes, name))


def _assemble(name, entry, pieces):
    """Rebuild one global host array from shard pieces (any world)."""
    shape = tuple(int(d) for d in entry["shape"])
    dtype = _np_dtype(entry["dtype"])
    if not pieces:
        raise CheckpointCorruptError(f"{name}: no shard data in any file")
    if shape == ():
        return np.asarray(pieces[0][1], dtype=dtype).reshape(())
    buf = np.zeros(shape, dtype)
    covered = 0
    for idx, data in pieces:
        sl = tuple(slice(a, b) for a, b in idx)
        buf[sl] = np.asarray(data, dtype=dtype).reshape(
            [b - a for a, b in idx])
        covered += int(np.prod([b - a for a, b in idx]))
    if covered < int(np.prod(shape)):
        raise CheckpointCorruptError(
            f"{name}: shards cover {covered} of {int(np.prod(shape))} "
            "elements (missing rank file?)")
    return buf


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------


def save(path, state, shardings=None, *, step=None, mesh=None, keep=None,
         async_=None, peer_timeout_s=None):
    """Snapshot ``state`` (a pytree of arrays) to ``path``.

    ``shardings`` is a matching pytree of NamedShardings (or None —
    everything recorded as replicated); its PartitionSpecs land in the
    manifest in mesh-independent wire form. ``keep`` rotates sibling
    snapshots sharing ``path``'s numeric-suffix prefix. ``async_``
    defaults to ``FLAGS_checkpoint_async``; the returned pending handle
    (async) resolves via :func:`wait_pending`.
    """
    import functools

    import jax

    if async_ is None:
        async_ = bool(flag("checkpoint_async"))
    # the capture runs on the calling (step) thread: its seconds are
    # checkpoint badput in the goodput ledger (deducted from the step
    # frame's compute when called inside one)
    with RecordEvent("checkpoint::capture"), _goodput().span("checkpoint"):
        named, _ = _named_leaves(state)
        names = [n for n, _ in named]
        leaves = _snapshot_leaves([l for _, l in named])
        if shardings is not None:
            specs = [
                _spec_wire_of(s)
                for s in jax.tree_util.tree_leaves(
                    shardings, is_leaf=_is_sharding)
            ]
            if len(specs) != len(names):
                raise CheckpointError(
                    f"shardings pytree has {len(specs)} leaves, state has "
                    f"{len(names)} — they must mirror each other")
        else:
            specs = [[] for _ in names]
    meta = {
        "step": -1 if step is None else int(step),
        "world": _flight()._safe_world(),
        "mesh_shape": dict(mesh.shape) if mesh is not None else None,
        "time": time.time(),
    }
    job = functools.partial(_write_snapshot, str(path), names, leaves,
                            specs, meta, keep, peer_timeout_s)
    if async_:
        _counter("checkpoint/async_saves").inc()
        return _SAVER.submit(job, label=str(path))
    job()
    return None


def _is_sharding(x):
    from jax.sharding import Sharding

    return isinstance(x, Sharding)


def _spec_wire_of(sharding):
    from ..parallel.sharding import spec_to_wire

    spec = getattr(sharding, "spec", None)
    return spec_to_wire(spec) if spec is not None else []


def _write_snapshot(final, names, leaves, specs, meta, keep,
                    peer_timeout_s):
    """Writer body (background thread in async mode). Every rank writes
    its shard file + commit record into the shared ``.tmp``; rank 0
    aggregates the manifest and publishes atomically."""
    from . import chaos

    rank = _flight()._safe_rank()
    world = int(meta.get("world") or 1)
    t0 = time.perf_counter()
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    # serialize + publish seconds: foreground checkpoint badput when the
    # save is sync (this runs on the step thread); automatically filed
    # as overlapped background work when the async writer thread runs it
    # under a live step frame (overlapped work costs no wall time)
    with _goodput().span("checkpoint"), \
            RecordEvent("checkpoint::serialize"):
        from ..framework import serialization as _ser

        entries = {}
        pieces = {}
        for name, leaf, spec in zip(names, leaves, specs):
            dtype = getattr(leaf, "dtype", None)
            if dtype is None:  # plain python scalar leaf
                dtype = np.asarray(leaf).dtype
            entries[name] = {
                "shape": [int(d) for d in np.shape(leaf)],
                "dtype": str(dtype),
                "spec": spec,
            }
            p = _leaf_pieces(leaf, rank, world)
            if p:
                pieces[name] = p
        shard_name = f"shard_r{rank}.pdshard"
        # dumps() materializes device shards to host here, on the writer
        # thread — the D2H the capture already started
        crc, size = write_bytes(
            os.path.join(tmp, shard_name),
            _ser.dumps({"rank": rank, "pieces": pieces}))
    chaos.inject("mid_save")
    frag = {"rank": rank, "world": world, "file": shard_name,
            "crc32": crc, "size": size}
    write_bytes(os.path.join(tmp, f"rank_{rank}.json"),
                json.dumps(frag).encode("utf-8"))
    _fsync_dir(tmp)
    if rank != 0:
        return  # publication is rank 0's job
    files = {shard_name: {"crc32": crc, "size": size}}
    deadline = time.monotonic() + float(
        _PEER_WAIT_S if peer_timeout_s is None else peer_timeout_s)
    for r in range(1, world):
        rec = _await_peer_commit(tmp, r, deadline)
        files[rec["file"]] = {"crc32": rec["crc32"], "size": rec["size"]}
    write_manifest(tmp, files, **meta, entries=entries)
    with _goodput().span("checkpoint"), RecordEvent("checkpoint::publish"):
        if os.path.exists(final):
            shutil.rmtree(final, ignore_errors=True)
        os.replace(tmp, final)
        _fsync_dir(os.path.dirname(final) or ".")
    _counter("checkpoint/saves").inc()
    _flight().record_event(
        "checkpoint_saved", path=final, step=meta["step"],
        world=world, ms=round((time.perf_counter() - t0) * 1e3, 3))
    led = _goodput().active_ledger()
    if led is not None:
        # re-publish the goodput sidecar after every snapshot
        # publication: a resume can never land on a checkpoint newer
        # than the ledger's lost-work pricing basis
        try:
            led.publish()
        except OSError:
            pass
    if keep:
        _rotate(final, int(keep))


def _await_peer_commit(tmp, r, deadline):
    frag_path = os.path.join(tmp, f"rank_{r}.json")
    while True:
        try:
            with open(frag_path, "r") as f:
                return json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            pass  # not yet written / mid-write
        if time.monotonic() > deadline:
            raise CheckpointError(
                f"rank {r} never committed its shard into {tmp} — "
                "snapshot left unpublished (torn .tmp is swept on resume)")
        time.sleep(0.02)


_STEP_DIR = re.compile(r"^(.*?)(\d+)$")


def _rotate(final, keep):
    """Drop oldest sibling snapshots beyond ``keep`` (same numeric-
    suffix prefix, e.g. step_*). Only intact (manifest-bearing) dirs
    count toward the quota; torn ones are swept separately."""
    parent = os.path.dirname(os.path.abspath(final))
    m = _STEP_DIR.match(os.path.basename(final))
    if not m:
        return
    prefix = m.group(1)
    found = []
    try:
        listing = os.listdir(parent)
    except FileNotFoundError:
        return
    for d in listing:
        dm = _STEP_DIR.match(d)
        if dm is None or dm.group(1) != prefix:
            continue
        if os.path.isfile(os.path.join(parent, d, MANIFEST)):
            found.append((int(dm.group(2)), d))
    for _, d in sorted(found)[:-keep]:
        shutil.rmtree(os.path.join(parent, d), ignore_errors=True)


# ---------------------------------------------------------------------------
# validate / load
# ---------------------------------------------------------------------------


def _read_manifest(path):
    mpath = os.path.join(path, MANIFEST)
    try:
        with open(mpath, "r") as f:
            manifest = json.load(f)
    except FileNotFoundError:
        raise CheckpointCorruptError(f"{path}: no {MANIFEST} (torn save)")
    except (json.JSONDecodeError, OSError) as e:
        raise CheckpointCorruptError(f"{path}: unreadable manifest: {e}")
    if not isinstance(manifest, dict) or "files" not in manifest:
        raise CheckpointCorruptError(f"{path}: malformed manifest")
    return manifest


def _read_checked(path, fname, meta):
    fpath = os.path.join(path, fname)
    try:
        with open(fpath, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        raise CheckpointCorruptError(f"{path}: missing file {fname}")
    crc = zlib.crc32(data) & 0xFFFFFFFF
    if crc != int(meta["crc32"]) or len(data) != int(meta["size"]):
        raise CheckpointCorruptError(
            f"{path}/{fname}: checksum/size mismatch "
            f"(crc {crc:#x} != {int(meta['crc32']):#x} or "
            f"size {len(data)} != {meta['size']})")
    return data


def validate(path):
    """Manifest + every listed file present with matching CRC32/size.
    Returns the manifest; raises CheckpointCorruptError otherwise."""
    manifest = _read_manifest(path)
    for fname, meta in manifest["files"].items():
        _read_checked(path, fname, meta)
    return manifest


def load(path):
    """Read + verify a snapshot; returns ``(flat, manifest)`` where
    ``flat`` maps leaf name -> fully-assembled global numpy array."""
    from ..framework import serialization as _ser

    manifest = _read_manifest(path)
    pieces = {}
    for fname, meta in manifest["files"].items():
        data = _read_checked(path, fname, meta)
        if not fname.endswith(".pdshard"):
            continue
        payload = _ser.loads(data, return_numpy=True)
        for name, ps in payload["pieces"].items():
            pieces.setdefault(name, []).extend(ps)
    entries = manifest.get("entries", {})
    flat = {
        name: _assemble(name, entry, pieces.get(name, []))
        for name, entry in entries.items()
    }
    return flat, manifest


def sweep_tmp(parent):
    """Remove torn ``*.tmp`` snapshot dirs left by mid-save deaths.
    Called on startup/resume, before any new save targets the dir."""
    removed = []
    try:
        listing = os.listdir(parent)
    except FileNotFoundError:
        return removed
    for d in listing:
        full = os.path.join(parent, d)
        if d.endswith(".tmp") and os.path.isdir(full):
            shutil.rmtree(full, ignore_errors=True)
            removed.append(full)
    if removed:
        _flight().record_event("checkpoint_tmp_swept", parent=str(parent),
                               count=len(removed))
    return removed


def latest_checkpoint(parent, prefix="step_"):
    """Newest *intact* snapshot under ``parent``: scans ``<prefix>N``
    dirs newest-first, validates each, skips (and records) corrupt or
    manifest-less ones. Returns ``(path, manifest)`` or ``(None, None)``."""
    try:
        listing = os.listdir(parent)
    except FileNotFoundError:
        return None, None
    candidates = []
    for d in listing:
        if not d.startswith(prefix) or d.endswith(".tmp"):
            continue
        try:
            candidates.append((int(d[len(prefix):]), d))
        except ValueError:
            continue
    for _, d in sorted(candidates, reverse=True):
        full = os.path.join(parent, d)
        try:
            manifest = validate(full)
        except CheckpointCorruptError as e:
            _counter("checkpoint/corrupt_skipped").inc()
            _flight().record_event("checkpoint_skipped_corrupt",
                                   path=full, error=str(e)[:200])
            continue
        return full, manifest
    return None, None


# ---------------------------------------------------------------------------
# train-step integration (TrainStepFn / ShardedTrainStep)
# ---------------------------------------------------------------------------


def save_train_step(step_obj, path, step=None, async_=None, keep=None,
                    peer_timeout_s=None):
    """Snapshot a train step's device state (``.state`` + its
    ``.state_shardings``/``.mesh`` when present — ShardedTrainStep) with
    full resharding metadata."""
    return save(
        path,
        step_obj.state,
        getattr(step_obj, "state_shardings", None),
        step=step,
        mesh=getattr(step_obj, "mesh", None),
        keep=keep,
        async_=async_,
        peer_timeout_s=peer_timeout_s,
    )


def restore_train_step(step_obj, path):
    """Load a snapshot into a live train step, re-slicing every leaf
    onto the step's *current* mesh/shardings (which may differ in world
    size from the save — the reshard-on-resume path). Returns the
    manifest (callers read ``manifest['step']`` to resume the loop)."""
    import jax
    import jax.numpy as jnp

    with RecordEvent("checkpoint::restore"), _goodput().span("restore"):
        flat, manifest = load(path)
        named, treedef = _named_leaves(step_obj.state)
        names = [n for n, _ in named]
        missing = sorted(set(names) - set(flat))
        extra = sorted(set(flat) - set(names))
        if missing or extra:
            raise CheckpointError(
                f"{path} does not match this train step's state: "
                f"missing={missing[:5]} extra={extra[:5]}")
        shardings = getattr(step_obj, "state_shardings", None)
        if shardings is not None:
            sh_leaves = jax.tree_util.tree_leaves(
                shardings, is_leaf=_is_sharding)
        else:
            sh_leaves = [None] * len(names)
        new_leaves = []
        resharded = False
        for (name, tmpl), sh in zip(named, sh_leaves):
            host = flat[name]
            tshape = tuple(np.shape(tmpl))
            if tuple(host.shape) != tshape:
                raise CheckpointError(
                    f"{name}: checkpoint shape {host.shape} != live state "
                    f"shape {tshape}")
            host = np.asarray(host, dtype=_np_dtype(
                str(getattr(tmpl, "dtype", host.dtype))))
            if sh is not None:
                with RecordEvent("checkpoint::reshard"):
                    arr = jax.make_array_from_callback(
                        tshape, sh, lambda idx, h=host: h[idx])
                resharded = True
            else:
                arr = jnp.asarray(host)
            # owned device copy: on CPU, asarray/make_array may alias the
            # host numpy buffer zero-copy — the compiled step DONATES its
            # state, and donating an aliased buffer frees memory numpy
            # owns (heap corruption). Same hazard TrainStepFn.__init__
            # guards against for the initial eager state.
            new_leaves.append(jnp.copy(arr))
        step_obj.state = jax.tree_util.tree_unflatten(treedef, new_leaves)
    _counter("checkpoint/restores").inc()
    mesh = getattr(step_obj, "mesh", None)
    world_changed = (
        int(manifest.get("world") or 1) != _flight()._safe_world()
        or (mesh is not None
            and manifest.get("mesh_shape") not in (None, dict(mesh.shape)))
    )
    if resharded and world_changed:
        _counter("checkpoint/reshards").inc()
        _flight().record_event(
            "checkpoint_resharded", path=str(path),
            saved_world=manifest.get("world"),
            saved_mesh=json.dumps(manifest.get("mesh_shape")),
            new_world=_flight()._safe_world(),
            new_mesh=json.dumps(dict(mesh.shape) if mesh else None))
    _flight().record_event("checkpoint_restored", path=str(path),
                           step=manifest.get("step", -1))
    led = _goodput().active_ledger()
    if led is not None:
        # price the resume: steps the previous life committed AFTER this
        # manifest must be recomputed — the ledger charges them to
        # lost_work as they re-commit
        led.note_resume(int(manifest.get("step", -1)))
    return manifest


# ---------------------------------------------------------------------------
# background writer
# ---------------------------------------------------------------------------


class _Pending:
    def __init__(self, label):
        self.label = label
        self.error = None
        self._done = threading.Event()

    @property
    def done(self):
        return self._done.is_set()

    def wait(self, timeout=None, raise_error=True):
        if not self._done.wait(timeout):
            raise CheckpointError(
                f"checkpoint save {self.label!r} still pending after "
                f"{timeout}s")
        if raise_error and self.error is not None:
            raise self.error
        return self


class AsyncSaver:
    """One FIFO writer thread: snapshots publish in submission order
    (rotation and resume both depend on monotonic publication)."""

    def __init__(self):
        self._q = queue.Queue()
        self._lock = threading.Lock()
        self._thread = None
        self._pending = []

    def submit(self, fn, label=""):
        p = _Pending(label)
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="ptpu-ckpt-writer", daemon=True)
                self._thread.start()
            # prune only successes: an errored pending must survive here
            # until a wait_pending() consumes (and can re-raise) it — a
            # dropped snapshot must not fail silently
            self._pending = [x for x in self._pending
                             if not x.done or x.error is not None]
            self._pending.append(p)
        self._q.put((fn, p))
        return p

    def _run(self):
        while True:
            fn, p = self._q.get()
            try:
                fn()
            except BaseException as e:  # surfaced via wait_pending
                p.error = e
                try:
                    _counter("checkpoint/save_errors").inc()
                    _flight().record_event(
                        "checkpoint_save_failed", label=p.label,
                        error=f"{type(e).__name__}: {e}"[:200])
                except Exception:
                    pass
            finally:
                p._done.set()

    def wait_pending(self, timeout=None, raise_errors=True):
        """Drain every submitted save; with ``raise_errors`` the first
        writer failure (or a timeout) re-raises here — a dropped
        snapshot must not fail silently. Saves that outlive ``timeout``
        are put BACK on the pending list so a later drain still tracks
        them."""
        with self._lock:
            pending, self._pending = self._pending, []
        first = None
        unfinished = []
        for p in pending:
            if not p._done.wait(timeout):
                unfinished.append(p)
                continue
            if first is None and p.error is not None:
                first = p.error
        if unfinished:
            with self._lock:
                self._pending = unfinished + self._pending
        if raise_errors:
            if first is not None:
                raise first
            if unfinished:
                raise CheckpointError(
                    f"{len(unfinished)} checkpoint saves still pending "
                    f"after {timeout}s (first: {unfinished[0].label!r})")
        return first


_SAVER = AsyncSaver()


def wait_pending(timeout=None, raise_errors=True):
    """Block until all in-flight async saves are durable (or failed)."""
    return _SAVER.wait_pending(timeout=timeout, raise_errors=raise_errors)


def submit(fn, label=""):
    """Queue durable-write work on the shared FIFO writer thread
    (auto_checkpoint's epoch snapshots ride the same queue, so epoch
    and step snapshots publish in one global order)."""
    return _SAVER.submit(fn, label)
