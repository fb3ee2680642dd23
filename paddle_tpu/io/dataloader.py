"""DataLoader with multiprocess workers and device prefetch.

Reference parity: fluid/reader.py DataLoader :123 / DygraphGeneratorLoader
:697 (worker subprocess loop :870), operators/reader/buffered_reader.cc
(double-buffered H2D prefetch), memory/allocation/mmap_allocator.cc
(shared-memory tensor transport between workers and the trainer).

TPU-native: worker processes serialize numpy batches over the native
shared-memory ring (paddle_tpu._native.shm_ring, C++) — falling back to
multiprocessing.Queue pickling — and the main process keeps
``prefetch_factor`` batches in flight with async jax.device_put, so the
accelerator never stalls on input (buffered_reader.cc's role).
"""
from __future__ import annotations

import atexit
import itertools
import multiprocessing as mp
import queue as queue_mod
import threading
import time
import weakref

import numpy as np

from ..monitor import record_input_wait_ms, registry as _mon
from ..monitor import flight_recorder as _flight
from ..profiler import RecordEvent
from .dataset import IterableDataset
from .sampler import BatchSampler

# stop sentinel must survive pickling across the process boundary (an
# object() loses identity in the worker), so use None
_MP_STOP = None


def default_collate_fn(batch):
    """Stack samples into batch arrays (fluid/dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(
            default_collate_fn([s[i] for s in batch])
            for i in range(len(sample))
        )
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    from ..framework.tensor import Tensor

    if isinstance(sample, Tensor):
        return np.stack([s.numpy() for s in batch])
    return np.asarray(batch)


def _worker_loop(dataset, index_queue, data_queue, collate_fn, ring_name,
                 ring_capacity):
    """Worker process body (reader.py:870 _reader_process_loop).

    Results travel over the native shared-memory ring when available
    (mmap_allocator.cc transport equivalent); the mp.Queue is the fallback
    and the error channel.
    """
    ring = None
    if ring_name:
        try:
            from .._native import ShmRing

            ring = ShmRing(ring_name, capacity=ring_capacity, owner=False)
        except Exception:
            ring = None
    try:
        while True:
            task = index_queue.get()
            if task is None:
                break
            seq, indices = task
            try:
                batch = collate_fn([dataset[i] for i in indices])
                if ring is not None:
                    try:
                        ring.put((seq, batch))
                        data_queue.put((seq, ring_name, None))  # ready signal
                        continue
                    except ValueError:  # batch larger than the ring
                        pass
                data_queue.put((seq, batch, None))
            except Exception as e:  # propagate to main process
                data_queue.put((seq, None, e))
    except KeyboardInterrupt:
        pass
    finally:
        if ring is not None:
            ring.close(unlink=False)


class _MultiprocessIter:
    def __init__(self, loader):
        self.loader = loader
        ds = loader.dataset
        self.batches = list(iter(loader.batch_sampler))
        ctx = mp.get_context("fork")
        self.index_queue = ctx.Queue()
        self.data_queue = ctx.Queue(maxsize=loader.num_workers * loader.prefetch_factor)
        # one shared-memory ring per worker (SPSC); None disables
        self.rings = {}
        ring_names = [None] * loader.num_workers
        ring_cap = 64 << 20
        if loader.use_shared_memory:
            try:
                from .._native import ShmRing, available, ring_name

                if available():
                    for _ in range(loader.num_workers):
                        name = ring_name("dl")
                        self.rings[name] = ShmRing(
                            name, capacity=ring_cap, owner=True
                        )
                    ring_names = list(self.rings.keys())
            except Exception:
                self.rings = {}
        self.workers = [
            ctx.Process(
                target=_worker_loop,
                args=(ds, self.index_queue, self.data_queue,
                      loader.collate_fn, ring_names[i], ring_cap),
                daemon=True,
            )
            for i in range(loader.num_workers)
        ]
        for w in self.workers:
            w.start()
        # worker-lifecycle breadcrumb: a dump taken while the main thread
        # is parked in worker_wait shows exactly which worker pids were
        # supposed to be feeding it (and whether shm rings were in play)
        _flight.record_event(
            "dataloader_workers_start", workers=len(self.workers),
            pids=[w.pid for w in self.workers],
            batches=len(self.batches), shm_rings=len(self.rings))
        atexit.register(self.shutdown)
        self._send = 0
        self._recv = 0
        self._reorder = {}
        # pre-dispatch
        for _ in range(loader.num_workers * loader.prefetch_factor):
            self._dispatch()

    def _dispatch(self):
        if self._send < len(self.batches):
            self.index_queue.put((self._send, self.batches[self._send]))
            self._send += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._recv >= len(self.batches):
            self.shutdown()
            raise StopIteration
        if self._recv not in self._reorder:
            # the main process is BLOCKED on workers here — the span/stat
            # that tells an input-bound run from a compute-bound one
            with RecordEvent("dataloader::worker_wait"):
                t0 = time.perf_counter()
                while self._recv not in self._reorder:
                    seq, batch, err = self.data_queue.get()
                    if err is not None:
                        self.shutdown()
                        raise err
                    if isinstance(batch, str) and batch in self.rings:
                        # ready-signal: payload sits in that worker's ring
                        rseq, batch = self.rings[batch].get()
                        seq = rseq
                    self._reorder[seq] = batch
                _mon.histogram("io/worker_wait_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
        batch = self._reorder.pop(self._recv)
        self._recv += 1
        self._dispatch()
        return batch

    def shutdown(self):
        if self.workers:
            _flight.record_event(
                "dataloader_workers_stop", workers=len(self.workers),
                delivered=getattr(self, "_recv", 0),
                dispatched=getattr(self, "_send", 0))
        for _ in self.workers:
            try:
                self.index_queue.put(_MP_STOP)
            except Exception:
                pass
        for w in self.workers:
            w.join(timeout=1)
            if w.is_alive():
                w.terminate()
        self.workers = []
        for ring in getattr(self, "rings", {}).values():
            try:
                ring.close(unlink=True)
            except Exception:
                pass
        self.rings = {}


class _DevicePrefetcher:
    """buffered_reader.cc equivalent: keep N batches already on device,
    with the host fetch + H2D enqueue OVERLAPPING the consumer's step.

    Under ``FLAGS_io_prefetch_overlap`` (default) a background thread
    owns the upstream ``next()`` (parse/collate wait) and the
    ``jax.device_put`` enqueue, double-buffered through a bounded queue
    of ``depth`` device-resident batches — the consumer's ``__next__``
    is a queue pop, so batch N+1's transfer is in flight while step N
    computes and the only consumer-visible input wait is a genuine
    underrun (visible as the monitor's ``input_wait_ratio``). With the
    flag off, the legacy synchronous refill runs inline in ``__next__``
    (the consumer pays parse + enqueue on the step path). Shared by the
    DataLoader's buffer reader and Executor.train_from_dataset (via
    DatasetBase._iter_device_batches)."""

    _DONE = object()

    def __init__(self, it, depth=2, to_device=None):
        from ..flags import flag

        self.it = it
        self.depth = max(1, int(depth))
        self.to_device = to_device
        self._overlap = bool(flag("io_prefetch_overlap"))
        if self._overlap:
            self._q = queue_mod.Queue(maxsize=self.depth)
            self._stop = threading.Event()
            self._done = False
            # the fill thread closes ONLY over (it, q, stop) — never
            # self: a thread frame referencing the prefetcher would keep
            # it reachable forever, so an abandoned iterator could never
            # be collected and the finalizer below could never fire
            self._thread = threading.Thread(
                target=_prefetch_fill_loop,
                args=(self.it, self.to_device, self._q, self._stop,
                      self._DONE),
                name="ptpu-h2d-prefetch", daemon=True)
            self._thread.start()
            # abandonment shutdown: when the consumer drops the iterator
            # mid-epoch, GC runs this and the fill thread exits at its
            # next 0.1s stop-check instead of spinning forever
            self._finalizer = weakref.finalize(self, self._stop.set)
        else:
            self.buf = []
            self._fill()

    def close(self):
        """Stop the background fill (idempotent)."""
        if self._overlap:
            self._stop.set()

    # -- legacy synchronous path --------------------------------------------

    def _fill(self):
        while len(self.buf) < self.depth:
            try:
                self.buf.append(
                    _prefetch_prepare(self.it, self.to_device))
            except StopIteration:
                return

    def __iter__(self):
        return self

    def __next__(self):
        # consumer-side wall time in here is input wait: with overlap on
        # it is the queue-pop wait (a true underrun); with it off, the
        # inline refill's upstream parse/collate + enqueue
        t0 = time.perf_counter()
        if self._overlap:
            if self._done:
                raise StopIteration  # terminal: never block again
            while True:
                try:
                    item = self._q.get(timeout=0.1)
                    break
                except queue_mod.Empty:
                    # after close() the fill thread refuses further puts
                    # (even its DONE tail), so an empty queue is
                    # terminal — without this check a consumer would
                    # block forever waiting for a sentinel that can
                    # never arrive
                    if self._stop.is_set():
                        self._done = True
                        raise StopIteration
            if item is self._DONE:
                self._done = True
                self.close()
                raise StopIteration
            if isinstance(item, BaseException):
                self._done = True
                self.close()
                raise item
            batch = item
        else:
            if not self.buf:
                raise StopIteration
            batch = self.buf.pop(0)
            self._fill()
        _mon.counter("io/batches").inc()
        # feeds io/input_wait_ms_total (counter), the monitor's window
        # input-wait ratio, and the goodput ledger's input_wait phase
        record_input_wait_ms((time.perf_counter() - t0) * 1e3)
        return batch


def _prefetch_prepare(it, to_device):
    """One upstream fetch + device enqueue (both prefetcher paths)."""
    with RecordEvent("dataloader::prefetch_fill"):
        batch = next(it)
    if to_device:
        import jax

        # async enqueue of the H2D copy (the actual transfer overlaps
        # the consumer's step; the span shows enqueue stalls when the
        # transfer queue backs up)
        with RecordEvent("dataloader::h2d"):
            batch = jax.tree_util.tree_map(jax.device_put, batch)
    return batch


def _prefetch_fill_loop(it, to_device, q, stop, done_sentinel):
    """_DevicePrefetcher's background fill (module-level on purpose —
    see the constructor: the thread must not keep the prefetcher
    alive). Exceptions travel to the consumer through the queue."""

    def put(item) -> bool:
        # bounded put that stays responsive to shutdown: an abandoned
        # consumer must not leave the thread parked on a full queue
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    tail = done_sentinel
    try:
        while not stop.is_set():
            try:
                item = _prefetch_prepare(it, to_device)
            except StopIteration:
                break
            except BaseException as e:  # surface on the consumer side
                tail = e
                break
            if not put(item):
                return  # consumer abandoned the iterator
    finally:
        put(tail)


class _AccountedIter:
    """Input-wait accounting for the unbuffered path (the buffered path
    accounts inside _DevicePrefetcher.__next__). Attribute access
    proxies to the wrapped iterator so callers still reach the
    multiprocess machinery (rings, shutdown) underneath."""

    def __init__(self, it):
        self._it = it

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        batch = next(self._it)
        _mon.counter("io/batches").inc()
        record_input_wait_ms((time.perf_counter() - t0) * 1e3)
        return batch

    def __getattr__(self, name):
        return getattr(self._it, name)


class DataLoader:
    """paddle.io.DataLoader surface (fluid/reader.py:123)."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        self.return_list = return_list
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _single_iter(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for indices in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        # one event per epoch: correlates "which epoch / which mode" with
        # whatever the rest of the ring shows hanging
        _flight.record_event(
            "dataloader_epoch",
            workers=self.num_workers if not self._iterable_mode else 0,
            iterable=self._iterable_mode,
            buffered=self.use_buffer_reader)
        if self.num_workers > 0 and not self._iterable_mode:
            it = iter(_MultiprocessIter(self))
        else:
            it = self._single_iter()
        if self.use_buffer_reader:
            return iter(
                _DevicePrefetcher(it, depth=self.prefetch_factor,
                                  to_device=True)
            )
        return _AccountedIter(it)
