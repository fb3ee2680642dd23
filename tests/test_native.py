"""Native C++ component tests (shm ring transport)."""
import multiprocessing as mp
import os

import numpy as np
import pytest

from paddle_tpu._native import ShmRing, available

pytestmark = pytest.mark.skipif(
    not available(), reason="native toolchain unavailable"
)


def test_ring_roundtrip_bytes():
    r = ShmRing(capacity=1 << 20)
    try:
        r.push_bytes(b"hello")
        r.push_bytes(b"world" * 1000)
        assert r.pop_bytes() == b"hello"
        assert r.pop_bytes() == b"world" * 1000
        assert r.empty()
    finally:
        r.close()


def test_ring_pickled_objects():
    r = ShmRing(capacity=1 << 20)
    try:
        r.put((7, np.arange(5)))
        seq, arr = r.get()
        assert seq == 7
        np.testing.assert_array_equal(arr, np.arange(5))
    finally:
        r.close()


def test_ring_wraparound():
    r = ShmRing(capacity=4096)
    try:
        payload = os.urandom(1000)
        for i in range(20):  # cycles the 4KB ring several times
            r.push_bytes(payload)
            assert r.pop_bytes() == payload
    finally:
        r.close()


def test_ring_too_large_record():
    r = ShmRing(capacity=1024)
    try:
        with pytest.raises(ValueError):
            r.push_bytes(b"x" * 2048)
    finally:
        r.close()


def test_ring_names_distinct_where_ids_agree(monkeypatch):
    """Two live objects 64 KiB apart agree in ``id(obj) & 0xFFFF``; rings
    named by it were then ONE segment with two producers. Forced here by
    making every id agree: default-named rings and the rings of two live
    DataLoader iterators must still be distinct segments."""
    import paddle_tpu._native as native
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.io import dataloader as dl_mod

    for mod in (native, dl_mod):
        monkeypatch.setattr(mod, "id", lambda obj: 0x10000, raising=False)
    a, b = ShmRing(capacity=4096), ShmRing(capacity=4096)
    try:
        assert a.name != b.name
        a.push_bytes(b"only in a")
        assert b.empty()
    finally:
        a.close()
        b.close()
    ds = TensorDataset([np.arange(8, dtype=np.float32)])
    its = [iter(DataLoader(ds, batch_size=2, num_workers=2,
                           use_shared_memory=True, use_buffer_reader=False))
           for _ in range(2)]
    try:
        names = [n for it in its for n in it.rings]
        assert len(names) == 4 and len(set(names)) == 4
    finally:
        for it in its:
            it.shutdown()


def _producer(name, n):
    ring = ShmRing(name, capacity=1 << 20, owner=False)
    for i in range(n):
        ring.put((i, np.full(100, i)))
    ring.close(unlink=False)


def test_ring_cross_process():
    r = ShmRing(capacity=1 << 20)
    try:
        ctx = mp.get_context("fork")
        p = ctx.Process(target=_producer, args=(r.name, 50))
        p.start()
        for i in range(50):
            seq, arr = r.get()
            assert seq == i
            np.testing.assert_array_equal(arr, np.full(100, i))
        p.join()
    finally:
        r.close()


def test_dataloader_uses_shm_transport():
    from paddle_tpu.io import DataLoader, TensorDataset

    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    y = np.arange(20, dtype=np.int64)
    ds = TensorDataset([x, y])
    loader = DataLoader(ds, batch_size=5, num_workers=2,
                        use_shared_memory=True, use_buffer_reader=False)
    it = iter(loader)
    assert getattr(it, "rings", None), "shm rings not engaged"
    batches = list(it)
    assert len(batches) == 4
    np.testing.assert_array_equal(batches[0][1], [0, 1, 2, 3, 4])
