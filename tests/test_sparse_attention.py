"""Block-sparse attention over a ring with a pooled ring beside it
(nn/sparse_attention.py) and its cache kind (generation/cache.py
SparseKVKind), at a preset that keeps the published ratios (kernel = 2 x
stride, block = 4 x stride): stride 2, kernel 4, block 8, one initial
block, a window of 16 (two blocks), top-6, dense_len 64, 8 heads on 2
K/V heads. float32, seeded; the plain reference is the benchmark's
(benchmark/configs/minicpm-sala-9b/reference.py `_sparse` / `choose`),
which writes the six steps query by query and imports nothing of the
program.

Tolerances: both sides are float32 and differ in the order of their sums
(key chunks joined by running maxima against one softmax a row): 2e-5 on
outputs of size 0.1-1; a query that attends a block its selection left
out, or all of them, moves an output by 1e-2 and more (the planted
faults at the end)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation import cache as gcache
from paddle_tpu.nn import SparseCache, SparseConfig, SparseGQAttention
from paddle_tpu.nn import sparse_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "configs", "minicpm-sala-9b",
                        "reference.py")
    spec = importlib.util.spec_from_file_location("sala_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
SPARSE = SparseConfig(kernel=4, stride=2, block=8, init_blocks=1, window=16,
                      topk=6, dense_len=64)
SC = dict(kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
          window_size=16, topk=6, dense_len=64)
WIDTHS = dict(h=32, hq=8, hkv=2, d=8)
STORE = 128


def _layer(seed=3, **kw):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    lay = SparseGQAttention(32, 8, 2, 8, sparse=SPARSE, q_block=16,
                            key_chunk=32, initializer_range=0.3, **kw)
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    lay.q_norm._array = 1.0 + 0.3 * jax.random.normal(k[0], (8,))
    lay.k_norm._array = 1.0 + 0.3 * jax.random.normal(k[1], (8,))
    return lay


def _x(t, seed=0):
    return jax.random.normal(jax.random.PRNGKey(100 + seed), (t, 32))


def _want(lay, x, keep=None):
    """The reference's layer on one sequence, given the layer's own
    weights: (y [T, hidden], what the selection saw)."""
    w = {n: p._array.astype(jnp.float32) for n, p in lay.named_parameters()}

    @jax.jit
    def run(x, w):
        with jax.default_matmul_precision("highest"):
            return REF._sparse(x, w, WIDTHS, SC, REF._mm(False), 1e-6, keep)

    return run(x, w)


def _fresh(lay, rows=1, store=STORE):
    kind = gcache.sparse_kv(2, 8, SPARSE)
    return kind.wrap(kind.arrays(rows, store, "float32"),
                     jnp.zeros((rows,), jnp.int32))


# lengths under, at and over dense_len; multiples of block and stride,
# of stride alone, of neither
LENGTHS = [5, 40, 63, 64, 65, 72, 101, 128]


@pytest.mark.parametrize("t", LENGTHS)
def test_the_layer_is_the_references_six_steps(t):
    lay, x = _layer(), _x(t)
    want, _ = _want(lay, x)
    got = jax.jit(lambda x: lay(x[None])[0])(x)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _program_choice(saw, t):
    """`select_blocks` on the reference's normalised q and k, every
    position at once: [hkv, T, blocks] bool over the padded length."""
    q, k = saw["q"], saw["k"]
    full = -(-t // SPARSE.block) * SPARSE.block
    k = jnp.pad(k, ((0, full - t), (0, 0), (0, 0))).transpose(1, 0, 2)
    pos = jnp.broadcast_to(jnp.arange(t), (2, t))
    return jax.jit(lambda q, k: sa.select_blocks(
        q.transpose(1, 2, 0, 3), sa.pool_keys(k, SPARSE), pos, SPARSE,
        8 ** -0.5))(q, k)


@pytest.mark.parametrize("t", LENGTHS)
def test_every_position_chooses_what_the_reference_chooses(t):
    lay, x = _layer(), _x(t, 1)
    _, saw = _want(lay, x, keep=(0, t))
    want = np.asarray(saw["chosen"]).transpose(1, 0, 2)   # [hkv, T, blocks]
    got = np.asarray(_program_choice(saw, t))
    assert (got == want).all()
    newest = np.arange(t) // 8
    for p in range(t):
        # forced: block 0 and the two newest; nothing beyond the newest
        assert got[:, p, 0].all() and got[:, p, newest[p]].all()
        assert got[:, p, max(newest[p] - 1, 0)].all()
        assert not got[:, p, newest[p] + 1:].any()
        n = got[:, p].sum(-1)
        assert (n == (newest[p] + 1 if p + 1 < 64 else 6)).all()


@pytest.mark.parametrize("real,bucket", [(40, 64), (70, 128), (101, 128),
                                         (64, 64), (65, 128), (128, 128)])
def test_a_padded_prefill_fills_the_rings_and_reads_real_rows(real, bucket):
    lay, x = _layer(), _x(real, 2)
    want, saw = _want(lay, x, keep=(0, real))
    padded = jnp.pad(x, ((0, bucket - real), (0, 0)))[None]
    got, cache = jax.jit(lambda x, c: lay(x, cache=c))(padded, _fresh(lay))
    np.testing.assert_allclose(np.asarray(got[0, :real]), np.asarray(want),
                               atol=2e-5)
    k = saw["k"].transpose(1, 0, 2)                       # [hkv, real, d]
    np.testing.assert_allclose(np.asarray(cache.k[0, :, :real]),
                               np.asarray(k), atol=1e-6)
    rows = (real - SPARSE.kernel) // SPARSE.stride + 1    # that exist
    pooled = np.stack([np.asarray(k[:, 2 * j:2 * j + 4]).mean(1)
                       for j in range(rows)], 1)
    np.testing.assert_allclose(np.asarray(cache.ck[0, :, :rows]), pooled,
                               atol=1e-6)


@pytest.mark.parametrize("n,m", [(40, 30), (60, 10), (64, 8), (70, 25),
                                 (17, 3)])
def test_prefill_then_steps_is_the_full_sequence(n, m):
    """A decode step at t attends what the full forward's row t attends
    (so it chooses what the prefill's row t chooses), across dense_len;
    the pooled ring stays the pooling of the K ring."""
    lay, x = _layer(), _x(n + m, 3)
    want, saw = _want(lay, x, keep=(0, n + m))
    bucket = next(b for b in (32, 64, 128) if b >= n)
    step = jax.jit(lambda x, c: lay(x, cache=c))
    _, cache = step(jnp.pad(x[:n], ((0, bucket - n), (0, 0)))[None],
                    _fresh(lay))
    for t in range(n, n + m):
        cache = SparseCache(*cache[:3], jnp.asarray([t], jnp.int32))
        got, cache = step(x[None, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(got[0, 0]),
                                   np.asarray(want[t]), atol=2e-5)
    rows = (n + m - SPARSE.kernel) // SPARSE.stride + 1
    np.testing.assert_allclose(
        np.asarray(cache.ck[0, :, :rows]),
        np.asarray(sa.pool_keys(cache.k[0], SPARSE)[:, :rows]), atol=1e-6)
    # the choice itself, decode's form against the prefill's
    t = n + m - 1
    q = saw["q"].transpose(1, 2, 0, 3)                    # [hkv, g, T, d]
    one = sa.select_blocks(q[None, :, :, t:t + 1], cache.ck,
                           jnp.full((1, 2, 1), t), SPARSE, 8 ** -0.5)
    assert (np.asarray(one[0, :, 0, :(t // 8) + 1])
            == np.asarray(saw["chosen"][t])).all()


def test_slots_on_either_side_of_dense_len_share_one_step():
    """One batched step: a slot under dense_len reads all its live
    blocks, a slot over it its six, each as if it were alone."""
    lay = _layer()
    lens = [20, 63, 64, 100]
    kind = gcache.sparse_kv(2, 8, SPARSE)
    rings = [a for a in kind.arrays(len(lens), STORE, "float32")]
    xs, wants = [], []
    fill = jax.jit(lambda x, c: lay(x, cache=c))
    for i, n in enumerate(lens):
        x = _x(n + 1, 10 + i)
        wants.append(_want(lay, x)[0][n])
        _, c = fill(jnp.pad(x[:n], ((0, STORE - n), (0, 0)))[None],
                    _fresh(lay))
        rings = [r.at[i].set(a[0]) for r, a in zip(rings, c[:3])]
        xs.append(x[n])
    got, _ = fill(jnp.stack(xs)[:, None],
                  SparseCache(*rings, jnp.asarray(lens, jnp.int32)))
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(jnp.stack(wants)), atol=2e-5)


def test_past_the_rings_end_a_token_takes_the_last_rows_place():
    """The kind's statement: no wrap; position store - 1 is rewritten,
    the pooled ring follows, and the step is the reference's on what
    the ring then holds."""
    store = 80
    lay, x = _layer(), _x(store + 3, 4)
    step = jax.jit(lambda x, c: lay(x, cache=c))
    _, cache = step(x[None, :64], _fresh(lay, store=store))
    kept = list(range(store - 1))
    for t in range(64, store + 3):
        cache = SparseCache(*cache[:3], jnp.asarray([t], jnp.int32))
        got, cache = step(x[None, t:t + 1], cache)
        if t >= store:
            held = x[jnp.asarray(kept + [t])]
            np.testing.assert_allclose(
                np.asarray(got[0, 0]), np.asarray(_want(lay, held)[0][-1]),
                atol=2e-5)
    rows = (store - SPARSE.kernel) // SPARSE.stride + 1
    np.testing.assert_allclose(
        np.asarray(cache.ck[0, :, :rows]),
        np.asarray(sa.pool_keys(cache.k[0], SPARSE)[:, :rows]), atol=1e-6)


# -- the cache kind -----------------------------------------------------------

def test_the_kind_counts_its_bytes_and_what_a_step_reads():
    kind = gcache.sparse_kv(2, 8, SPARSE)
    assert gcache.is_layer_kinds([kind, gcache.state(((4, 8, 8),),
                                                     ("float32",))])
    arrays = kind.arrays(3, STORE, "float32")
    assert [a.shape for a in arrays] == [(3, 2, 128, 8), (3, 2, 128, 8),
                                         (3, 2, 64, 8)]
    assert kind.slot_nbytes(STORE, "float32") * 3 == gcache.cache_nbytes(
        arrays)
    assert kind.bytes_per_token("float32") == 2 * 2 * 8 * 4 + 2 * 8 * 4 // 2
    assert kind.ring(STORE) == STORE and not kind.continues
    with pytest.raises(ValueError):
        kind.arrays(1, 100, "float32")
    # what steps 1-6 read, against a count made from the reference's
    # chosen sets: K/V rows of the chosen blocks up to t, and half a row
    # for every pooled key scored
    lay, t = _layer(), 111
    _, saw = _want(lay, _x(t + 1, 5), keep=(0, t + 1))
    live = np.asarray([20, 63, 64, 90, t + 1])
    want = []
    for n in live:
        chosen = np.asarray(saw["chosen"][n - 1, 0])
        rows = sum(min(8, n - 8 * b) for b in np.flatnonzero(chosen))
        pooled = 0 if n < 64 else (n - 4) // 2 + 1
        want.append(rows + (pooled + 1) // 2)
        assert kind.blocks_read(live)[len(want) - 1] == chosen.sum()
    assert kind.rows_read(live).tolist() == want
    assert kind.blocks_live(live).tolist() == [3, 8, 8, 12, 14]
    # what this implementation brings: gather_blocks whole blocks (all
    # of a 64-row dense context: 8) and the pooled ring, whatever is live
    assert SPARSE.gather_blocks == 8
    assert kind.rows_fetched(live, STORE, "float32").tolist() \
        == [8 * 8 + 64 // 2] * 5


# -- planted faults -----------------------------------------------------------

def _dense_for_sparse(monkeypatch):
    monkeypatch.setattr(sa, "select_blocks", lambda q, pooled, t, cfg, s: (
        jnp.arange(pooled.shape[-2] * cfg.stride // cfg.block)
        <= t[..., None] // cfg.block))


def _no_forced_blocks(monkeypatch):
    sound = sa.select_blocks
    monkeypatch.setattr(sa, "select_blocks", lambda q, p, t, cfg, s: sound(
        q, p, t, cfg._replace(init_blocks=0, window=0), s))


def _top_three(monkeypatch):
    sound = sa.select_blocks
    monkeypatch.setattr(sa, "select_blocks", lambda q, p, t, cfg, s: sound(
        q, p, t, cfg._replace(topk=3), s))


@pytest.mark.parametrize("plant", [_dense_for_sparse, _no_forced_blocks,
                                   _top_three])
def test_a_planted_fault_is_far_outside_the_tolerance(plant, monkeypatch):
    lay, x = _layer(), _x(120, 6)
    want, _ = _want(lay, x)
    plant(monkeypatch)
    got = jax.jit(lambda x: lay(x[None])[0])(x)
    err = np.abs(np.asarray(got) - np.asarray(want)).max(-1)
    assert err[:63].max() < 2e-5 or plant is _no_forced_blocks
    assert err[64:].max() > 1e-2
