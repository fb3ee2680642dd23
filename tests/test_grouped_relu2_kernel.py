"""The non-gated experts' grouped kernel (ops/pallas/grouped_relu2.py) in
interpret mode against the path it replaces on a TPU, `RoutedExperts`'
two `jax.lax.ragged_dot` calls with `relu^2` between them, at small
widths: groups that are empty, of one row, of exactly a row tile, larger
than one, one group with every row, no group with any, fewer grouped
rows than rows; rows and weights that nothing may read poisoned; every
row tile and block of the hidden width; the count of multiplied rows
against a plain enumeration of the grid's work; and the layer's choice
between the two paths.

Tolerances: float32 differs in the order of the sums only (blocks of the
hidden width): 2e-5 on outputs of order 10. bfloat16: the kernel squares
the float32 product where XLA's path rounds it to bfloat16 first, and
both round the hidden rows and the output once: 2 ulp of an output of
order 8-16 (2^-3)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import RoutedExperts

gk = importlib.import_module("paddle_tpu.ops.pallas.grouped_relu2")

N, W, F, R, TILE = 6, 128, 256, 96, 16
CASES = {
    "empty, one row, a tile, over a tile": [0, 1, 16, 40, 0, 7],
    "tiles shared by neighbours": [3, 3, 3, 3, 3, 3],
    "one expert takes every row": [0, 0, 96, 0, 0, 0],
    "every row falls elsewhere": [0, 0, 0, 0, 0, 0],
    "fewer grouped rows than rows": [0, 0, 0, 0, 0, 50],
    "whole tiles": [16, 32, 0, 16, 16, 16],
}
TOL = {"float32": 2e-5, "bfloat16": 2 ** -3}


def _inputs(dtype, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.standard_normal((R, W)), dtype)
    up = jnp.asarray(0.1 * rng.standard_normal((N, W, F)), dtype)
    down = jnp.asarray(0.1 * rng.standard_normal((N, F, W)), dtype)
    return xs, up, down


def _xla(xs, up, down, sizes):
    """The layer's path off the chip, word for word."""
    hid = jnp.square(jax.nn.relu(jax.lax.ragged_dot(
        xs, up, sizes).astype(jnp.float32)))
    return jax.lax.ragged_dot(hid.astype(xs.dtype), down, sizes)


def _kernel(xs, up, down, sizes, **kw):
    return gk.grouped_relu2(xs, up, down, jnp.asarray(sizes, jnp.int32),
                            interpret=True, **kw)


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_two_ragged_dots(dtype, case):
    sizes = CASES[case]
    xs, up, down = _inputs(dtype)
    live = sum(sizes)
    got, _ = _kernel(xs, up, down, sizes, tile=TILE)
    want = _xla(xs, up, down, jnp.asarray(sizes, jnp.int32))
    assert got.shape == want.shape and got.dtype == want.dtype
    if live:
        assert np.abs(_f32(want)[:live]).max() > 4.0
        assert np.abs(_f32(got)[:live] - _f32(want)[:live]).max() \
            <= TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["empty, one row, a tile, over a tile",
                                  "fewer grouped rows than rows",
                                  "every row falls elsewhere"])
def test_rows_past_the_groups_and_unhit_experts_are_never_read(dtype, case):
    """The rows past the last group and both matrices of every expert
    that got no row hold NaN: the grouped rows come out finite and
    bit-equal to the clean operands' (a row of a shared tile is
    multiplied and not stored; an unhit expert's block is not computed
    on)."""
    sizes = np.asarray(CASES[case])
    xs, up, down = _inputs(dtype, seed=1)
    live = int(sizes.sum())
    unhit = jnp.asarray(sizes == 0)[:, None, None]
    assert live < R and bool(unhit.any())
    dirty = (jnp.where(jnp.arange(R)[:, None] >= live, jnp.nan, xs),
             jnp.where(unhit, jnp.nan, up), jnp.where(unhit, jnp.nan, down))
    got = _f32(_kernel(*dirty, sizes, tile=TILE)[0])[:live]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, _f32(_kernel(xs, up, down, sizes, tile=TILE)[0])[:live])


@pytest.mark.parametrize("tile,hidden_block", [
    (8, None), (16, 128), (32, None), (64, 128), (96, None), (None, None)])
def test_any_row_tile_and_hidden_block_give_the_same_result(tile,
                                                            hidden_block):
    sizes = CASES["empty, one row, a tile, over a tile"]
    xs, up, down = _inputs("float32", seed=2)
    got, _ = _kernel(xs, up, down, sizes, tile=tile,
                     hidden_block=hidden_block)
    want = _xla(xs, up, down, jnp.asarray(sizes, jnp.int32))
    live = sum(sizes)
    assert np.abs(_f32(got)[:live] - _f32(want)[:live]).max() \
        <= TOL["float32"]


@pytest.mark.parametrize("tile", [8, 16, 32, 64])
@pytest.mark.parametrize("case", list(CASES))
def test_tile_rows_is_the_grids_own_count(case, tile):
    """The work items, enumerated plainly (a group takes every row tile
    one of its rows lies in, groups in order, tiles in order), are the
    first `total` entries of `work_items`; the entries past them repeat
    the last, so that a step past the end asks for no new block; and
    `tile_rows` is their number times the tile."""
    sizes = CASES[case]
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    tiles = -(-R // tile)
    items = [(g, t) for g, (s, n) in enumerate(zip(start, sizes))
             for t in range(tiles) if n and s < (t + 1) * tile
             and s + n > t * tile]
    group, tile_id, first, end, total = map(
        np.asarray, gk.work_items(jnp.asarray(sizes, jnp.int32), R, tile))
    assert int(total) == len(items) <= len(group) == tiles + N - 1
    assert list(zip(group[:len(items)], tile_id[:len(items)])) == items
    last = items[-1] if items else (N - 1, 0)
    assert set(zip(group[len(items):], tile_id[len(items):])) <= {last}
    np.testing.assert_array_equal(first, start)
    np.testing.assert_array_equal(end, np.cumsum(sizes))
    xs, up, down = _inputs("float32")
    assert int(_kernel(xs, up, down, sizes, tile=tile)[1]) \
        == len(items) * tile >= sum(sizes)


def test_the_row_tile_follows_the_pairs_a_held_expert():
    """From what a call sees: rows over groups, rounded up to the dtype's
    sublane packing, 128 at most. The served cell's decode step (64
    slots x 22 pairs over 128 held experts) takes 16, its 1,024-token
    chunk 128."""
    assert gk.row_tile(1408, 128, jnp.bfloat16) == 16
    assert gk.row_tile(22528, 128, jnp.bfloat16) == 128
    assert gk.row_tile(1408, 128, jnp.float32) == 16
    assert gk.row_tile(64, 128, jnp.float32) == 8
    assert gk.row_tile(12, 8, jnp.bfloat16) == 16
    assert gk.grouped_relu2_supported((1408, 1024), (128, 1024, 2688),
                                      (128, 2688, 1024), "bfloat16")
    for xs, up, down, dtype in (
            ((1408, 1024), (128, 1024, 2688), (128, 2688, 1024), "float16"),
            ((1400, 1024), (128, 1024, 2688), (128, 2688, 1024), "bfloat16"),
            ((1408, 1000), (128, 1000, 2688), (128, 2688, 1000), "bfloat16"),
            ((1408, 1024), (128, 1024, 2688), (128, 2688, 512), "bfloat16"),
            ((1408, 1 << 17), (2, 1 << 17, 256), (2, 256, 1 << 17),
             "bfloat16")):
        assert not gk.grouped_relu2_supported(xs, up, down, dtype)


def _layer(activation, seed=3):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    return RoutedExperts(32, 128, 16, 6, held=(4, 8), shared_width=40,
                         score="sigmoid", selection_bias=True,
                         activation=activation, latent_size=128,
                         initializer_range=0.2)


@pytest.mark.parametrize("activation,gate,taken", [
    ("relu2", True, True), ("relu2", False, False), ("swiglu", True, False)])
def test_the_layer_takes_the_kernel_where_it_may(activation, gate, taken,
                                                 monkeypatch):
    """Experts without a gate, where a Mosaic call may be emitted (the
    gate is opened here; off the TPU the kernel then runs interpreted):
    the kernel, the same result as the `ragged_dot` path to the order of
    the sums, and the count of its multiplied rows beside the loads; a
    closed gate or gated experts: `ragged_dot`, and no count."""
    m = _layer(activation)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.float32)
    want = np.asarray(m(x))
    assert m.last_tile_rows is None
    monkeypatch.setattr(moe, "can_emit_mosaic", lambda: gate)
    got = np.asarray(m(x))
    assert (m.last_tile_rows is not None) == taken
    if not taken:
        np.testing.assert_array_equal(got, want)
        return
    np.testing.assert_allclose(got, want, atol=2e-5)
    pairs = int(m.last_load.sum())
    assert pairs <= int(m.last_tile_rows) and int(m.last_tile_rows) % 8 == 0
    # a long sequence in chunks: the chunks' counts add up
    whole = int(m.last_tile_rows)
    np.testing.assert_allclose(np.asarray(m.in_chunks(x, chunk=8)), want,
                               atol=2e-5)
    assert int(m.last_load.sum()) == pairs
    assert int(m.last_tile_rows) >= whole
