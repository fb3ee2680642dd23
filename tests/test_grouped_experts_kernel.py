"""The routed experts' grouped kernel (ops/pallas/grouped_experts.py) in
interpret mode against the path it replaces on a TPU, `RoutedExperts`'
`jax.lax.ragged_dot` calls - three with float32 `silu(g) * u` between
them for gated experts, two with `relu^2` for the others - at small
widths: groups that are empty, of one row, of exactly a row tile, larger
than one, one group with every row, no group with any, fewer grouped
rows than rows; rows and weights that nothing may read poisoned; every
row tile and block of the hidden width; the count of multiplied rows
against a plain enumeration of the grid's work; and the layer's choice
between the two paths.

Tolerances, as shares of the largest output (4-25 here): float32 differs
in the order of the sums only (blocks of the hidden width): 3e-6.
bfloat16: the kernel takes the activation of the float32 products where
XLA's path rounds them to bfloat16 first, and both round the hidden rows
and the output once: 2^-6, two units in the last place of the largest."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import RoutedExperts

gk = importlib.import_module("paddle_tpu.ops.pallas.grouped_experts")

N, W, F, R, TILE = 6, 128, 256, 96, 16
CASES = {
    "empty, one row, a tile, over a tile": [0, 1, 16, 40, 0, 7],
    "tiles shared by neighbours": [3, 3, 3, 3, 3, 3],
    "one expert takes every row": [0, 0, 96, 0, 0, 0],
    "every row falls elsewhere": [0, 0, 0, 0, 0, 0],
    "fewer grouped rows than rows": [0, 0, 0, 0, 0, 50],
    "whole tiles": [16, 32, 0, 16, 16, 16],
}
TOL = {"float32": 3e-6, "bfloat16": 2 ** -6}
ACTIVATIONS = ["relu2", "swiglu"]


def _inputs(dtype, activation, seed=0, hidden=F):
    """`(xs, up, down, gate)`, the gate `None` for `relu2`; the first
    three are the same draws for either activation."""
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.standard_normal((R, W)), dtype)
    up = jnp.asarray(0.1 * rng.standard_normal((N, W, hidden)), dtype)
    down = jnp.asarray(0.1 * rng.standard_normal((N, hidden, W)), dtype)
    gate = None if activation == "relu2" else jnp.asarray(
        0.2 * rng.standard_normal((N, W, hidden)), dtype)
    return xs, up, down, gate


def _xla(xs, up, down, gate, sizes):
    """The layer's path off the chip, word for word."""
    if gate is None:
        hid = jnp.square(jax.nn.relu(jax.lax.ragged_dot(
            xs, up, sizes).astype(jnp.float32)))
    else:
        hid = jax.nn.silu(jax.lax.ragged_dot(xs, gate, sizes).astype(
            jnp.float32)) * jax.lax.ragged_dot(xs, up, sizes).astype(
            jnp.float32)
    return jax.lax.ragged_dot(hid.astype(xs.dtype), down, sizes)


def _kernel(xs, up, down, gate, sizes, **kw):
    return gk.grouped_experts(xs, up, down, jnp.asarray(sizes, jnp.int32),
                              gate, interpret=True, **kw)


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_ragged_dots(dtype, case, activation):
    sizes = CASES[case]
    ops = _inputs(dtype, activation)
    live = sum(sizes)
    got, _ = _kernel(*ops, sizes, tile=TILE)
    want = _xla(*ops, jnp.asarray(sizes, jnp.int32))
    assert got.shape == want.shape and got.dtype == want.dtype
    if live:
        scale = np.abs(_f32(want)[:live]).max()
        assert scale > 4.0
        assert np.abs(_f32(got)[:live] - _f32(want)[:live]).max() \
            <= TOL[dtype] * scale


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["empty, one row, a tile, over a tile",
                                  "fewer grouped rows than rows",
                                  "every row falls elsewhere"])
def test_rows_past_the_groups_and_unhit_experts_are_never_read(
        dtype, case, activation):
    """The rows past the last group and every matrix of every expert
    that got no row hold NaN: the grouped rows come out finite and
    bit-equal to the clean operands' (a row of a shared tile is
    multiplied and not stored; an unhit expert's block is not computed
    on)."""
    sizes = np.asarray(CASES[case])
    xs, *weights = _inputs(dtype, activation, seed=1)
    live = int(sizes.sum())
    unhit = jnp.asarray(sizes == 0)[:, None, None]
    assert live < R and bool(unhit.any())
    dirty = [jnp.where(jnp.arange(R)[:, None] >= live, jnp.nan, xs)] + [
        None if w is None else jnp.where(unhit, jnp.nan, w) for w in weights]
    got = _f32(_kernel(*dirty, sizes, tile=TILE)[0])[:live]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, _f32(_kernel(xs, *weights, sizes, tile=TILE)[0])[:live])


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("tile,hidden_block", [
    (8, None), (16, 128), (32, None), (64, 128), (96, None), (None, None),
    (8, 256), (16, 384)])
def test_any_row_tile_and_hidden_block_give_the_same_result(
        tile, hidden_block, activation):
    """The group of 40 rows spans two to six tiles, and with a block
    under the hidden width (768 here: two to six blocks) its later
    tiles walk the blocks in the other direction."""
    sizes = CASES["empty, one row, a tile, over a tile"]
    ops = _inputs("float32", activation, seed=2, hidden=768)
    got, _ = _kernel(*ops, sizes, tile=tile, hidden_block=hidden_block)
    want = _xla(*ops, jnp.asarray(sizes, jnp.int32))
    live = sum(sizes)
    assert np.abs(_f32(got)[:live] - _f32(want)[:live]).max() \
        <= TOL["float32"] * np.abs(_f32(want)[:live]).max()


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hidden,cols", [(512, 128), (768, 256), (1024, 256)])
def test_a_sliced_body_equals_blocks_of_the_slice(hidden, cols, dtype,
                                                  activation):
    """The body walks its block of the hidden width ``cols`` columns at
    a time (the code Mosaic compiles is a slice's, not a block's:
    PERF.md, PR 47): a whole-expert block walked in slices sums the same
    products in the same order as blocks of one slice each walked
    forwards, which is what even work items do: their rows are equal to
    the last bit. Odd items walk their blocks backwards: the same
    products summed in the other order."""
    sizes = CASES["empty, one row, a tile, over a tile"]
    ops = _inputs(dtype, activation, seed=4, hidden=hidden)
    whole, rows_w = _kernel(*ops, sizes, hidden_block=hidden,
                            hidden_slice=cols)
    blocks, rows_b = _kernel(*ops, sizes, hidden_block=cols,
                             hidden_slice=cols)
    assert int(rows_w) == int(rows_b)
    group, tile_id, start, end, total = map(np.asarray, gk.work_items(
        jnp.asarray(sizes, jnp.int32), R, TILE))
    even = np.zeros(R, bool)
    for i in range(0, int(total), 2):
        lo = max(int(start[group[i]]), int(tile_id[i]) * TILE)
        hi = min(int(end[group[i]]), (int(tile_id[i]) + 1) * TILE)
        even[lo:hi] = True
    live = sum(sizes)
    assert even[:live].sum() >= 16 and (~even[:live]).sum() >= 16
    whole, blocks = _f32(whole)[:live], _f32(blocks)[:live]
    np.testing.assert_array_equal(whole[even[:live]], blocks[even[:live]])
    assert np.abs(whole - blocks).max() \
        <= (1e-6 if dtype == "float32" else 2 ** -7) * np.abs(whole).max()


@pytest.mark.parametrize("block,cols", [
    (1280, 256), (1024, 512), (2688, 384), (640, 128), (256, 256), (96, 96)])
def test_the_slice_follows_the_block(block, cols):
    """The most whole lanes, at most 512, that divide the block
    (`solar-open2-250b`'s whole expert, the halves of `k-exaone-236b`
    and `longcat-flash-omni`, `nemotron-3-super-120b`'s whole expert); a
    block that is no whole lanes (a toy, interpreted) is one slice."""
    assert gk._slice(block) == cols


@pytest.mark.parametrize("name,tokens", [
    ("nemotron-3-super-120b", 64), ("solar-open2-250b", 32),
    ("k-exaone-236b", 2048), ("longcat-flash-omni", 1024)])
def test_the_body_is_a_slices_code(name, tokens):
    """At the served widths the kernel's body holds each product ONCE
    and one loop of block / slice trips, whatever the block: the loop is
    what keeps Mosaic's compile at 0.4-1.4 s where the unrolled block
    took 2.1-4.3 s (PERF.md, PR 47: my AOT compiles)."""
    n, w, f, gated = SERVED[name]
    bf16 = jnp.bfloat16
    rows = tokens * 8

    def layer(xs, gate, up, down, sizes):
        return gk.grouped_experts(xs, up, down, sizes,
                                  gate if gated else None, interpret=False)

    sds = jax.ShapeDtypeStruct
    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(layer)(
            sds((rows, w), bf16), sds((n, w, f), bf16), sds((n, w, f), bf16),
            sds((n, f, w), bf16), sds((n,), jnp.int32)))
    block = gk._hidden_block(w, f, bf16, 2 + gated)
    assert text.count("dot_general") == 2 + gated
    assert re.findall(r"scan\[.*?length=(\d+)", text, re.S) \
        == [str(block // gk._slice(block))]


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("tile", [8, 16, 32, 64])
@pytest.mark.parametrize("case", list(CASES))
def test_tile_rows_is_the_grids_own_count(case, tile, activation):
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
    assert int(_kernel(*_inputs("float32", activation), sizes,
                       tile=tile)[1]) == len(items) * tile >= sum(sizes)


def test_the_row_tile_follows_the_pairs_a_held_expert():
    """From what a call sees: rows over groups, rounded up to the dtype's
    sublane packing, 128 at most. `nemotron-3-super-120b`'s decode step
    (64 slots x 22 pairs over 128 held experts) takes 16, its 1,024-token
    chunk 128; the gated cells' decode steps 16 or 32 and their prompts
    128."""
    assert gk.row_tile(1408, 128, jnp.bfloat16) == 16
    assert gk.row_tile(22528, 128, jnp.bfloat16) == 128
    assert gk.row_tile(1408, 128, jnp.float32) == 16
    assert gk.row_tile(64, 128, jnp.float32) == 8
    assert gk.row_tile(12, 8, jnp.bfloat16) == 16
    assert gk.row_tile(32 * 8, 40, jnp.bfloat16) == 16
    assert gk.row_tile(32 * 12, 16, jnp.bfloat16) == 32
    assert gk.row_tile(2048 * 8, 16, jnp.bfloat16) == 128


SERVED = {  # held experts, width, hidden width, a gate
    "nemotron-3-super-120b": (128, 1024, 2688, False),
    "solar-open2-250b": (40, 4096, 1280, True),
    "k-exaone-236b": (16, 6144, 2048, True),
    "longcat-flash-omni": (16, 6144, 2048, True),
}


@pytest.mark.parametrize("name,block", [
    ("nemotron-3-super-120b", 2688), ("solar-open2-250b", 1280),
    ("k-exaone-236b", 1024), ("longcat-flash-omni", 1024)])
def test_the_hidden_block_follows_an_experts_bytes(name, block):
    """An expert that fits VMEM twice is one block (`nemotron-3-super-
    120b`'s two matrices 11 MB, `solar-open2-250b`'s three 31.5 MB);
    the 75.5 MB experts of the other two go in halves."""
    n, w, f, gated = SERVED[name]
    shapes = [(1408, w), (n, w, f), (n, f, w), "bfloat16"] \
        + [(n, w, f)] * gated
    assert gk.grouped_experts_supported(*shapes)
    assert gk._hidden_block(w, f, jnp.bfloat16, 2 + gated) == block


@pytest.mark.parametrize("xs,up,down,dtype,gate", [
    ((1408, 1024), (128, 1024, 2688), (128, 2688, 1024), "float16", None),
    ((1400, 1024), (128, 1024, 2688), (128, 2688, 1024), "bfloat16", None),
    ((1408, 1000), (128, 1000, 2688), (128, 2688, 1000), "bfloat16", None),
    ((1408, 1024), (128, 1024, 2688), (128, 2688, 512), "bfloat16", None),
    ((1408, 1 << 17), (2, 1 << 17, 256), (2, 256, 1 << 17), "bfloat16",
     None),
    ((256, 4096), (40, 4096, 1280), (40, 1280, 4096), "bfloat16",
     (40, 4096, 640)),
    ((256, 1 << 16), (2, 1 << 16, 256), (2, 256, 1 << 16), "bfloat16",
     (2, 1 << 16, 256))])
def test_what_the_kernel_declines(xs, up, down, dtype, gate):
    """Another dtype, rows that are no whole sublane pack, widths that
    are no whole lanes, matrices that do not fit each other (a gate
    shaped unlike `w_up` among them), and an expert of which not even a
    128-column block fits VMEM twice - the last one fits without a gate
    and not with one."""
    assert not gk.grouped_experts_supported(xs, up, down, dtype, gate)
    if gate == up:
        assert gk.grouped_experts_supported(xs, up, down, dtype)


def _layer(activation, seed=3):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    return RoutedExperts(32, 128, 16, 6, held=(4, 8), shared_width=40,
                         score="sigmoid", selection_bias=True,
                         activation=activation, latent_size=128,
                         initializer_range=0.2)


@pytest.mark.parametrize("activation,gate,taken", [
    ("relu2", True, True), ("relu2", False, False), ("swiglu", True, True),
    ("swiglu", False, False)])
def test_the_layer_takes_the_kernel_where_it_may(activation, gate, taken,
                                                 monkeypatch):
    """Where a Mosaic call may be emitted (the gate is opened here; off
    the TPU the kernel then runs interpreted), experts of either
    activation: the kernel, the same result as the `ragged_dot` path to
    the order of the sums, and the count of its multiplied rows beside
    the loads; a closed gate: `ragged_dot`, and no count."""
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
