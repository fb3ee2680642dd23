"""A routed expert layer's grouped products as one kernel (TPU pallas),
for rows sorted by group, group ``g`` holding ``sizes[g]`` of them::

    gated:      out[r] = (silu(xs[r] @ w_gate[g]) * (xs[r] @ w_up[g])) @ w_down[g]
    non-gated:  out[r] = relu(xs[r] @ w_up[g])^2 @ w_down[g]

XLA's path is three (two) ``jax.lax.ragged_dot`` calls with the gate, up
and float32 hidden rows written to HBM between them, over ALL the sorted
rows though an eighth or a 32nd land on this chip's experts, and its
grouped kernel gives a group a 256-row tile; a decode step has 1-5 rows
a group and a prompt's chunk 24-128 (PERF.md, PRs 41 and 46), so the
calls are bound by the weights they stream and read 36-75 % of that
roofline. This kernel streams a hit expert's matrices once and does a
few rows of work on them:

- the rows stay where the caller's sort put them. A **work item** is one
  row tile of one group: a group takes every tile its rows touch, so a
  tile that holds the end of one group and the start of the next is
  visited by both, consecutively, and each writes only its own rows
  (:func:`work_items`; the layout of
  ``jax.experimental.pallas.ops.tpu.megablox``). A group with no rows
  has no item, and the rows past the last group belong to none: they
  are never written, and hold whatever the buffer held;
- grid = (work item, block of the hidden width); the items' groups and
  tiles, the groups' first and last rows and the number of items are
  scalar-prefetch operands, so the block of ``w_gate`` / ``w_up`` /
  ``w_down`` a step needs is known before it. Consecutive items of one
  group ask for the same block and no DMA is issued; the steps past the
  last item map to the last item's blocks (no DMA) and ``pl.when`` skips
  their body. Where an expert does not fit VMEM whole (:func:`_hidden_block`)
  its blocks are walked forwards by even items and backwards by odd
  ones, so that the second tile of a group finds the block the first
  one ended on (a group of two tiles streams three halves of its expert
  and not four);
- the block a step holds is what the DMA streams; the body walks it
  :func:`_slice` columns at a time in a loop the compiler keeps as a
  loop, so the code Mosaic compiles is a slice's and not a block's (a
  31.5 MB expert unrolled whole took it 2-6 s a program, a slice's 0.4-1.4:
  PERF.md, PR 47). The hidden rows ``[tile, slice]`` live in VMEM:
  float32 out of the first products, ``silu(g) * u`` or ``relu^2`` in
  float32, ONE rounding to the operands' dtype for the last product, a
  float32 accumulator over the slices and blocks of the hidden width,
  ONE rounding of the output.

**The names.** The ``pallas_call`` is named ``ragged-dot-none-swiglu``
with a gate and ``ragged-dot-none-relu2`` without, and the prefix is
part of a contract: the benchmark finds the grouped products of the held
experts by the instruction's name (``benchmark/opcount/solar_open2.py``,
``k_exaone.py``, ``longcat_flash.py`` and ``nemotron_h.py``:
``is_expert_kernel`` is ``name.startswith("ragged-dot-none")``,
``is_expert_op`` is ``"ragged-dot" in text``), and this kernel is those
products, so it is named to be read by the same readers (ROADMAP.md D8
asks a ``benchmark`` PR for readers that go by something better than a
name).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._platform import on_tpu_platform

__all__ = ["grouped_experts", "grouped_experts_supported", "row_tile",
           "work_items"]

_LANES = 128
# most rows a tile: past it an item's hidden rows crowd the weights out
# of VMEM and nothing is gained (the weights' stream bounds the call)
_MAX_TILE = 128
# what the double-buffered weight blocks may take of a v5e's 128 MiB of
# VMEM: a whole gated expert of solar-open2-250b (3 x 10.5 MB) twice, a
# half of k-exaone-236b's or longcat-flash-omni's (3 x 12.6 MB) twice
_WEIGHT_VMEM = 80 << 20
# most columns of a block the kernel's body takes at a time
_MAX_SLICE = 512
_NAMES = {False: "ragged-dot-none-relu2", True: "ragged-dot-none-swiglu"}


def _sublanes(dtype):
    """Rows of ``dtype`` a vector register's sublanes pack."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def row_tile(rows, groups, dtype):
    """Rows a work item takes, from what the call can see: the pairs a
    group would get were they spread evenly (``rows / groups``, static),
    rounded up to the dtype's sublane packing, at most ``_MAX_TILE``."""
    sub = _sublanes(dtype)
    even = -(-int(rows) // int(groups))
    def up(v):
        return -(-v // sub) * sub

    return int(min(up(even), _MAX_TILE, up(int(rows))))


def _hidden_block(width, hidden, dtype, matrices=2):
    """Columns of the hidden width a grid step takes: all of them where
    an expert's ``matrices`` (2, with a gate 3) fit ``_WEIGHT_VMEM``
    twice buffered, else the largest lane multiple that divides the
    hidden width and fits; ``None`` where none does."""
    per_column = 2 * matrices * int(width) * jnp.dtype(dtype).itemsize
    for parts in range(1, max(int(hidden) // _LANES, 1) + 1):
        if hidden % parts == 0 and (parts == 1
                                    or (hidden // parts) % _LANES == 0):
            if per_column * (hidden // parts) <= _WEIGHT_VMEM:
                return hidden // parts
    return None


def _slice(block):
    """Columns of a ``block`` of the hidden width the body takes at a
    time: the most whole lanes, at most ``_MAX_SLICE``, that divide it
    (the block itself where it is no whole lanes: interpreted toys)."""
    for cols in range(min(_MAX_SLICE, block) // _LANES * _LANES, 0, -_LANES):
        if block % cols == 0:
            return cols
    return block


def grouped_experts_supported(xs_shape, up_shape, down_shape, dtype,
                              gate_shape=None) -> bool:
    """Whether the kernel takes ``xs [R, w]``, ``w_up [n, w, f]`` and
    ``w_down [n, f, w]`` (and ``w_gate``, shaped as ``w_up``, for gated
    experts), all of ``dtype``: widths that are whole lanes, whole
    sublane packs of rows, and a block of the hidden width that fits
    VMEM."""
    if str(dtype) not in ("bfloat16", "float32") or len(xs_shape) != 2 \
            or len(up_shape) != 3 or len(down_shape) != 3:
        return False
    rows, width = map(int, xs_shape)
    n, w, f = map(int, up_shape)
    gated = gate_shape is not None
    return (tuple(map(int, down_shape)) == (n, f, w) and w == width
            and (not gated or tuple(map(int, gate_shape)) == (n, w, f))
            and width % _LANES == 0 and f % _LANES == 0
            and rows % _sublanes(dtype) == 0
            and _hidden_block(width, f, dtype, 2 + gated) is not None)


def work_items(sizes, rows, tile):
    """The grid's work for groups of ``sizes [n]`` sorted rows among
    ``rows``, ``tile`` rows an item: ``(group [items], tile_id [items],
    start [n], end [n], total)``, all int32. Item ``i`` is tile
    ``tile_id[i]`` of group ``group[i]``, whose rows are ``start[g] ..
    end[g] - 1``; the items go by group and, inside a group, by tile, so
    a tile two groups share is visited twice in a row. ``total`` items
    are real; ``items`` is the static bound (every non-empty group an
    item, and one more for every tile border inside a group), and the
    entries past ``total`` repeat the last real item's."""
    n = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes, dtype=jnp.int32)
    start = end - sizes
    first = start // tile
    visits = jnp.where(sizes > 0, (end - 1) // tile - first + 1, 0)
    done = jnp.cumsum(visits, dtype=jnp.int32)
    total = done[-1]
    items = -(-int(rows) // tile) + n - 1
    i = jnp.minimum(jnp.arange(items, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    group = jnp.minimum(
        (done[None, :] <= i[:, None]).sum(1, dtype=jnp.int32), n - 1)
    tile_id = first[group] + i - (done[group] - visits[group])
    return group, tile_id, start, end, total


def _kernel(group_ref, tile_ref, start_ref, end_ref, total_ref, x_ref,
            *refs, tile, cols):
    from jax.experimental import pallas as pl

    *gate_ref, up_ref, down_ref, o_ref, acc_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < total_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        x = x_ref[...]

        # a loop the compiler keeps: the code is a slice's, not a block's
        def one_slice(s, carry):
            at = pl.ds(pl.multiple_of(s * cols, cols), cols)
            hid = jnp.dot(x, up_ref[:, at],
                          preferred_element_type=jnp.float32)
            if gate_ref:
                hid = jax.nn.silu(jnp.dot(
                    x, gate_ref[0][:, at],
                    preferred_element_type=jnp.float32)) * hid
            else:
                hid = jnp.square(jnp.maximum(hid, 0.0))
            acc_ref[...] += jnp.dot(hid.astype(x.dtype), down_ref[at, :],
                                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, up_ref.shape[1] // cols, one_slice, 0)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            g = group_ref[i]
            row = tile_ref[i] * tile + jax.lax.broadcasted_iota(
                jnp.int32, (tile, 1), 0)
            mine = (row >= start_ref[g]) & (row < end_ref[g])
            o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                                   o_ref[...])


def grouped_experts(xs, w_up, w_down, sizes, w_gate=None, tile=None,
                    hidden_block=None, hidden_slice=None, interpret=None):
    """``(out [R, w], tile_rows)``: ``out[r]`` as the module's head has
    it (gated where ``w_gate`` is given) for the rows of groups ``0 ..
    n-1``, ``sizes[g]`` rows a group in order from row 0 (their sum may
    be less than ``R``: the rows past it are not written); ``tile_rows``
    (int32 scalar) the rows the work items multiplied, ``total x tile``,
    of which the groups' own rows are ``sizes.sum()``. ``tile``,
    ``hidden_block`` and ``hidden_slice`` default to :func:`row_tile`,
    the largest block that fits and :func:`_slice` of it; ``interpret``
    defaults to "not on a TPU". The call is
    a ``jax.jit`` of its own: the expert layers of one program call it
    with the same shapes, and are traced and lowered once for all of
    them (a lowering is 0.2-0.3 s of a serving cell's set-up on the
    chip's host: PERF.md, PR 47)."""
    rows, width = xs.shape
    n, _, hidden = w_up.shape
    weights = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    tile = row_tile(rows, n, xs.dtype) if tile is None else int(tile)
    fb = (_hidden_block(width, hidden, xs.dtype, len(weights))
          if hidden_block is None else int(hidden_block))
    if interpret is None:
        interpret = not on_tpu_platform()
    cols = _slice(fb) if hidden_slice is None else int(hidden_slice)
    return _call(xs, weights, sizes, tile, fb, cols, bool(interpret))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _call(xs, weights, sizes, tile, fb, cols, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = xs.shape
    f_blocks = weights[0].shape[2] // fb
    group, tile_id, start, end, total = work_items(sizes, rows, tile)

    def rows_at(i, j, group, tile_id, start, end, total):
        return tile_id[i], 0

    def hidden_at(i, j, total):
        if f_blocks == 1:
            return 0
        # odd items walk the blocks backwards; a step past the last item
        # stays on the block the last one left
        i, j = jnp.minimum(i, total[0] - 1), jnp.where(
            i < total[0], j, f_blocks - 1)
        return jnp.where(i % 2 == 1, f_blocks - 1 - j, j)

    def up_at(i, j, g, t, s, e, n):
        return g[i], 0, hidden_at(i, j, n)

    def down_at(i, j, g, t, s, e, n):
        return g[i], hidden_at(i, j, n), 0

    item = xs.dtype.itemsize
    vmem = (2 * len(weights) * width * fb * item  # the weights, twice
            + 4 * tile * width * item        # rows in and out, twice
            + tile * width * 4               # the accumulator
            # a slice's hidden rows: each first product, the rounded rows
            + (len(weights) - 1) * 2 * tile * cols * (4 + item)
            + (4 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, cols=cols),
        name=_NAMES[len(weights) == 3],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(group.shape[0], f_blocks),
            in_specs=[pl.BlockSpec((tile, width), rows_at)]
            + [pl.BlockSpec((None, width, fb), up_at)] * (len(weights) - 1)
            + [pl.BlockSpec((None, fb, width), down_at)],
            out_specs=pl.BlockSpec((tile, width), rows_at),
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, width), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(group, tile_id, start, end, total.reshape(1), xs, *weights)
    return out, total * tile
