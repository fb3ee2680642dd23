"""A non-gated expert layer's two grouped products as one kernel (TPU
pallas): ``out[r] = relu(xs[r] @ w_up[g(r)])^2 @ w_down[g(r)]`` for rows
sorted by group, group ``g`` holding ``sizes[g]`` of them.

XLA's path is two ``jax.lax.ragged_dot`` calls with the hidden rows
written to HBM between them, and its grouped kernel gives a group a
256-row tile; a decode step has 4-5 rows a group and a prompt's chunk
some 44 (PERF.md, PR 41), so both are bound by the weights they stream.
This kernel streams a hit expert's two matrices once and does a few rows
of work on them:

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
  scalar-prefetch operands, so the block of ``w_up`` / ``w_down`` a step
  needs is known before it. Consecutive items of one group ask for the
  same block and no DMA is issued; the steps past the last item map to
  the last item's blocks (no DMA) and ``pl.when`` skips their body;
- the hidden rows ``[tile, block]`` live in VMEM: float32 out of the
  first product, ``relu^2`` in float32, ONE rounding to the operands'
  dtype for the second product, a float32 accumulator over the blocks
  of the hidden width, ONE rounding of the output.

**The name.** The ``pallas_call`` is named ``ragged-dot-none-relu2``,
and the name is part of a contract: the benchmark finds the grouped
products of the held experts by the instruction's name
(``benchmark/opcount/nemotron_h.py``: ``is_expert_kernel`` is
``name.startswith("ragged-dot-none")``, ``is_expert_op`` is
``"ragged-dot" in text``), and this kernel is those products, so it is
named to be read by the same readers (ROADMAP.md D8 asks a ``benchmark``
PR for readers that go by something better than a name).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._platform import on_tpu_platform

__all__ = ["grouped_relu2", "grouped_relu2_supported", "row_tile",
           "work_items"]

_LANES = 128
# most rows a tile: past it an item's hidden rows crowd the weights out
# of VMEM and nothing is gained (the weights' stream bounds the call)
_MAX_TILE = 128
# what the two double-buffered weight blocks may take of VMEM; one
# expert of the served configuration is 2 x 5.5 MB, twice buffered
_WEIGHT_VMEM = 48 << 20
_NAME = "ragged-dot-none-relu2"


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


def _hidden_block(width, hidden, dtype):
    """Columns of the hidden width a grid step takes: all of them where
    an expert's two matrices fit ``_WEIGHT_VMEM`` twice buffered, else
    the largest lane multiple that divides the hidden width and fits;
    ``None`` where none does."""
    per_column = 4 * int(width) * jnp.dtype(dtype).itemsize
    for parts in range(1, max(int(hidden) // _LANES, 1) + 1):
        if hidden % parts == 0 and (parts == 1
                                    or (hidden // parts) % _LANES == 0):
            if per_column * (hidden // parts) <= _WEIGHT_VMEM:
                return hidden // parts
    return None


def grouped_relu2_supported(xs_shape, up_shape, down_shape, dtype) -> bool:
    """Whether the kernel takes ``xs [R, w]``, ``w_up [n, w, f]`` and
    ``w_down [n, f, w]``, all of ``dtype``: widths that are whole lanes,
    whole sublane packs of rows, and a block of the hidden width that
    fits VMEM."""
    if str(dtype) not in ("bfloat16", "float32") or len(xs_shape) != 2 \
            or len(up_shape) != 3 or len(down_shape) != 3:
        return False
    rows, width = map(int, xs_shape)
    n, w, f = map(int, up_shape)
    return (tuple(map(int, down_shape)) == (n, f, w) and w == width
            and width % _LANES == 0 and f % _LANES == 0
            and rows % _sublanes(dtype) == 0
            and _hidden_block(width, f, dtype) is not None)


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
            up_ref, down_ref, o_ref, acc_ref, *, tile):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < total_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        hid = jnp.dot(x_ref[...], up_ref[...],
                      preferred_element_type=jnp.float32)
        hid = jnp.square(jnp.maximum(hid, 0.0)).astype(x_ref.dtype)
        acc_ref[...] += jnp.dot(hid, down_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            g = group_ref[i]
            row = tile_ref[i] * tile + jax.lax.broadcasted_iota(
                jnp.int32, (tile, 1), 0)
            mine = (row >= start_ref[g]) & (row < end_ref[g])
            o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                                   o_ref[...])


def grouped_relu2(xs, w_up, w_down, sizes, tile=None, hidden_block=None,
                  interpret=None):
    """``(out [R, w], tile_rows)``: ``out[r] = relu(xs[r] @ w_up[g])^2 @
    w_down[g]`` for the rows of groups ``0 .. n-1``, ``sizes[g]`` rows a
    group in order from row 0 (their sum may be less than ``R``: the rows
    past it are not written); ``tile_rows`` (int32 scalar) the rows the
    work items multiplied, ``total x tile``, of which the groups' own
    rows are ``sizes.sum()``. ``tile`` and ``hidden_block`` default to
    :func:`row_tile` and the largest block that fits; ``interpret``
    defaults to "not on a TPU"."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = xs.shape
    n, _, hidden = w_up.shape
    tile = row_tile(rows, n, xs.dtype) if tile is None else int(tile)
    fb = (_hidden_block(width, hidden, xs.dtype) if hidden_block is None
          else int(hidden_block))
    f_blocks = hidden // fb
    if interpret is None:
        interpret = not on_tpu_platform()
    group, tile_id, start, end, total = work_items(sizes, rows, tile)

    def rows_at(i, j, group, tile_id, start, end, total):
        return tile_id[i], 0

    def hidden_at(i, j, total):
        # a step past the last item stays on the block the last one left
        return jnp.where(i < total[0], j, f_blocks - 1)

    item = xs.dtype.itemsize
    vmem = (4 * width * fb * item            # the weights, twice buffered
            + 4 * tile * width * item        # rows in and out, twice
            + tile * width * 4               # the accumulator
            + 2 * tile * fb * (4 + item)     # the hidden rows
            + (4 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        name=_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(group.shape[0], f_blocks),
            in_specs=[
                pl.BlockSpec((tile, width), rows_at),
                pl.BlockSpec((None, width, fb),
                             lambda i, j, g, t, s, e, n:
                             (g[i], 0, hidden_at(i, j, n))),
                pl.BlockSpec((None, fb, width),
                             lambda i, j, g, t, s, e, n:
                             (g[i], hidden_at(i, j, n), 0)),
            ],
            out_specs=pl.BlockSpec((tile, width), rows_at),
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, width), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(group, tile_id, start, end, total.reshape(1), xs, w_up, w_down)
    return out, total * tile
