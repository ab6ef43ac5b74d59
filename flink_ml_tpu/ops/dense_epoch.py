"""One epoch's dense loss from ONE read of its batch: a Pallas TPU kernel.

`losses._dense(pointwise)` is two reductions over the batch, the row-dot
and the gradient, and on the chip each streams the batch from HBM (two
`multiply_reduce_fusion`s, 93% of a dense fit's device time). Here a tile of
rows is brought into fast memory once; from it come the row-dots against the
coefficient, the pointwise loss and multiplier (`LossFunc.pointwise`, so one
kernel serves every dense loss), and the tile's part of the gradient and of
the loss and weight sums, while the next tile's read runs.

Written for a table the TPU keeps rows-minor (`mesh_lib.rows_minor`: a table
narrower than a tile's 128 lanes lies [column][row] in memory, rows on the
lanes). The kernel is handed that table the other way round, `X.T`, which
under that layout is the same bytes, and takes blocks of (width, tile): the
row-dot is a sum over sublanes, the gradient a sum over lanes that is kept
as (width, 128) partial sums and finished once an epoch. It is handed the
WHOLE table and the batch's first row, not a sliced batch (a custom call on
a slice makes XLA write the slice out first, a third pass): the blocks are
the table's own aligned tiles that cover [start, start + batch), and rows
outside it, rows >= n and whatever lies past the table's end in its last
tile weigh 0 and are read as 0.

Float32 products and sums throughout; only the order of summation differs
from the reduce form (rows are summed by lane, then the lanes). Which fit
takes it is `optimizer._can_one_pass`'s to say, and nothing else's.
"""

from __future__ import annotations

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.mesh import LANES, SUBLANES

GROUP = SUBLANES * LANES  # rows a 1-D column's tile holds: 1024
# a block of the table in fast memory, bytes at most: two are held (the
# pipeline's double buffer)
_BLOCK_BYTES = 4 << 20
# rows a tile x sqrt(padded width / batch), as the v5e had it best (PERF.md,
# PR 30: tiles of 2048 rows for batches of 100,000 rows 100 wide)
_TILE_SCALE = 64


@functools.cache
def _kernel_language():
    """`jax.experimental.pallas` and its TPU half, imported by the first fit
    that takes the kernel and by no other (a process that never does pays
    nothing). Where the installation writes no bytecode
    (PYTHONDONTWRITEBYTECODE, as on the benchmark's machines), every process
    compiles the language's modules from source, 1.3–1.5 s of a first fit:
    there their bytecode is kept beside jax's persistent compile cache, if
    one is configured, as that cache keeps the executables (0.25 s once it
    is there). Python's own checks of a cached file against its source
    apply; nothing is written where no compile cache is."""
    cache = jax.config.jax_compilation_cache_dir
    redirect = bool(sys.dont_write_bytecode and cache)
    saved = sys.dont_write_bytecode, sys.pycache_prefix
    if redirect:
        sys.dont_write_bytecode, sys.pycache_prefix = False, os.path.join(cache, "pyc")
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    finally:
        if redirect:
            sys.dont_write_bytecode, sys.pycache_prefix = saved
    return pl, pltpu


def tile_rows(width: int, batch: int, rows: int) -> int:
    """Rows a block holds, in whole groups of 1024 (a 1-D column is read by
    whole tiles of its own). The blocks are the table's own aligned tiles,
    so an epoch reads a tile more than its batch on average, which costs as
    tile x width, and every block is a step of the pipeline, which costs as
    batch / tile: the two are equal at a tile that goes as sqrt(batch /
    width). No more than the table has or fast memory holds twice."""
    width_pad = -(-width // SUBLANES) * SUBLANES
    balanced = round(_TILE_SCALE * math.sqrt(batch / width_pad) / GROUP) * GROUP
    by_bytes = _BLOCK_BYTES // (4 * width_pad)
    return max(GROUP, min(balanced, by_bytes // GROUP * GROUP, rows // GROUP * GROUP))


def _grid_steps(batch: int, tile: int) -> int:
    """The most tiles a batch can touch: it starts at a multiple of itself,
    so on a tile's first row whenever whole tiles make it up."""
    if batch % tile == 0:
        return batch // tile
    return (batch + tile - 2) // tile + 1


def _kernel(pl, pointwise, batch, tile, weighted, scalars, *refs):
    if weighted:
        coeff_ref, x_ref, y_ref, w_ref, grad_ref, sums_ref = refs
    else:
        coeff_ref, x_ref, y_ref, grad_ref, sums_ref = refs
    step = pl.program_id(0)
    start, n = scalars[0], scalars[1]
    stop = jnp.minimum(start + batch, n)
    first, last = start // tile, (start + batch - 1) // tile
    row0 = (first + step) * tile  # of this tile's first lane in the table

    @pl.when(step == 0)
    def _():
        grad_ref[...] = jnp.zeros_like(grad_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    # the coefficient down every lane of a group: (width, 1024)
    coeff = jnp.concatenate([coeff_ref[...]] * SUBLANES, axis=1)
    sublane = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def runs(wide):
        """(..., 1024) as its eight runs of 128 lanes."""
        return [wide[:, s * LANES:(s + 1) * LANES] for s in range(SUBLANES)]

    def inside(base, shape, row_of):
        """Which rows of a group are the batch's, in the table's form (row r
        on lane r) or a column's (on sublane r // 128, lane r % 128)."""
        row = row0 + base + row_of(*(lax.broadcasted_iota(jnp.int32, shape, d) for d in (0, 1)))
        return (row >= start) & (row < stop)

    def one_group(g, acc):
        """1024 rows: eight runs of 128 lanes of the table, and one tile of
        each column. What lies outside the batch is read as 0, not multiplied
        by 0: past the table's end a tile holds whatever was there. The
        row-dots are folded onto the columns' form, so that the pointwise
        part works on whole registers."""
        grad, lsum, wsum = acc
        base = pl.multiple_of(g * GROUP, GROUP)
        x = jnp.where(inside(base, (1, GROUP), lambda _, lane: lane), x_ref[:, pl.ds(base, GROUP)], 0.0)
        in_column = inside(base, (SUBLANES, LANES), lambda sublane, lane: sublane * LANES + lane)
        y = jnp.where(in_column, y_ref[pl.ds(base, GROUP)].reshape(SUBLANES, LANES), 0.0)
        w = jnp.where(in_column, w_ref[pl.ds(base, GROUP)].reshape(SUBLANES, LANES) if weighted else 1.0, 0.0)
        dot = jnp.zeros_like(y)
        for s, of_run in enumerate(runs(jnp.sum(x * coeff, axis=0, keepdims=True))):
            dot = jnp.where(sublane == s, of_run, dot)
        loss, multiplier = pointwise(dot, y, w)
        along_lanes = jnp.concatenate([multiplier[s:s + 1] for s in range(SUBLANES)], axis=1)
        return grad + sum(runs(x * along_lanes)), lsum + loss, wsum + w

    # the last step of a batch that touches one tile fewer is handed the
    # same tile again (no read) and skips it
    @pl.when(first + step <= last)
    def _():
        zero = jnp.zeros((SUBLANES, LANES), jnp.float32)
        grad, lsum, wsum = lax.fori_loop(0, tile // GROUP, one_group, (jnp.zeros_like(coeff_ref), zero, zero))
        grad_ref[...] += grad
        sums_ref[0:SUBLANES, :] += lsum
        sums_ref[SUBLANES:, :] += wsum


def one_pass(pointwise, Xt, y, w, coeff, start, n, batch: int, interpret: bool):
    """(loss_sum, grad_sum, weight_sum) of rows [start, start + batch) of the
    table, as `losses._dense(pointwise)` gives them for that slice, from one
    read of it. `Xt` is the table as [width, rows], `y` (and `w`, or None for
    weight 1 on rows < n) the whole columns; `start` and `n` may be traced.
    `interpret` runs the kernel's own code off the TPU."""
    pl, pltpu = _kernel_language()
    width, rows = Xt.shape
    tile = tile_rows(width, batch, rows)
    steps = _grid_steps(batch, tile)
    weighted = w is not None

    def block(i, scalars):
        first, last = scalars[0] // tile, (scalars[0] + batch - 1) // tile
        return jnp.minimum(first + i, last)

    column = pl.BlockSpec((tile,), lambda i, scalars: (block(i, scalars),))
    fixed = lambda i, scalars: (0, 0)  # noqa: E731
    grad, sums = pl.pallas_call(
        functools.partial(_kernel, pl, pointwise, batch, tile, weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((width, LANES), fixed),
                pl.BlockSpec((width, tile), lambda i, scalars: (0, block(i, scalars))),
                column,
            ]
            + [column] * weighted,
            out_specs=[pl.BlockSpec((width, LANES), fixed), pl.BlockSpec((2 * SUBLANES, LANES), fixed)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((width, LANES), jnp.float32),
            jax.ShapeDtypeStruct((2 * SUBLANES, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="dense_epoch_one_pass",
        interpret=interpret,
    )(
        jnp.stack([start, n]).astype(jnp.int32),
        jnp.broadcast_to(coeff[:, None], (width, LANES)),
        Xt, y, *([w] if weighted else []),
    )
    return jnp.sum(sums[:SUBLANES]), jnp.sum(grad, axis=1), jnp.sum(sums[SUBLANES:])
