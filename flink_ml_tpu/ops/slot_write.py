"""Writes at a batch's slots of a float32 vector and at nothing else of it:
two Pallas TPU kernels.

XLA's TPU scatter writes in place, but passes its whole operand through a
16 MB window of fast memory, a read and a write of every element whatever it
writes: ~2.4 ms for a 0.82 GB vector a batch changes at 37,000 coordinates
(PERF.md §6). Here only what the slots lie in moves:

- `write_in_place`: the vectors are the caller's to give up (donated) and
  the kernel's outputs alias them. A slot's tile, the 1,024 elements of a
  1-D float32 array that Mosaic copies as one (4 KB; a 1- or 128-element
  copy it refuses), is read into fast memory, written there and written
  back; slots in one tile share its read and its write. A chunk of slots
  starts all its reads before it waits for one, and all its writes.
- `copy_writing`: a fresh vector, the input with the slots written, made in
  ONE pass: the input streams through fast memory block by block, and each
  block's slots are written into it there before it is stored. For a
  vector that may not be written in place (a published record holds it),
  where a copy and a scatter would pass over it twice.

The slots are `coord[i]` <- `values[i]` for i < `count`: those coordinates
rise and do not repeat, and each is inside the vector; slots at and past
`count` are not written, wherever they point. `count` may be traced, so one
program serves any number of slots up to the array's length. Every element
that no slot names keeps its bits. The vector holds a whole tile or more:
a shorter one is laid out in tiles of 128, which the copies do not take.
Which caller takes these kernels is the caller's to decide from its arrays
(a TPU, float32, the length); `interpret` runs the kernels' own code
elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.mesh import LANES, SUBLANES
from .dense_epoch import _kernel_language

TILE = SUBLANES * LANES  # elements of a 1-D float32 array that Mosaic copies as one
CHUNK = 1024  # slots held in scalar memory at a time (a multiple of TILE)
BLOCK = 1 << 18  # elements of a block of `copy_writing`'s pass: 1 MB


def _padded(a, size, fill):
    return jnp.pad(a, (0, size - a.shape[0]), constant_values=fill)


def _in_tile():
    """Each element's place in its tile, laid out as the tile is: (8, 128)."""
    return lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES + lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 1
    )


def _tile(pl, at):
    """The tile that starts at element `at` (a multiple of TILE)."""
    return pl.ds(pl.multiple_of(at, TILE), TILE)


def _write(pl, buf, at, offset, value, in_tile):
    """`value` into the tile of `buf` at element `at`, at `offset` in it."""
    tile = buf[_tile(pl, at)].reshape(SUBLANES, LANES)
    buf[_tile(pl, at)] = jnp.where(in_tile == offset, value, tile).reshape(TILE)


def _in_place_kernel(pl, pltpu, k, count_ref, coord_ref, *refs):
    values, outs, bufs = refs[:k], refs[2 * k:3 * k], refs[3 * k:4 * k]
    tile_of_run, run_of_slot, reads, writes = refs[4 * k:]
    live = jnp.clip(count_ref[0] - pl.program_id(0) * CHUNK, 0, CHUNK)

    def copies(run, read):
        where, there = _tile(pl, tile_of_run[run] * TILE), _tile(pl, run * TILE)
        if read:
            return [pltpu.make_async_copy(out.at[where], buf.at[there], reads) for out, buf in zip(outs, bufs)]
        return [pltpu.make_async_copy(buf.at[there], out.at[where], writes) for out, buf in zip(outs, bufs)]

    def each_run(read, act):
        def body(run, carry):
            for copy in copies(run, read):
                getattr(copy, act)()
            return carry

        return body

    @pl.when(live > 0)
    def _():
        def read(j, carry):
            runs, last = carry
            tile = coord_ref[j] // TILE
            new = tile != last

            @pl.when(new)
            def _():
                tile_of_run[runs] = tile
                for copy in copies(runs, True):
                    copy.start()

            runs = runs + new.astype(jnp.int32)
            run_of_slot[j] = runs - 1
            return runs, tile

        runs, _ = lax.fori_loop(0, live, read, (jnp.int32(0), jnp.int32(-1)))
        lax.fori_loop(0, runs, each_run(True, "wait"), 0)
        in_tile = _in_tile()

        def put(j, carry):
            at, offset = run_of_slot[j] * TILE, coord_ref[j] % TILE
            for value, buf in zip(values, bufs):
                _write(pl, buf, at, offset, value[j], in_tile)
            return carry

        lax.fori_loop(0, live, put, 0)
        lax.fori_loop(0, runs, each_run(False, "start"), 0)
        # the next chunk may read a tile this one writes
        lax.fori_loop(0, runs, each_run(False, "wait"), 0)


def write_in_place(arrays, coord, values, count, interpret: bool = False):
    """`arrays` (1-D float32 of one length, given up by the caller) with
    `values[a][i]` at `coord[i]` for i < `count`, written where they lie.

    The vector's last tile, where its length is no multiple of TILE, is no
    whole tile for a copy to reach: its slots (the last of the sorted ones)
    are written by XLA as an update of those few elements."""
    pl, pltpu = _kernel_language()
    k, d = len(arrays), arrays[0].shape[0]
    whole = d // TILE * TILE
    slots = -(-coord.shape[0] // CHUNK) * CHUNK
    at = jnp.arange(coord.shape[0])
    count = jnp.asarray(count, jnp.int32)
    below = jnp.sum((coord < whole) & (at < count), dtype=jnp.int32)
    # chunks past the last live one are handed the same block again: no copy
    live_chunk = lambda i, n: (jnp.minimum(i, jnp.maximum(n[0] - 1, 0) // CHUNK),)  # noqa: E731
    scalars = functools.partial(pl.BlockSpec, (CHUNK,), live_chunk, memory_space=pltpu.SMEM)
    written = pl.pallas_call(
        functools.partial(_in_place_kernel, pl, pltpu, k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // CHUNK,),
            in_specs=[scalars() for _ in range(1 + k)] + [pl.BlockSpec(memory_space=pl.ANY)] * k,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * k,
            scratch_shapes=[pltpu.VMEM((CHUNK * TILE,), jnp.float32)] * k
            + [pltpu.SMEM((CHUNK,), jnp.int32)] * 2
            + [pltpu.SemaphoreType.DMA(())] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((d,), jnp.float32)] * k,
        input_output_aliases={2 + k + a: a for a in range(k)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=k * CHUNK * TILE * 4 + (16 << 20)
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="slot_write_in_place",
    )(
        below.reshape(1),
        _padded(coord, slots, 0),
        *(_padded(v, slots, 0.0) for v in values),
        *arrays,
    )
    if whole == d:
        return tuple(written)
    # the partial tile's slots are among the last `d - whole` before `count`
    m = min(d - whole, coord.shape[0])
    first = jnp.clip(count - m, 0, coord.shape[0] - m)
    ends = lax.dynamic_slice(coord, (first,), (m,))
    mine = (ends >= whole) & (first + jnp.arange(m) < count)
    place = jnp.where(mine, ends - whole, d - whole)
    return tuple(
        lax.dynamic_update_slice(
            out, lax.slice(out, (whole,), (d,)).at[place].set(lax.dynamic_slice(v, (first,), (m,)), mode="drop"), (whole,)
        )
        for out, v in zip(written, values)
    )


def _copy_kernel(pl, pltpu, block, count_ref, coord_hbm, value_hbm, in_ref, out_ref, coord_s, value_s, at):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        at[0] = 0  # the next slot to write
        at[1] = -1  # the chunk of slots held

    out_ref[...] = in_ref[...]
    count, end, in_tile = count_ref[0], (b + 1) * block, _in_tile()

    def fetched(j):
        chunk = j // CHUNK

        @pl.when(chunk != at[1])
        def _():
            there = pl.ds(pl.multiple_of(chunk * CHUNK, CHUNK), CHUNK)
            pltpu.sync_copy(coord_hbm.at[there], coord_s)
            pltpu.sync_copy(value_hbm.at[there], value_s)
            at[1] = chunk

        return j % CHUNK

    def here(j):
        return (j < count) & (coord_s[fetched(j)] < end)

    def write(j):
        offset = coord_s[j % CHUNK] - b * block
        _write(pl, out_ref, offset // TILE * TILE, offset % TILE, value_s[j % CHUNK], in_tile)
        return j + 1

    at[0] = lax.while_loop(here, write, at[0])


def copy_writing(array, coord, values, count, interpret: bool = False, block: int = BLOCK):
    """A fresh copy of `array` (1-D float32) with `values[i]` at `coord[i]`
    for i < `count`, in one pass over it in blocks of `block` elements (a
    multiple of TILE); `array` is left as it is."""
    pl, pltpu = _kernel_language()
    # a chunk more than the slots: the last slot's look at the next one
    slots = -(-coord.shape[0] // CHUNK) * CHUNK + CHUNK
    blocks = pl.BlockSpec((block,), lambda i, n: (i,))
    return pl.pallas_call(
        functools.partial(_copy_kernel, pl, pltpu, block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-array.shape[0] // block),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2 + [blocks],
            out_specs=blocks,
            scratch_shapes=[pltpu.SMEM((CHUNK,), jnp.int32), pltpu.SMEM((CHUNK,), jnp.float32), pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(array.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="slot_write_copy",
    )(
        jnp.asarray(count, jnp.int32).reshape(1),
        _padded(coord, slots, jnp.iinfo(jnp.int32).max),
        _padded(values, slots, 0.0),
        array,
    )
