"""A sparse epoch that gathers only what is sparse: a column plan for a
padded-CSR table, and the loss built from it.

`losses._sparse(pointwise)` is a gather of one coefficient an entry and a
scatter-add of one product an entry, and on the TPU both cost by the entry,
whatever the entry holds (6.6-7.0 ns each way on a v5e, 98% of a sparse
fit's device time: PERF.md §5, §6). A table whose rows are written
field by field (a click log: a row holds one entry a field, the table keeps
field j in column j) does not need them for most of its columns:

- a column that holds ONE id over all its rows is a dense column: its part
  of the row-dot is `values[:, j] * coeff[id]`, its part of the gradient
  `sum(values[:, j] * multiplier)` into that id (a dictionary of one id);
- a column that holds a few hundred distinct ids is a table of that many
  coefficients, its *dictionary*: the row's coefficient is selected by
  comparing the row's id with every id of the dictionary (a reduction XLA
  fuses without writing the rows x dictionary matrix), and its part of the
  gradient is the same comparison reduced over the rows, one sum an id,
  scatter-added into the gradient at the dictionary's ids;
- every other column keeps `losses.sparse_dot` and the scatter-add.

The plan is made once a fit, on the device, by one small program
(`_column_dictionaries`) that counts the distinct ids of every column over
the rows the fit's epochs reach, as cheaply as the column allows: two sorts
of the first `SAMPLE_ROWS` of those rows give each column's candidates; one
pass over all of them confirms them (every valid entry is one of the
candidates); and a column that held an id the sample missed (a rare category
of a skewed field) is sorted in full. An epoch trains batch `epoch mod
batches`, so a fit of E epochs from the first reads the table's first
`min(E x batch, rows)` rows, all of them once it wraps (`plan_fit`); those
rows are read where they lie, with no copy. The host reads back one count a
column to fix the program's static shape: each column's class and its
dictionary's width, rounded up to a power of two so that tables whose counts
differ (partitions of one log, another seed, another day) share a few
compiled programs and not one each. The dictionaries are runtime arrays.

Exact for every input, and no batch can miss: a dictionary holds every id its
column holds in the rows the fit trains, whatever the sample saw, so the
class of a column follows from its count alone and not from the sample's
luck. Ids in rows no epoch reads change no row-dot and no gradient, so they
are not counted: over the rows a fit reads a column may hold fewer ids than
over the whole table, or one. No
entry is dropped, two fields of a row that hash to one id both count (the
dictionaries' sums are scatter-added at their ids beside the gather
columns'), values are applied in float32 after the selection, and nothing is
cast below the table's float32. Only the order of summation differs from
`_sparse` (a row's entries are summed by class, a dictionary's rows by id).
Which fit takes it is `can_plan`'s to say, from the arrays alone, and nothing
else's; every fit on the CPU keeps `_sparse`, on which the bit-parity
contracts between solo, fleet, chunked, stream and whole-fit programs stand.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import tracing
from ..parallel import mesh as mesh_lib
from ..utils import metrics
from ..utils.lazyjit import lazy_jit
from .losses import LossFunc, sparse_dot, sparse_rows_dot

# ids of a dictionary the confirming pass compares a column with at a time,
# and the narrowest dictionary: a tile's lanes
BUCKET = mesh_lib.LANES
# the widest dictionary (T): a column of more distinct ids keeps the gather.
# On a v5e (PERF.md §6, PR 32) a dictionary id costs an epoch of 100,000 rows
# ~0.16 us both ways and a gathered column 1.33 ms, so a bucket of 4,096 ids
# pays and one of 8,192 does not
DICTIONARY_MAX = 32 * BUCKET
# rows of the table, its first, that the plan sorts for its candidates (two
# sorts of 39 columns: 5 ms on a v5e)
SAMPLE_ROWS = 1 << 16
# an id no entry holds: it fills a dictionary up to its width, sorts last,
# and lies past every model's end, where the scatter-add drops it
NO_ID = np.iinfo(np.int32).max

GATHER = 0  # a column's width in a plan: 0 keeps the gather, 1 is a constant column


def can_plan(X, loss_func: LossFunc, mesh) -> bool:
    """Whether a one-shard flat fit asks for a column plan: a padded-CSR
    tuple of int32 ids and float32 values on ONE data shard, arrays on a TPU
    (where a gather costs by the entry; on the CPU `_sparse` stays, to the
    bit), a loss built on a `pointwise`. Read off the arrays, the mesh and
    the loss, nothing a user sets."""
    return (
        isinstance(X, tuple)
        and loss_func.sparse
        and loss_func.pointwise is not None
        and isinstance(X[0], jax.Array)
        and X[0].ndim == 2
        and X[1].dtype == jnp.float32
        and mesh_lib.num_data_shards(mesh) == 1
        and mesh_lib.on_tpu(X[0])
    )


def _distinct(ids):
    """(how many distinct ids the last axis holds, the first `DICTIONARY_MAX`
    of them in ascending order, filled up with `NO_ID`). Padding entries (-1)
    are no id."""
    ordered = jnp.sort(jnp.where(ids >= 0, ids, NO_ID), axis=-1)
    first = jnp.concatenate(
        [jnp.ones_like(ordered[..., :1], bool), ordered[..., 1:] != ordered[..., :-1]], axis=-1
    ) & (ordered != NO_ID)
    distinct = jnp.sort(jnp.where(first, ordered, NO_ID), axis=-1)[..., :DICTIONARY_MAX]
    short = DICTIONARY_MAX - distinct.shape[-1]
    if short > 0:
        distinct = jnp.pad(distinct, [(0, 0)] * (distinct.ndim - 1) + [(0, short)], constant_values=NO_ID)
    return jnp.sum(first, axis=-1, dtype=jnp.int32), distinct


@partial(lazy_jit, static_argnames=("rows",))
def _column_dictionaries(indices, rows=None):
    """(distinct ids a column holds over the table's first `rows` rows, all
    of them by default, i32[nnz], the first `DICTIONARY_MAX` of them in
    ascending order i32[nnz, DICTIONARY_MAX]). A count above
    `DICTIONARY_MAX` is the sample's, and a lower bound. A column's rows are
    cut inside the loop over the columns, which the compiler folds into the
    column's slice of the table (on a TPU, which keeps a narrow table's rows
    on the lanes, the columns are a view of the table): no rows are copied,
    however few the fit reaches, and for all of them the program is the one
    that always read all."""
    rows = indices.shape[0] if rows is None else rows
    candidates = _distinct(indices[: min(rows, SAMPLE_ROWS)].T)

    def of_reached_rows(column):
        ids, (count, known) = column
        ids = ids[:rows]
        # a padding entry has nothing to match, and a constant column no more than its id
        matched = (ids < 0) | (ids == known[0])
        # as many buckets of the candidates as hold ids; none for a column that is no
        # dictionary anyway
        buckets = jnp.where((count > 1) & (count <= DICTIONARY_MAX), -(-count // BUCKET), 0)

        def more(k, matched):
            # ids by rows: the rows stay on the lanes (the other way round a v5e takes 5x as long)
            some = lax.dynamic_slice_in_dim(known, k * BUCKET, BUCKET)
            return matched | jnp.any(some[:, None] == ids[None, :], axis=0)

        matched = lax.fori_loop(0, buckets, more, matched)
        missed = (count <= DICTIONARY_MAX) & jnp.logical_not(jnp.all(matched))
        return lax.cond(missed, _distinct, lambda _: (count, known), ids)

    return lax.map(of_reached_rows, (indices.T, candidates))


def width_of(count: int) -> int:
    """A column's width in the plan from the distinct ids it holds: 1 for a
    constant column, the count rounded up to a power of two (`BUCKET` at
    least) for a dictionary, `GATHER` past `DICTIONARY_MAX` (and for a
    column of padding alone)."""
    if count <= 1:
        return int(count)
    if count > DICTIONARY_MAX:
        return GATHER
    return max(BUCKET, 1 << (count - 1).bit_length())


def column_plan(indices, rows=None) -> Tuple[Optional[Tuple[int, ...]], Optional[jax.Array]]:
    """(each column's width, the dictionaries) of a staged table's ids, over
    its first `rows` rows (all of them by default), or (None, None) where
    every column keeps the gather. One small program and one readback of a
    count a column: a host sync of the fit (kind `plan`), whose wait is the
    program's pass over those rows."""
    counts, dictionaries = _column_dictionaries(indices, rows)
    counts = tracing.sync("plan", counts)
    widths = tuple(width_of(int(count)) for count in counts)
    if not any(widths):
        return None, None
    return widths, dictionaries


def plan_fit(X, loss_func: LossFunc, mesh, batch: int, epochs: int):
    """`column_plan` of a sparse flat fit's staged table where `can_plan`
    admits it, (None, None) elsewhere, and the fit's counters: one tick of
    `sparse_epoch.planned` or `.general`, and the entries of one epoch's
    batch (columns x rows), all of them and those the epoch gathers.

    `epochs` is the most epochs the fit runs from its first: epoch e trains
    batch k = e mod batches, rows [k x batch, (k + 1) x batch) of the table
    as handed over, so the plan reads the first `min(epochs x batch, rows)`
    rows, every row once the epochs wrap. Where it plans,
    `sparse_epoch.plan_rows` and `sparse_epoch.table_rows` count the rows it
    read and the table's."""
    widths = dictionaries = None
    if can_plan(X, loss_func, mesh):
        table_rows = int(X[0].shape[0])
        rows = min(int(epochs) * batch, table_rows)
        widths, dictionaries = column_plan(X[0], rows)
        metrics.inc_counter("sparse_epoch.plan_rows", rows)
        metrics.inc_counter("sparse_epoch.table_rows", table_rows)
    columns = int(X[0].shape[1])
    gathered = columns if widths is None else widths.count(GATHER)
    metrics.inc_counter("sparse_epoch.general" if widths is None else "sparse_epoch.planned")
    metrics.inc_counter("sparse_epoch.entries", batch * columns)
    metrics.inc_counter("sparse_epoch.entries_gathered", batch * gathered)
    return widths, dictionaries


def _columns(arr, columns):
    """The table's `columns` (ascending) as one array: runs of neighbours
    sliced where they lie."""
    runs, start = [], 0
    for i in range(1, len(columns) + 1):
        if i == len(columns) or columns[i] != columns[i - 1] + 1:
            runs.append(arr[:, columns[start] : columns[i - 1] + 1])
            start = i
    return runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=1)


def _blocks(widths: Tuple[int, ...], apart=()):
    """(the gathered columns, (width, the columns of that width) for every
    other width but those kept `apart`, narrowest first)."""
    gathered = tuple(j for j, width in enumerate(widths) if width == GATHER)
    blocks = tuple(
        (width, tuple(j for j, other in enumerate(widths) if other == width))
        for width in sorted(set(widths) - {GATHER, *apart})
    )
    return gathered, blocks


def _slots(dictionaries, blocks):
    """Every dictionary's ids, block by block [columns, width], and all of
    them in one row, where their coefficients are gathered and their sums
    scatter-added."""
    ids_known = [jnp.stack([dictionaries[j, :width] for j in columns]) for width, columns in blocks]
    return ids_known, jnp.concatenate([block.reshape(-1) for block in ids_known])


def planned_loss(loss_func: LossFunc, widths: Tuple[int, ...]):
    """`loss_func` over a table planned as `widths`:
    fn(X, y, w, coeff, dictionaries) -> (loss_sum, grad_sum, weight_sum).
    Columns of one width are taken together, as one block of the batch
    against one block of dictionaries: an op over 100,000 rows costs the chip
    ~0.1 ms whatever it does, and a column of its own would pay that twice."""
    pointwise = loss_func.pointwise
    gathered, blocks = _blocks(widths)

    def fn(X, y, w, coeff, dictionaries):
        indices, values = X
        # every dictionary's ids and their coefficients in one small gather
        ids_known, slots = _slots(dictionaries, blocks)
        known = coeff[jnp.minimum(slots, coeff.shape[0] - 1)]
        dot = jnp.zeros(indices.shape[:1], coeff.dtype)
        taken, offset = [], 0
        for (width, columns), block in zip(blocks, ids_known):
            ids = _columns(indices, columns)
            vals = jnp.where(ids >= 0, _columns(values, columns), 0.0).astype(coeff.dtype)
            of_block = known[offset : offset + block.size].reshape(block.shape)
            offset += block.size
            # [row, column, id of the column's dictionary]: whether it is the row's;
            # one is (the plan holds every row), none for a padding entry
            same = ids[:, :, None] == block[None]
            chosen = jnp.sum(jnp.where(same, of_block[None], 0.0), axis=2)
            dot = dot + jnp.sum(vals * chosen, axis=1)
            taken.append((same, vals))

        grad = jnp.zeros_like(coeff)
        if gathered:
            # the gather's ids wait for the selections: the coefficient comes out
            # of the model update's conditional in HBM, and behind the selections
            # the compiler brings it into fast memory, where an entry costs the
            # gather 6.6 ns against 11.4 (PERF.md §6, PR 32)
            dot, wide = lax.optimization_barrier((dot, _columns(indices, gathered)))
            gather_dot, safe, vals = sparse_dot(wide, _columns(values, gathered), coeff)
            dot = dot + gather_dot
        loss, multiplier = pointwise(dot, y, w)
        if gathered:
            grad = grad.at[safe].add(vals * multiplier[:, None], mode="drop")
        sums = [
            jnp.sum(jnp.where(same, (vals * multiplier[:, None])[:, :, None], 0.0), axis=0).reshape(-1)
            for same, vals in taken
        ]
        grad = grad.at[slots].add(jnp.concatenate(sums), mode="drop")
        return jnp.sum(loss), grad, jnp.sum(w)

    return fn


def planned_rows_loss(loss_func: LossFunc, widths: Tuple[int, ...]):
    """`planned_loss` for N members at once, their coefficients held
    member-minor [d, N] (`losses.rows_variant`'s form, the fleet's row
    program): fn(X, y, w, coeff, dictionaries) -> (loss_sum [N], grad_sum
    [d, N], weight_sum). The plan is the fleet's, made once a fit, and every
    entry is read once for all members:

    - a constant column's part of the row-dots is its values times its id's
      ONE row of N coefficients, and of the gradient its values against the
      multipliers, summed over the rows (no gather an entry);
    - a dictionary column's entry is compared with its dictionary once, as
      `planned_loss` compares it, for its place in the dictionaries' own
      rows ([slots, N], gathered once an epoch): the entry's N coefficients
      are ONE row of that small table, and its gradients ONE row
      segment-summed into it, scatter-added at the dictionaries' ids;
    - a gathered column takes `losses.sparse_rows_dot` and the segment-sum
      of rows into [d, N]."""
    pointwise = loss_func.pointwise
    gathered, blocks = _blocks(widths, apart=(1,))
    constant = tuple(j for j, width in enumerate(widths) if width == 1)

    def fn(X, y, w, coeff, dictionaries):
        indices, values = X
        last = coeff.shape[0] - 1
        dot = jnp.zeros((indices.shape[0], coeff.shape[1]), coeff.dtype)
        if constant:
            constant_ids = dictionaries[jnp.asarray(constant), 0]
            constant_vals = jnp.where(
                _columns(indices, constant) >= 0, _columns(values, constant), 0.0
            ).astype(coeff.dtype)
            dot = dot + jnp.sum(constant_vals[:, :, None] * coeff[jnp.minimum(constant_ids, last)][None], axis=1)
        if blocks:
            ids_known, slots = _slots(dictionaries, blocks)
            known = coeff[jnp.minimum(slots, last)]  # [slots, N]
            places, block_vals, offset = [], [], 0
            for (width, columns), block in zip(blocks, ids_known):
                ids = _columns(indices, columns)
                block_vals.append(jnp.where(ids >= 0, _columns(values, columns), 0.0).astype(coeff.dtype))
                # an entry's place among the slots: the one id of its column's dictionary
                # it is (the plan holds every row's), 0 for a padding entry, whose value is 0
                first = offset + jnp.arange(len(columns), dtype=jnp.int32)[:, None] * width
                same = ids[:, :, None] == block[None]
                places.append(jnp.sum(jnp.where(same, first + jnp.arange(width, dtype=jnp.int32), 0), axis=2))
                offset += block.size
            places, block_vals = jnp.concatenate(places, axis=1), jnp.concatenate(block_vals, axis=1)
            dot = dot + jnp.sum(block_vals[:, :, None] * known[places], axis=1)
        if gathered:
            # as `planned_loss` orders it: the gather's ids wait for the selections
            dot, wide = lax.optimization_barrier((dot, _columns(indices, gathered)))
            gather_dot, safe, gather_vals = sparse_rows_dot(wide, _columns(values, gathered), coeff)
            dot = dot + gather_dot
        loss, multiplier = pointwise(dot, y[:, None], w[:, None])
        grad = jnp.zeros_like(coeff)
        if gathered:
            grad = grad.at[safe].add(gather_vals[:, :, None] * multiplier[:, None, :], mode="drop")
        taken = []  # (ids, the gradient's rows at them)
        if constant:
            taken.append((constant_ids, jnp.sum(constant_vals[:, :, None] * multiplier[:, None, :], axis=0)))
        if blocks:
            sums = jnp.zeros_like(known).at[places].add(block_vals[:, :, None] * multiplier[:, None, :])
            taken.append((slots, sums))
        if taken:
            grad = grad.at[jnp.concatenate([at for at, _ in taken])].add(
                jnp.concatenate([rows for _, rows in taken]), mode="drop"
            )
        return jnp.sum(loss, axis=0), grad, jnp.sum(w)

    return fn
