"""Distributed mini-batch SGD — the training engine for linear models.

TPU-native re-design of common/optimizer/SGD.java:82-292 +
RegularizationUtils.java + Optimizer.java:35. The reference caches
partition data in ListState, per epoch computes a local gradient over the
next batch slice, all-reduces [grad, weightSum, lossSum] with chunked
shuffles, and updates a replicated model. Here the whole dataset lives on
device sharded over the mesh `data` axis, reshaped to
(num_batches, batch, dim) with zero-weight padding rows (static shapes —
the reference's ragged final batch becomes padded rows that contribute
nothing), and the epoch loop is one XLA while-loop: the gradient
contraction over the sharded batch axis makes XLA insert the ICI psum that
replaces AllReduceImpl.java:71-103.

The whole training loop is ONE module-level jitted function whose data and
hyperparameters are runtime arguments: repeated fits with the same shapes
reuse the compiled executable (and the persistent compilation cache works
across processes), so only the first-ever fit pays XLA compile time.

Semantics matched to the reference for loss parity:
- batch k = rows [k*B, (k+1)*B) cycling, B = globalBatchSize;
- update: coeff -= lr/totalWeight * grad, then proximal regularization
  (RegularizationUtils.regularize); first epoch computes a gradient on the
  initial model before any update; one extra update after termination
  (SGD.java onIterationTerminated);
- termination criteria = totalLoss/totalWeight, stop on
  (epoch+1) >= maxIter or loss <= tol (TerminateOnMaxIterOrTol.java:72).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import tracing
from ..parallel import collectives
from ..parallel import mesh as mesh_lib
from ..parallel import prefetch as h2d
from ..utils import metrics
from ..utils.lazyjit import lazy_jit
from . import dense_epoch, sparse_epoch
from .losses import PRODUCT_VARIANTS, ROW_VARIANTS, LossFunc


@partial(jax.tree_util.register_dataclass, data_fields=("strips",), meta_fields=("width",))
@dataclass(frozen=True)
class BatchStrips:
    """A table as the exchange lays it out (`_exchange_batches_impl`): `strips`
    is (num_batches, shards, width_pad, piece), each batch's share on a shard
    [column, row] as it arrived, and `width` the table's own. Written for the
    TPU, which keeps such an array as it reads, [batch][shard][column][row] in
    whole (8, 128) tiles, once the column axis is whole sublanes (`width_pad`;
    the rows it pads itself): batch k of a shard's share is one contiguous
    run. The same array as (num_batches, batch, width), the general form's,
    the v5e keeps [column][batch][row], eight batches to a tile. `shape` and
    `dtype` are the general form's, for those who only ask."""

    strips: jax.Array
    width: int

    @property
    def dtype(self):
        return self.strips.dtype

    @property
    def shape(self):
        num_batches, shards, _, piece = self.strips.shape
        return (num_batches, shards * piece, self.width)

    def batch(self, k):
        """Batch k as [rows, width]: the strips where they lie, read the
        other way round, the pad columns left out."""
        strips = lax.dynamic_index_in_dim(self.strips, k, 0, False)
        return jnp.swapaxes(strips, 1, 2)[:, :, : self.width].reshape(-1, self.width)


@partial(jax.tree_util.register_dataclass, data_fields=("rows",), meta_fields=("size",))
@dataclass(frozen=True)
class FlatBatches:
    """A one-shard table where it lies, read as its batches
    (`SGD._in_place`): `rows` is the caller's own (n, width) array, n a whole
    number of batches of `size` rows, and batch k is rows [k * size,
    (k + 1) * size), sliced where an epoch reads it as `_sgd_train_flat`
    slices its own. Nothing is laid out: the general form's (num_batches,
    batch, width) array is a second table (and a third where the device pads
    100 columns to a tile's 128 lanes), which a table that fills half a chip
    has no room for."""

    rows: jax.Array
    size: int

    @property
    def dtype(self):
        return self.rows.dtype

    def batch(self, k):
        return lax.dynamic_slice_in_dim(self.rows, k * self.size, self.size, 0)


def _index_batch(X_b, k):
    """Select batch k from batched features; X may be a dense array or the
    sparse (indices, values) tuple — every driver treats features as a
    pytree so the sparse padded-CSR layout flows through unchanged. Each
    array says by its own form how its batch is found: the general layout's
    (num_batches, batch, ...) by an index, the exchange's `BatchStrips` and
    the in-place `FlatBatches` by their views."""
    if isinstance(X_b, tuple):
        return tuple(_index_batch(leaf, k) for leaf in X_b)
    if isinstance(X_b, (BatchStrips, FlatBatches)):
        return X_b.batch(k)
    return lax.dynamic_index_in_dim(X_b, k, 0, False)


def _slice_rows(X, start, rows):
    if isinstance(X, tuple):
        return tuple(lax.dynamic_slice_in_dim(leaf, start, rows, 0) for leaf in X)
    return lax.dynamic_slice_in_dim(X, start, rows, 0)


def _feature_dtype(X):
    return X[1].dtype if isinstance(X, tuple) else X.dtype


def _layout_batches_impl(arr, n, num_batches, batch, b_pad, d_pad, sharding):
    """Device-side batch layout: strip any staging pad beyond the true row
    count n, pad rows to num_batches*batch, reshape to
    (num_batches, batch, ...), pad the per-batch axis to b_pad (divisible
    over the data shards) and optionally the feature axis to d_pad, then
    constrain to the training sharding. Runs entirely in HBM — the host
    never copies the dataset (the round-1 host re-layout at ~30 MB/s was
    the training bottleneck).

    The general form of the layout: it takes any input (ragged row counts,
    a staging pad, `b_pad`, `d_pad`, replicated data, one shard, a table
    sharded some other way) and leaves the movement between chips to GSPMD.
    `SGD._lay_out` takes `_exchange_batches_impl` instead where
    `_can_exchange` says the input allows it, and counts which form ran
    (`layout.general`, `layout.exchange`). On a row-sharded table GSPMD
    finds one all-to-all too; what the exchange saves is XLA's way of
    splitting the row axis of a table the TPU keeps rows-minor."""
    if arr.shape[0] != n:
        arr = arr[:n]
    pad_rows = num_batches * batch - n
    if pad_rows:
        arr = jnp.pad(arr, [(0, pad_rows)] + [(0, 0)] * (arr.ndim - 1))
    arr = arr.reshape((num_batches, batch) + arr.shape[1:])
    if b_pad != batch:
        arr = jnp.pad(arr, [(0, 0), (0, b_pad - batch)] + [(0, 0)] * (arr.ndim - 2))
    if d_pad is not None and d_pad != arr.shape[-1]:
        arr = jnp.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, d_pad - arr.shape[-1])])
    return lax.with_sharding_constraint(arr, sharding)


_LAYOUT_STATICS = ("n", "num_batches", "batch", "b_pad", "d_pad", "sharding")
# Borrowed variant for caller-owned buffers (device-born Table columns);
# donating variant for buffers _batchify staged itself — donation lets XLA
# free the flat copy during layout, halving peak HBM for the dataset.
_layout_batches = lazy_jit(_layout_batches_impl, static_argnames=_LAYOUT_STATICS)
_layout_batches_donating = lazy_jit(
    _layout_batches_impl, static_argnames=_LAYOUT_STATICS, donate_argnums=(0,)
)


def _padded_strip(width, piece):
    """The shape the exchange sends a [column, piece of a batch] strip in:
    padded to whole tiles of a 32-bit type."""
    sublanes, lanes = mesh_lib.SUBLANES, mesh_lib.LANES
    return -(-width // sublanes) * sublanes, -(-piece // lanes) * lanes


def _pad_is_small(width, piece) -> bool:
    """Whether that pad adds at most a sixteenth to the bytes sent and
    staged: 4.4% for the 100 columns and 25000 rows of the reference's
    table on four shards, 16 times for a piece of 8 rows."""
    width_pad, piece_pad = _padded_strip(width, piece)
    return 16 * width_pad * piece_pad <= 17 * width * piece


def _can_exchange(arr, n, batch, shards, d_pad, mesh) -> bool:
    """Whether `_exchange_batches_impl` gives `_layout_batches_impl`'s
    batches, and cheaper. It gives them for a device table of exactly n rows,
    sharded by rows over the mesh's data axis (`shards` of them; 1 under
    `replicate_data`), where every shard's rows are whole batches, a batch
    divides over the shards and no feature pad is asked for. It is cheaper
    where the device keeps the rows minor (`mesh_lib.rows_minor`; on rows
    kept major, the CPU's and a wide table's, the general form is already
    one all-to-all between two copies), the pad to whole tiles is small
    (`BatchStrips` keeps it for the whole fit), and a shard's batches are
    whole slabs of the exchange. Only 32-bit types: a bfloat16 table's tile
    is another, and the same code compiled for it to two passes where this
    has one. A 1-D column (y, a weight) keeps the general form, 0.5 ms of a
    fit on four v5e chips. All read off shapes, the dtype, the sharding and
    the device's layout, nothing a user sets; and the one place that
    decides: what it turns away keeps the general form's (num_batches,
    batch, ...) array as it was, and the training loops tell the two apart
    by what they are handed (`_index_batch`)."""
    return (
        shards > 1
        and d_pad is None
        and isinstance(arr, jax.Array)
        and arr.ndim == 2
        and arr.dtype.itemsize == 4
        and arr.shape[0] == n
        and n % (shards * batch) == 0
        and batch % shards == 0
        and _pad_is_small(arr.shape[1], batch // shards)
        and n // (shards * batch) % mesh_lib.SUBLANES == 0
        and arr.sharding.is_equivalent_to(mesh_lib.data_sharding(mesh, 2), 2)
        and mesh_lib.rows_minor(arr)
    )


def _can_one_pass(X, loss_func, mesh) -> bool:
    """Whether a one-shard flat fit's epochs take `dense_epoch.one_pass`, one
    read of the batch, for `loss_func`'s two reductions over it. The kernel
    is written for a dense table the TPU keeps rows-minor
    (`mesh_lib.rows_minor`: narrower than a tile's lanes; a wide table kept
    rows-major gives it no [column, row] view), of a 32-bit float type (a
    bfloat16 tile is another), with at least one 1-D tile of rows, on ONE
    data shard (a custom call has no partitioning rule, and `_sgd_train`
    already reads its batch once), for a loss built on a `pointwise`. All
    read off the array, the mesh and the loss, nothing a user sets; the one
    place that decides, counted as `dense_epoch.one_pass` or
    `dense_epoch.reduce` a dense fit. What it turns away keeps `dense_dot`
    and `dense_grad` as they are: so every fit on the CPU, whose bit-parity
    contracts between solo, fleet, chunked and whole-fit programs stand on
    the reduce form."""
    return (
        isinstance(X, jax.Array)
        and X.ndim == 2
        and X.dtype == jnp.float32
        and X.shape[0] >= dense_epoch.GROUP
        and loss_func.pointwise is not None
        and mesh_lib.num_data_shards(mesh) == 1
        and mesh_lib.on_tpu(X)
        and mesh_lib.rows_minor(X)
    )


def _can_walk(X, y, weights, batch, max_iter, dtype, mesh) -> bool:
    """Whether a fit on several data shards trains every batch on the shard
    that holds it (`SGD._stage_walk`) and lays nothing out. It does where no
    batch is read twice, `max_iter` at most the table's batches (one pass or
    a part of one: a layout built in the fit would be used once), over a
    dense float table sharded by rows over a mesh of the data axis alone,
    every share in this process and whole batches (so every global batch,
    contiguous rows, lies whole on one shard), with y, and a weight column
    where there is one, device columns sharded the same way, all of the
    engine's dtype (a cast is a copy of the table). All read off shapes,
    dtypes, the sharding, the mesh and `max_iter`, nothing a user sets; and
    the one place that decides, counted as `layout.walk` a walked fit. What it
    turns away keeps the batched route as it was: a fit of several passes, a
    straddling batch, a sparse or a host table pay the layout once and train
    data-parallel (`_can_exchange` then says which form lays the table out).
    The routes decided before it in `_stage_async` (one shard, feature
    sharding, a checkpoint directory) and the fleet's `replicate_data` never
    ask."""
    shards = mesh_lib.num_data_shards(mesh)
    columns = [y] if weights is None else [y, weights]
    if not (
        shards > 1
        and mesh.devices.size == shards
        and isinstance(X, jax.Array)
        and X.ndim == 2
        and all(isinstance(c, jax.Array) and c.ndim == 1 for c in columns)
    ):
        return False
    n = X.shape[0]
    return (
        jnp.issubdtype(X.dtype, jnp.floating)
        and n > 0
        and n % (shards * batch) == 0
        and max_iter <= n // batch
        and X.is_fully_addressable
        and X.sharding.is_equivalent_to(mesh_lib.data_sharding(mesh, 2), 2)
        and all(
            c.shape[0] == n and c.sharding.is_equivalent_to(mesh_lib.data_sharding(mesh, 1), 1)
            for c in columns
        )
        and all(arr.dtype == dtype for arr in [X] + columns)
    )


def _can_train_in_place(X, y, weights, batch, dtype, mesh) -> bool:
    """Whether a fleet's programs read a table's batches where the table
    lies (`SGD._in_place`, a `FlatBatches` view) and lay nothing out. They do
    where `_stage_flat` would train a solo fit without a copy: ONE device
    (a mesh of it alone, and the table on it), a dense table of the
    engine's dtype (a cast is a copy of the table), or a padded-CSR pair of
    int32 ids and values of that dtype (`FlatBatches` of each), whose rows
    are a whole number of batches (ragged rows keep the padded copy), with
    y, and a weight column where there is one, device columns of that dtype.
    All read off shapes, dtypes, the arrays' devices and the mesh, nothing a
    user sets; and the one place that decides, counted as `fleet.in_place` a
    fleet fit. What it turns away keeps the laid-out route as it was:
    several data shards, the fleet-sharded regime (which needs several), a
    host table. `FitFleet` never asks for a stream or under a checkpoint
    directory."""
    columns = [y] if weights is None else [y, weights]
    leaves, dtypes = (X, (jnp.int32, dtype)) if isinstance(X, tuple) else ((X,), (dtype,))
    if not (
        len(leaves) == len(dtypes)
        and all(isinstance(a, jax.Array) and a.ndim == 2 and a.dtype == t for a, t in zip(leaves, dtypes))
        and len({a.shape for a in leaves}) == 1
    ):
        return False
    n = leaves[0].shape[0]
    return (
        mesh.devices.size == 1
        and n > 0
        and n % batch == 0
        and all(a.devices() == set(mesh.devices.flat) for a in leaves)
        and all(isinstance(c, jax.Array) and c.ndim == 1 and c.dtype == dtype for c in columns)
    )


def _viewed(X_b):
    """The array behind a table as the fleet's programs are handed it: a
    `FlatBatches` view's rows, `BatchStrips`' strips, or the array itself."""
    return X_b.rows if isinstance(X_b, FlatBatches) else X_b.strips if isinstance(X_b, BatchStrips) else X_b


def _fleet_multiplies(X_b, loss_func) -> bool:
    """Whether a fleet's epochs form their members' row-dots and gradients as
    two float32 matrix products at `Precision.HIGHEST`
    (`losses.product_variant`: under the member `vmap` ONE [B, d] x [d, N]
    and ONE [N, B] x [B, d] on the TPU's matrix unit) in place of the reduce
    form's two vector-unit reductions, which took 94% of a 100-member
    fleet's epoch (PERF.md §5, PR 39). They do where the table, as the
    fleet's programs are handed it (in place, laid out, in strips or a
    stream's stacked segments), is a dense float32 array on a TPU, for a
    dense loss. All read off the array and the loss, nothing a user sets;
    the one place that decides, counted as `fleet.product.matrix` or
    `fleet.product.reduce` a fleet fit. What it turns away keeps `dense_dot`
    and `dense_grad`: so every fleet on the CPU, whose members tier-1 holds
    to their solo fits bit for bit. The solo programs never ask."""
    X = _viewed(X_b)
    return (
        loss_func.name in PRODUCT_VARIANTS
        and isinstance(X, jax.Array)
        and X.dtype == jnp.float32
        and mesh_lib.on_tpu(X)
    )


def _fleet_rows(X_b, loss_func) -> bool:
    """Whether a fleet's epochs take the member-row form
    (`_sgd_fleet_rows_whole_fit`, `losses.rows_variant`,
    `sparse_epoch.planned_rows_loss`): the coefficients held [d, N], an
    entry's N coefficients ONE gathered row and its N gradients ONE row
    segment-summed, where `_sparse` under the member `vmap` gathers and
    scatters N single values an entry out of and into [N, d]. They do where
    the table, as the fleet's programs are handed it (in place, laid out or
    in strips), is a padded-CSR pair whose values are float32 on a TPU, for a sparse
    loss: `_fleet_multiplies`' place, for the table it turns away. All read
    off the arrays and the loss, nothing a user sets; `FitFleet` asks for its
    whole-fit route of one fleet alone (not under a checkpoint directory, not
    fleet-sharded) and counts the answer as `fleet.product.rows` or
    `fleet.product.reduce`. What it turns away keeps `_sparse`: so every
    fleet on the CPU, whose members tier-1 holds to their solo fits bit for
    bit."""
    if not (isinstance(X_b, tuple) and len(X_b) == 2):
        return False
    values = _viewed(X_b[1])
    return (
        loss_func.name in ROW_VARIANTS
        and isinstance(values, jax.Array)
        and values.dtype == jnp.float32
        and mesh_lib.on_tpu(values)
    )


def _exchange_batches_impl(arr, batch, mesh):
    """The batch layout of a row-sharded table [rows, width] as one explicit
    exchange: every shard cuts each of its own batches (it holds whole
    ones) into one piece a shard, an all-to-all sends piece j of every batch
    to shard j, and source shard c's local batch b arrives as global batch
    c*nb_local + b. The batches are `_layout_batches_impl`'s to the letter,
    shard by shard, for the inputs `_can_exchange` admits (a dense X and
    both sparse leaves), handed over as `BatchStrips`: every batch stays
    [column, row], as it arrived.

    Written for a table the device keeps rows-minor, in memory [column,
    row]: there a piece of a batch is a strip of every column, and a
    reshape that splits the row axis (at 25000, no multiple of the 128
    lanes) costs XLA a column-by-column loop over the whole share, 92 of the
    139 ms the general form takes for a 3.3 GB share on four v5e chips.
    Here each strip is sliced out where it lies and written, padded to
    whole tiles, into a staging buffer [shard, batch, column, row] that the
    all-to-all takes as it is. The padding is what keeps XLA from
    transposing the staged buffer before and after the all-to-all. The
    exchange goes in slabs of eight batches, so that only one slab is staged
    and received at a time.

    `order` below says where every strip belongs and moves nothing: since
    the result keeps a batch as it arrived, the compiler lays the buffer the
    slabs are stacked into [source shard][slab][batch of the slab], writes
    each slab's arrival into its final place, and the cut of the pad rows is
    the same bytes read another way (compiled for four v5e chips: 1.0 GB of
    temporaries for a 3.3 GB share, where the general form takes 6.4)."""
    data = mesh_lib.DATA_AXIS
    shards = mesh_lib.num_data_shards(mesh)
    width = arr.shape[1]
    piece = batch // shards
    nb_local = arr.shape[0] // (shards * batch)
    slab = mesh_lib.SUBLANES
    width_pad, piece_pad = _padded_strip(width, piece)

    def stage(local, first):
        """One shard's strips of local batches first .. first + slab, as
        [1, destination shard, batch x column, row]."""
        cols = local.T  # [column, row]: as it lies

        def strip(k):  # of local batch first + k % slab, for shard k // slab
            start = ((first + k % slab) * shards + k // slab) * piece
            rows = lax.dynamic_slice_in_dim(cols, start, piece, 1)
            return jnp.pad(rows, ((0, width_pad - width), (0, piece_pad - piece)))

        staged = lax.map(strip, jnp.arange(shards * slab))
        return staged.reshape(1, shards, slab * width_pad, piece_pad)

    def exchange_slab(first):
        staged = collectives.shard_map_over(
            mesh, (P(data, None), P()), P(data, None, None, None), stage
        )(arr, first)  # [source shard, destination shard, ...], held by the source
        return collectives.all_to_all(staged, mesh, 0, 1)  # ... by the destination

    arrived = lax.map(exchange_slab, jnp.arange(0, nb_local, slab))

    def order(local):
        """[slab index, source shard, 1, batch of the slab x column, row] ->
        [source shard x slab index x batch, 1, column, row]: the global batch
        order, the pad rows cut."""
        strips = local.reshape(-1, shards, slab, width_pad, piece_pad)[..., :piece]
        return jnp.swapaxes(strips, 0, 1).reshape(shards * nb_local, 1, width_pad, piece)

    strips = collectives.shard_map_over(
        mesh, P(None, None, data, None, None), P(None, data, None, None), order
    )(arrived)
    return BatchStrips(strips, width)


_EXCHANGE_STATICS = ("batch", "mesh")
_exchange_batches = lazy_jit(_exchange_batches_impl, static_argnames=_EXCHANGE_STATICS)
_exchange_batches_donating = lazy_jit(
    _exchange_batches_impl, static_argnames=_EXCHANGE_STATICS, donate_argnums=(0,)
)


@partial(
    lazy_jit,
    static_argnames=("n", "num_batches", "batch", "b_pad", "dtype", "sharding"),
)
def _default_weights(n, num_batches, batch, b_pad, dtype, sharding):
    """Unit weights for the first n rows, 0 for padding — generated on
    device so the default-weight case transfers nothing."""
    idx = jnp.arange(num_batches * batch)
    w = (idx < n).astype(dtype).reshape(num_batches, batch)
    if b_pad != batch:
        w = jnp.pad(w, [(0, 0), (0, b_pad - batch)])
    return lax.with_sharding_constraint(w, sharding)


def regularize(coeff, reg, elastic_net, learning_rate):
    """Proximal regularization step; returns (new_coeff, reg_loss).

    Matches RegularizationUtils.regularize, including its use of the
    (unsquared) L2 norm in the reported L2 loss. All arguments may be traced
    values — branch selection is by jnp.where so one compiled program covers
    every (reg, elasticNet) configuration.
    """
    reg = jnp.asarray(reg, coeff.dtype)
    en = jnp.asarray(elastic_net, coeff.dtype)
    sign = jnp.sign(coeff)
    # The single proximal formula specializes to each reference branch:
    # en=0 -> coeff*(1 - lr*reg); en=1 -> coeff - lr*reg*sign; else mixed.
    step = learning_rate * (en * reg * sign + (1.0 - en) * reg * coeff)
    new_coeff = jnp.where(reg > 0.0, coeff - step, coeff)
    l2_only = reg / 2.0 * jnp.linalg.norm(coeff)
    l1_only = jnp.sum(en * reg * sign)
    mixed = jnp.sum(en * reg * sign + (1.0 - en) * (reg / 2.0) * coeff * coeff)
    loss = jnp.where(
        reg == 0.0, 0.0, jnp.where(en == 0.0, l2_only, jnp.where(en == 1.0, l1_only, mixed))
    )
    return new_coeff, loss


def _update_model(coeff, grad, wsum, lr, reg, elastic_net):
    def do_update(c):
        c = c - (lr / jnp.maximum(wsum, 1e-30)) * grad
        c, _ = regularize(c, reg, elastic_net, lr)
        return c

    return lax.cond(wsum > 0, do_update, lambda c: c, coeff)


# Jitted entry for the host-driven tails (stream + checkpointed loops):
# called eagerly, the lax.cond closes over that fit's gradient VALUES as
# constants and XLA compiles a fresh program per fit — one stray compile
# per stream fit on the jit.compiles counter. As a jitted function all
# operands are runtime arguments, so every fit at a given model shape
# re-enters one executable.
_final_update = lazy_jit(_update_model)


def _binomial_labels_ok(y):
    """{0,1} label validity flag (LogisticRegression.java:78-87), fused
    into the training program so validation rides the fit's single packed
    readback instead of costing its own host round trip. Weight-0 padding
    rows carry label 0.0, which passes the check by construction."""
    return jnp.all((y == 0.0) | (y == 1.0)).astype(jnp.float32)


def _unpack_hyper(hyper, dtype):
    """(max_iter, tol, lr, reg, elastic_net) views of the packed f32
    hyper-parameter vector. One small H2D transfer replaces five scalar
    uploads per fit — every host→device buffer is its own transfer."""
    return (
        hyper[0].astype(jnp.int32),
        hyper[1],
        hyper[2].astype(dtype),
        hyper[3].astype(dtype),
        hyper[4].astype(dtype),
    )


def _pack_train_result(coeff, criteria, epochs, flag=None, pack_sharding=None):
    """Fuse (flag?, coeff, criteria, epochs) into ONE flat array INSIDE the
    training program, so the host reads everything back in a single
    transfer. Packs in at least float32 so integer epoch counts stay exact
    under low-precision compute dtypes. With `pack_sharding` every part is
    first constrained to one (replicated) layout: GSPMD miscompiles a
    concatenate of differently-sharded parts on a multi-axis mesh into a
    cross-data-shard partial-sum (each value comes back multiplied by the
    data-axis size) — the constraint forces the all-gather first."""
    dt = jnp.promote_types(coeff.dtype, jnp.float32)
    parts = [
        coeff.astype(dt),
        jnp.reshape(jnp.asarray(criteria).astype(dt), (1,)),
        jnp.reshape(jnp.asarray(epochs).astype(dt), (1,)),
    ]
    if flag is not None:
        parts.insert(0, jnp.reshape(flag.astype(dt), (1,)))
    if pack_sharding is not None:
        parts = [lax.with_sharding_constraint(p, pack_sharding) for p in parts]
    return jnp.concatenate(parts)


def _pack_leg_carry(state, ok):
    """A leg's end state (coeff, grad, wsum, epoch, criteria) and the label
    flag so far (1 where none is kept) as ONE vector of 2 * dim + 4 numbers,
    in `_pack_train_result`'s type: the next leg's device is handed one
    array."""
    coeff, grad, wsum, epoch, criteria = state
    dt = jnp.promote_types(coeff.dtype, jnp.float32)
    tail = jnp.stack([wsum.astype(dt), epoch.astype(dt), criteria.astype(dt), ok.astype(dt)])
    return jnp.concatenate([coeff.astype(dt), grad.astype(dt), tail])


def _first_leg_carry(init_coeff, dtype):
    """`_pack_leg_carry`'s vector of a fit's start, as `_sgd_train_flat`
    starts without one: no gradient, no weight, epoch 0, a loss of infinity,
    every label fine so far. Made on the host for a host coefficient (one
    upload with the first leg's launch), where it lies for a device one."""
    xp = jnp if isinstance(init_coeff, jax.Array) else np
    coeff = xp.asarray(init_coeff).astype(dtype).astype(np.promote_types(dtype, np.float32))
    return xp.concatenate([coeff, xp.zeros_like(coeff), xp.asarray([0.0, 0.0, np.inf, 1.0], coeff.dtype)])


def _unpack_leg_carry(carry, dtype):
    """(state, flag so far) of `_pack_leg_carry`'s vector, the state in the
    loop's own types (an epoch count is exact in float32 below 2^24, as
    `_hyper`'s max_iter is)."""
    d = (carry.shape[0] - 4) // 2
    state = (
        carry[:d].astype(dtype),
        carry[d : 2 * d].astype(dtype),
        carry[2 * d].astype(dtype),
        carry[2 * d + 1].astype(jnp.int32),
        carry[2 * d + 2].astype(jnp.float32),
    )
    return state, carry[2 * d + 3].astype(jnp.float32)


@partial(
    lazy_jit,
    static_argnames=("loss_func", "batch", "has_weights", "check_labels", "one_pass", "interpret", "plan"),
)
def _sgd_train_flat(
    X, y, w, init_coeff, loss_func, batch, has_weights, n, hyper, check_labels,
    one_pass=False, interpret=False, plan=None, dictionaries=None, carry=None,
):
    """Single-data-shard variant of `_sgd_train` that slices each epoch's
    batch straight out of the FLAT row-major arrays with a dynamic slice.

    The batched (num_batches, B, d) layout exists so every batch spans all
    data shards; with one data shard it is a pure 4GB copy program on the
    critical path. Here the only program in the fit chain is this train loop —
    the result pack and (for classifiers) the label-validity check are
    fused into it. Rows are pre-padded to a batch multiple; absent
    weights are synthesized in-loop as (row_index < n) so padding rows
    contribute nothing and no separate weights program runs.

    With `one_pass` (`_can_one_pass`'s word, `_stage_flat` asks) nothing is
    sliced: an epoch's loss is `dense_epoch.one_pass` over the whole table,
    which reads batch k where it lies, once; the table is handed over the
    other way round and the columns as one row, views of the same bytes
    made once, outside the loop. `interpret` is for a table off the TPU.

    With `plan` (`sparse_epoch.plan_fit`'s widths, `_stage_flat` asks) a
    sparse epoch's loss is `sparse_epoch.planned_loss` over the sliced batch
    and the plan's `dictionaries`, in `loss_func`'s place.

    With `carry` the program is one leg of a walked fit (`SGD._stage_walk`):
    the table is one shard's share of whole batches, the loop takes up where
    the leg before left off (`carry` is its end state, `_pack_leg_carry`'s
    vector; the first leg's is made from the start coefficient, and
    `init_coeff` is None), `hyper`'s max_iter is the epoch this leg ends at,
    and the end state is handed on in place of the result (`_finish_walk`
    makes that of the last leg's). One program serves every leg: a kernel's
    lowering costs a process 0.3 s a program. No first row is asked for: a fit
    that reads no batch twice has batch k = epoch, and the shares hold equally
    many, so `epoch mod the share's batches` is the batch's place in the
    share. Without `carry` all of it folds away and the program is the
    one-shard fit's."""
    sliced_loss = loss_func
    if plan is not None:
        sliced_loss = partial(sparse_epoch.planned_loss(loss_func, plan), dictionaries=dictionaries)
    num_batches = y.shape[0] // batch
    dtype = _feature_dtype(X)
    max_iter, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
    if one_pass:
        views = (X.T, y, w if has_weights else None)

    def cond(state):
        _, _, _, epoch, criteria = state
        return jnp.logical_and(epoch < max_iter, criteria > tol)

    def body(state):
        coeff, grad, wsum, epoch, _ = state
        k = jnp.mod(epoch, num_batches)
        start = k * batch
        if one_pass:
            Xk, yk, wk = views
            loss = partial(
                dense_epoch.one_pass, loss_func.pointwise,
                start=start, n=n, batch=batch, interpret=interpret,
            )
        else:
            loss = sliced_loss
            Xk = _slice_rows(X, start, batch)
            yk = lax.dynamic_slice_in_dim(y, start, batch, 0)
            if has_weights:
                wk = lax.dynamic_slice_in_dim(w, start, batch, 0)
            else:
                wk = ((jnp.arange(batch) + start) < n).astype(dtype)
        carry, criteria = _epoch_step(
            Xk, yk, wk, (coeff, grad, wsum, epoch), loss, lr, reg, elastic_net
        )
        return carry + (criteria,)

    if carry is None:
        init_state = (
            jnp.asarray(init_coeff, dtype),
            jnp.zeros((init_coeff.shape[0],), dtype),
            jnp.asarray(0.0, dtype),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(jnp.inf, jnp.float32),
        )
    else:
        init_state, ok_so_far = _unpack_leg_carry(carry, dtype)
    coeff, grad, wsum, epochs, criteria = state = lax.while_loop(cond, body, init_state)
    if carry is None:
        coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    flag = _binomial_labels_ok(y) if check_labels else None
    if carry is not None:
        return _pack_leg_carry(state, ok_so_far if flag is None else jnp.minimum(flag, ok_so_far))
    return _pack_train_result(coeff, criteria, epochs, flag)


@partial(lazy_jit, static_argnames=("dtype", "has_flag"))
def _finish_walk(carry, hyper, dtype, has_flag):
    """A walked fit's result of its last leg's end state, on that leg's
    device: the one extra update after termination and the pack, as the
    one-shard program ends."""
    (coeff, grad, wsum, epochs, criteria), flag = _unpack_leg_carry(carry, dtype)
    _, _, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
    coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    return _pack_train_result(coeff, criteria, epochs, flag if has_flag else None)


@partial(lazy_jit, static_argnames=("loss_func", "check_labels", "pack_sharding"))
def _sgd_train(X_b, y_b, w_b, init_coeff, loss_func, hyper, check_labels, pack_sharding):
    """The full bounded training iteration as one XLA program.

    State machine mirrors SGD.java's CacheDataAndDoTrain: each epoch first
    applies the gradient reduced in the previous epoch, then computes the
    gradient of the next batch; one extra update lands after termination.
    Returns the packed [flag?, coeff, criteria, epochs] result vector
    (`unpack_train_result` is the host-side inverse).
    """
    num_batches = y_b.shape[0]
    d = init_coeff.shape[0]
    dtype = _feature_dtype(X_b)
    max_iter, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)

    def cond(state):
        _, _, _, epoch, criteria = state
        return jnp.logical_and(epoch < max_iter, criteria > tol)

    def body(state):
        coeff, grad, wsum, epoch, _ = state
        k = jnp.mod(epoch, num_batches)
        Xk = _index_batch(X_b, k)
        yk = lax.dynamic_index_in_dim(y_b, k, axis=0, keepdims=False)
        wk = lax.dynamic_index_in_dim(w_b, k, axis=0, keepdims=False)
        carry, criteria = _epoch_step(
            Xk, yk, wk, (coeff, grad, wsum, epoch), loss_func, lr, reg, elastic_net
        )
        return carry + (criteria,)

    init_state = (
        jnp.asarray(init_coeff, dtype),
        jnp.zeros((d,), dtype),
        jnp.asarray(0.0, dtype),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(jnp.inf, jnp.float32),
    )
    coeff, grad, wsum, epochs, criteria = lax.while_loop(cond, body, init_state)
    coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    flag = _binomial_labels_ok(y_b) if check_labels else None
    return _pack_train_result(coeff, criteria, epochs, flag, pack_sharding)


def _epoch_step(Xk, yk, wk, carry, loss_func, lr, reg, elastic_net):
    """The single-epoch math shared by every driver (`_sgd_train` body,
    host-driven checkpointing epochs, out-of-core stream epochs): apply the
    previous gradient, compute the next on this epoch's batch. One
    definition keeps the documented stream/in-memory coefficient parity a
    structural fact rather than three copies to keep in sync."""
    coeff, grad, wsum, epoch = carry
    coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    lsum, grad, wsum = loss_func(Xk, yk, wk, coeff)
    criteria = lsum / jnp.maximum(wsum, 1e-30)
    return (coeff, grad, wsum, epoch + 1), jnp.asarray(criteria, jnp.float32)


def _stream_epoch_impl(Xk, yk, wk, carry, criteria, loss_func, hyper):
    """Out-of-core epoch: the batch arrives as an argument (read back from
    the spillable data cache) instead of being indexed out of a resident
    (num_batches, B, d) array — only one batch ever occupies HBM.

    Criteria-guarded so the host may dispatch stream epochs ahead of their
    convergence readbacks: once `criteria <= tol` the program is an
    identity on (carry, criteria), exactly like a chunk dispatched past
    the tol-fire epoch. Returns (carry, criteria, packed[epoch, criteria])."""
    dtype = _feature_dtype(Xk)
    _, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)

    def run(args):
        c, _ = args
        return _epoch_step(Xk, yk, wk, c, loss_func, lr, reg, elastic_net)

    def skip(args):
        return args

    carry, criteria = lax.cond(criteria > tol, run, skip, (carry, criteria))
    packed = jnp.stack([carry[3].astype(jnp.float32), criteria])
    return carry, criteria, packed


# Borrowing variant for epochs whose post-state must stay readable on host
# (checkpoint snapshot pending); donating variant ping-pongs the carry in
# place in HBM (carry and criteria are argnums 3 and 4).
_stream_epoch = lazy_jit(_stream_epoch_impl, static_argnames=("loss_func",))
_stream_epoch_donating = lazy_jit(
    _stream_epoch_impl, static_argnames=("loss_func",), donate_argnums=(3, 4)
)


@partial(lazy_jit, static_argnames=("d", "mat_sharding", "row_sharding"))
def _unpack_stream_batch(packed, d, mat_sharding, row_sharding):
    """Split the dtype-packed [X | y | w] stream batch back into its parts
    ON DEVICE, constrained to the training shardings. The pack exists so a
    cached stream batch is uploaded as ONE host→device transfer
    (three separate uploads each paid their own dispatch); slicing columns
    out of the uploaded buffer moves no bytes and is bit-exact."""
    X = lax.with_sharding_constraint(packed[:, :d], mat_sharding)
    y = lax.with_sharding_constraint(packed[:, d], row_sharding)
    w = lax.with_sharding_constraint(packed[:, d + 1], row_sharding)
    return X, y, w


def _sgd_chunk_impl(X_b, y_b, w_b, carry, criteria, loss_func, hyper, chunk_end, fleet_axis=None):
    """Up to `chunk_end - carry.epoch` host-driven epochs fused into ONE
    device program, for the checkpointed train loop: the tol check runs
    every epoch inside the while condition (same order as the per-epoch
    loop, so the stop epoch is identical for any chunk size), and the only
    readback is the packed [epoch, criteria] pair.

    `fleet_axis` is the name of the `vmap` axis a fleet program maps this
    loop over: the epoch that finds the batch is then the fleet's furthest
    (a maximum over the members, one number; over a fleet axis that is
    sharded, four bytes on the wire an epoch), which is every running
    member's own (the members start together and step together) and a
    stopped member's step is thrown away by the loop's select. Indexed by
    each member's own counter the batch is a gather of N copies of itself,
    4.8 GB an epoch for 100 members of the reference's batch, and the table
    is copied to feed it."""
    num_batches = y_b.shape[0]
    dtype = _feature_dtype(X_b)
    _, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)

    def cond(state):
        c, crit = state
        return jnp.logical_and(c[3] < chunk_end, crit > tol)

    def step(state):
        c, _ = state
        epoch = c[3] if fleet_axis is None else collectives.all_reduce_max(c[3], fleet_axis)
        k = jnp.mod(epoch, num_batches)
        Xk = _index_batch(X_b, k)
        yk = lax.dynamic_index_in_dim(y_b, k, axis=0, keepdims=False)
        wk = lax.dynamic_index_in_dim(w_b, k, axis=0, keepdims=False)
        return _epoch_step(Xk, yk, wk, c, loss_func, lr, reg, elastic_net)

    carry, criteria = lax.while_loop(cond, step, (carry, criteria))
    packed = jnp.stack([carry[3].astype(jnp.float32), criteria])
    return carry, criteria, packed


_sgd_chunk = lazy_jit(_sgd_chunk_impl, static_argnames=("loss_func",))
_sgd_chunk_donating = lazy_jit(
    _sgd_chunk_impl, static_argnames=("loss_func",), donate_argnums=(3, 4)
)


def _sgd_whole_fit_impl(X_b, y_b, w_b, carry, criteria, loss_func, hyper, pack_sharding):
    """The ENTIRE checkpointed fit as ONE resident program: the epoch loop
    to maxIter (per-epoch tol check inside the while condition — the exact
    `_sgd_chunk_impl` body with chunk_end = maxIter), the one-extra final
    model update, and the packed [coeff, criteria, epochs] result, so the
    fit is one dispatch and one packed readback. The carry is ALSO
    returned (device-resident) for the optional fit-end snapshot; the
    `optimization_barrier` pins the final update to the materialized loop
    carry, which is what makes the result bit-identical to the chunked
    path's host-side `_final_update` (XLA may not fuse the update into the
    loop epilogue and reassociate the last gradient application)."""
    dtype = _feature_dtype(X_b)
    max_iter, _, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
    carry, criteria, _ = _sgd_chunk_impl(
        X_b, y_b, w_b, carry, criteria, loss_func, hyper, max_iter
    )
    coeff, grad, wsum, epochs = lax.optimization_barrier(carry)
    final_coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    packed = _pack_train_result(final_coeff, criteria, epochs, None, pack_sharding)
    return carry, criteria, packed


_sgd_whole_fit = lazy_jit(
    _sgd_whole_fit_impl, static_argnames=("loss_func", "pack_sharding")
)


def _sgd_stream_whole_fit_impl(packed_all, carry, criteria, loss_func, hyper, d, pack_sharding):
    """The whole out-of-core fit as ONE resident program.

    The stacked [X | y | w] stream segments (nb, b_pad, d+2) are the
    in-program data source — the device epoch cache's contents as one
    HBM-resident array, staged once. Each epoch dynamic-slices its batch
    out of the stack and materializes the column views with an
    `optimization_barrier`, mirroring how the host-driven loop receives
    them from `_unpack_stream_batch` as standalone buffers — that plus
    reusing `_stream_epoch_impl` verbatim (including its criteria guard)
    makes every epoch bit-identical to the per-epoch dispatch pipeline;
    the final update is barrier-pinned exactly as in `_sgd_whole_fit_impl`.
    Returns (carry, criteria, packed [coeff, criteria, epochs])."""
    dtype = _feature_dtype(packed_all)
    max_iter, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
    nb = packed_all.shape[0]

    def cond(state):
        c, crit = state
        return jnp.logical_and(c[3] < max_iter, crit > tol)

    def step(state):
        c, crit = state
        k = jnp.mod(c[3], nb)
        batch = lax.dynamic_index_in_dim(packed_all, k, 0, False)
        Xk, yk, wk = lax.optimization_barrier(
            (batch[:, :d], batch[:, d], batch[:, d + 1])
        )
        c, crit, _ = _stream_epoch_impl(Xk, yk, wk, c, crit, loss_func, hyper)
        return c, crit

    carry, criteria = lax.while_loop(cond, step, (carry, criteria))
    coeff, grad, wsum, epochs = lax.optimization_barrier(carry)
    final_coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    packed = _pack_train_result(final_coeff, criteria, epochs, None, pack_sharding)
    return carry, criteria, packed


_sgd_stream_whole_fit = lazy_jit(
    _sgd_stream_whole_fit_impl, static_argnames=("loss_func", "d", "pack_sharding")
)


# ---------------------------------------------------------------------------
# fleet kernels: N whole fits as ONE vmapped resident program (fleet.py)
# ---------------------------------------------------------------------------
#
# The fleet programs vmap the member fit over a leading fleet axis: the
# batched data (X_b, y_b, w_b) is CLOSED OVER (in_axes=None — input bytes
# are paid once for N models) while the carry leaves, criteria, and the
# packed hyper vector ([N, 5] — every member carries its own
# maxIter/tol/lr/reg/elasticNet) batch over members. JAX's `while_loop`
# batching rule runs the loop until every member's condition is false and
# select-freezes finished members' carries — exactly the per-member
# convergence-mask contract, and (pinned by tests/test_fleet.py) each
# member's result is bit-identical to its solo fit on the same mesh, on the
# CPU. On a TPU the fleet hands these programs the loss's matrix-product
# form (`_fleet_multiplies`): the members' row-dots and gradients are then
# one float32 product each an epoch, and a member agrees with its solo fit
# to rounding.
#
# `lax.optimization_barrier` has NO batching rule, so the final-update
# barrier of `_sgd_whole_fit_impl` must be applied OUTSIDE the vmap, on
# the stacked carry: one barrier pins every member's loop carry at once,
# preserving the update-not-fused-into-the-loop-epilogue guarantee that
# makes whole-fit results match the chunked path's host-side
# `_final_update` bitwise.
#
# The member axis has a name, `FLEET_AXIS`, by which the vmapped loop finds
# its batch ONCE for all members (`_sgd_chunk_impl`, `fleet_axis`).

FLEET_AXIS = "fleet"


def _fleet_member_finish(carry, criteria, hyper, dtype, flag):
    """One member's post-loop tail: the one-extra model update + the
    per-member result row [flag?, coeff, criteria, epochs]. vmapped by the
    fleet kernels (no per-part pack_sharding here — the stacked
    [N, pack] result is constrained once, outside the vmap)."""
    _, _, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
    coeff, grad, wsum, epochs = carry
    final_coeff = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
    return _pack_train_result(final_coeff, criteria, epochs, flag)


def _sgd_fleet_whole_fit_impl(
    X_b, y_b, w_b, carry, criteria, loss_func, hyper, check_labels, pack_sharding
):
    """N ENTIRE fits as ONE resident program: every member runs
    `_sgd_chunk_impl` to its own maxIter (per-epoch tol check inside the
    vmapped while condition — identical stop epoch to its solo fit), the
    stacked carry is barrier-pinned, and the vmapped finish packs the
    [N, flag? + d + 2] result for a single fleet readback. The {0,1}
    label-validity flag is computed ONCE outside the vmap (labels are
    shared) and broadcast into every member's row."""
    dtype = _feature_dtype(X_b)

    def member_loop(c, crit, h):
        member_max_iter = _unpack_hyper(h, dtype)[0]
        c, crit, _ = _sgd_chunk_impl(
            X_b, y_b, w_b, c, crit, loss_func, h, member_max_iter, FLEET_AXIS
        )
        return c, crit

    carry, criteria = jax.vmap(member_loop, axis_name=FLEET_AXIS)(carry, criteria, hyper)
    carry = lax.optimization_barrier(carry)
    flag = _binomial_labels_ok(y_b) if check_labels else None

    def member_finish(c, crit, h):
        return _fleet_member_finish(c, crit, h, dtype, flag)

    packed = jax.vmap(member_finish)(carry, criteria, hyper)
    if pack_sharding is not None:
        packed = lax.with_sharding_constraint(packed, pack_sharding)
    return carry, criteria, packed


_sgd_fleet_whole_fit = lazy_jit(
    _sgd_fleet_whole_fit_impl,
    static_argnames=("loss_func", "check_labels", "pack_sharding"),
)


def _sgd_fleet_chunk_impl(X_b, y_b, w_b, carry, criteria, loss_func, hyper, chunk_end):
    """The fleet chunk for the checkpointed train loop: every member runs
    `_sgd_chunk_impl` to min(chunk_end, its own maxIter) — a member whose
    budget ends inside the chunk freezes there, matching its solo stop
    epoch for any chunk size. Returns (carry, criteria, packed [N, 2])
    where each row is the member's (epoch, criteria) drain pair."""
    dtype = _feature_dtype(X_b)

    def member(c, crit, h):
        member_end = jnp.minimum(
            jnp.asarray(chunk_end, jnp.int32), _unpack_hyper(h, dtype)[0]
        )
        return _sgd_chunk_impl(X_b, y_b, w_b, c, crit, loss_func, h, member_end, FLEET_AXIS)

    return jax.vmap(member, axis_name=FLEET_AXIS)(carry, criteria, hyper)


_sgd_fleet_chunk = lazy_jit(_sgd_fleet_chunk_impl, static_argnames=("loss_func",))


def _sgd_fleet_final_impl(carry, criteria, hyper, pack_sharding):
    """The fleet chunked path's finish as its own program (the dispatch
    boundary is the barrier here, exactly like the solo `_final_update`):
    vmapped one-extra update + result pack → [N, d + 2]."""
    dtype = carry[0].dtype

    def member(c, crit, h):
        return _fleet_member_finish(c, crit, h, dtype, None)

    packed = jax.vmap(member)(carry, criteria, hyper)
    if pack_sharding is not None:
        packed = lax.with_sharding_constraint(packed, pack_sharding)
    return packed


_sgd_fleet_final = lazy_jit(_sgd_fleet_final_impl, static_argnames=("pack_sharding",))


def _sgd_fleet_stream_whole_fit_impl(
    packed_all, carry, criteria, loss_func, hyper, d, pack_sharding
):
    """N out-of-core fits as ONE resident program over the SHARED stacked
    [X | y | w] segment array.

    Unlike the dense fleet kernel this one keeps a GLOBAL epoch counter
    and vmaps only the per-epoch member step: the in-loop
    `optimization_barrier` that materializes the batch's column views (the
    solo kernel's host-pipeline parity trick) has no batching rule, so the
    batch must be sliced from an UNBATCHED index. That is loss-free:
    members advance in lockstep while active (an active member's epoch
    counter always equals the global counter — all start at 0 and step
    once per outer iteration), and a stopped member's step is a `select`
    identity, so each member still sees exactly its solo batch sequence.
    Members past their own maxIter freeze via `lax.cond` (vmap lowers it
    to the convergence-mask select); `_stream_epoch_impl`'s criteria guard
    freezes tol-converged members exactly as on the solo path."""
    dtype = _feature_dtype(packed_all)
    nb = packed_all.shape[0]
    max_iters = hyper[:, 0].astype(jnp.int32)
    tols = hyper[:, 1]

    def cond(state):
        c, crit, _ = state
        return jnp.any(jnp.logical_and(c[3] < max_iters, crit > tols))

    def step(state):
        c, crit, e = state
        batch = lax.dynamic_index_in_dim(packed_all, jnp.mod(e, nb), 0, False)
        Xk, yk, wk = lax.optimization_barrier(
            (batch[:, :d], batch[:, d], batch[:, d + 1])
        )

        def member(cm, critm, h):
            member_max_iter = _unpack_hyper(h, dtype)[0]

            def run(args):
                c0, cr0 = args
                c1, cr1, _ = _stream_epoch_impl(
                    Xk, yk, wk, c0, cr0, loss_func, h
                )
                return c1, cr1

            return lax.cond(cm[3] < member_max_iter, run, lambda a: a, (cm, critm))

        c, crit = jax.vmap(member)(c, crit, hyper)
        return c, crit, e + 1

    carry, criteria, _ = lax.while_loop(
        cond, step, (carry, criteria, jnp.asarray(0, jnp.int32))
    )
    carry = lax.optimization_barrier(carry)

    def member_finish(c, crit, h):
        return _fleet_member_finish(c, crit, h, dtype, None)

    packed = jax.vmap(member_finish)(carry, criteria, hyper)
    if pack_sharding is not None:
        packed = lax.with_sharding_constraint(packed, pack_sharding)
    return carry, criteria, packed


_sgd_fleet_stream_whole_fit = lazy_jit(
    _sgd_fleet_stream_whole_fit_impl,
    static_argnames=("loss_func", "d", "pack_sharding"),
)


def _update_rows(coeff, grad, wsum, lr, reg, elastic_net):
    """`_update_model` of N members at once, their coefficients member-minor
    [d, N] and their numbers [N] along its last axis: the step, then
    `regularize`, for the members whose last batch had weight."""
    stepped, _ = regularize(coeff - (lr / jnp.maximum(wsum, 1e-30)) * grad, reg, elastic_net, lr)
    return jnp.where(wsum > 0, stepped, coeff)


def _sgd_fleet_rows_whole_fit_impl(
    X_b, y_b, w_b, loss_func, hyper, d, check_labels, pack_sharding, plan=None, dictionaries=None
):
    """N ENTIRE fits of a padded-CSR table as ONE resident program in the
    member-row form (`_fleet_rows`): the members' coefficients and
    gradients are held member-minor, [d, N], from zeros made here, so that
    an entry's N coefficients are ONE row (`losses.rows_variant`; with
    `plan`, `sparse_epoch.planned_rows_loss` over the fleet's
    `dictionaries`). The schedule is `_sgd_fleet_whole_fit_impl`'s written
    out for the member axis: every member's own maxIter and tol, the batch
    found once at the fleet's furthest epoch, a stopped member's state kept
    by a select, the barrier-pinned final update, and the same packed [N,
    flag? + d + 2] result. Returns the pack."""
    dtype = _feature_dtype(X_b)
    n = hyper.shape[0]
    max_iter, tol = hyper[:, 0].astype(jnp.int32), hyper[:, 1]
    lr, reg, elastic_net = (hyper[:, i].astype(dtype) for i in (2, 3, 4))
    num_batches = y_b.shape[0]
    loss = loss_func
    if plan is not None:
        loss = partial(sparse_epoch.planned_rows_loss(loss_func, plan), dictionaries=dictionaries)

    def running(state):
        (_, _, _, epochs), criteria = state
        return jnp.logical_and(epochs < max_iter, criteria > tol)

    def body(state):
        (coeff, grad, wsum, epochs), criteria = state
        on = running(state)
        k = jnp.mod(jnp.max(epochs), num_batches)
        Xk = _index_batch(X_b, k)
        yk = lax.dynamic_index_in_dim(y_b, k, axis=0, keepdims=False)
        wk = lax.dynamic_index_in_dim(w_b, k, axis=0, keepdims=False)
        stepped = _update_rows(coeff, grad, wsum, lr, reg, elastic_net)
        lsum, new_grad, new_wsum = loss(Xk, yk, wk, stepped)
        new_criteria = jnp.asarray(lsum / jnp.maximum(new_wsum, 1e-30), jnp.float32)
        carry = (
            jnp.where(on, stepped, coeff),
            jnp.where(on, new_grad, grad),
            jnp.where(on, new_wsum, wsum),
            jnp.where(on, epochs + 1, epochs),
        )
        return carry, jnp.where(on, new_criteria, criteria)

    start = (
        (jnp.zeros((d, n), dtype), jnp.zeros((d, n), dtype), jnp.zeros((n,), dtype), jnp.zeros((n,), jnp.int32)),
        jnp.full((n,), jnp.inf, jnp.float32),
    )
    carry, criteria = lax.while_loop(lambda state: jnp.any(running(state)), body, start)
    coeff, grad, wsum, epochs = lax.optimization_barrier(carry)
    final = _update_rows(coeff, grad, wsum, lr, reg, elastic_net)
    dt = jnp.promote_types(dtype, jnp.float32)
    parts = [final.T.astype(dt), criteria[:, None].astype(dt), epochs[:, None].astype(dt)]
    if check_labels:
        parts.insert(0, jnp.broadcast_to(_binomial_labels_ok(y_b).astype(dt), (n, 1)))
    packed = jnp.concatenate(parts, axis=1)
    if pack_sharding is not None:
        packed = lax.with_sharding_constraint(packed, pack_sharding)
    return packed


_sgd_fleet_rows_whole_fit = lazy_jit(
    _sgd_fleet_rows_whole_fit_impl,
    static_argnames=("loss_func", "d", "check_labels", "pack_sharding", "plan"),
)


def unpack_fleet_train_result(host: np.ndarray, d: int, has_flag: bool = False):
    """Host-side inverse of the fleet result pack ([N, flag? + d + 2] —
    `_fleet_member_finish` rows): returns (flags_or_None, coeff [N, d],
    criteria [N], epochs [N])."""
    host = np.asarray(host)
    off = 1 if has_flag else 0
    flags = host[:, 0] if has_flag else None
    return (
        flags,
        host[:, off : off + d],
        host[:, -2],
        host[:, -1].astype(np.int64),
    )


def unpack_train_result(host: np.ndarray, d: int, has_flag: bool = False):
    """Host-side inverse of `_pack_train_result`: returns
    (flag_or_None, coeff[:d], criteria, epochs)."""
    flag = float(host[0]) if has_flag else None
    off = 1 if has_flag else 0
    return flag, host[off : off + d], float(host[-2]), int(host[-1])


def read_train_result(async_result):
    """Materialize an `optimize_async` result on the host in one transfer.
    Returns (flag_or_None, coeff[:d], criteria, epochs); the checkpointed
    host-driven path passes its host values through unchanged."""
    if async_result[0] == "host":  # checkpointed host-driven path
        _, coeff, criteria, epochs, flag, d = async_result
        # tpulint: disable=host-sync-leak -- host-driven branch: coeff is already host numpy here, the copy is free
        return flag, np.asarray(coeff)[:d], criteria, epochs
    if async_result[0] == "packed2d":  # 2D (data × model) whole-fit path
        from ..parallel.sgd2d import sgd2d_unpack_host

        _, packed, d, has_flag, nm, d_local = async_result
        # ONE device_get of the model-sharded pack (per-shard block =
        # [flag?, coeff_slice, criteria, epochs]) — no device hops a full
        # replicated result vector, matching the sharded residency story
        coeff, criteria, epochs, flag = sgd2d_unpack_host(
            _read_packed(packed), nm, d_local, has_flag
        )
        return flag, coeff[:d], criteria, epochs
    _, packed, d, has_flag = async_result
    return unpack_train_result(_read_packed(packed), d, has_flag=has_flag)


def _read_packed(packed) -> np.ndarray:
    """The fit's one packed readback, as the `fit.readback` phase around the
    funnel's sync of kind `fit`: the wait for the train program apart from
    the result's copy (an explicit device_get, because the transfer-guard
    readback-budget tests run fits under jax.transfer_guard("disallow") to
    catch stray implicit pulls). On a timeline the two steps are the
    readback lane's events; the phase marks no host lane."""
    with tracing.phase("fit.readback", marks=False):
        return tracing.sync("fit", packed)


@dataclass
class SGD:
    """Parallel mini-batch SGD (common/optimizer/SGD.java).

    With `checkpoint_dir` set, training runs one jitted epoch per host step
    and snapshots (coeff, grad, wsum, epoch, criteria) at epoch boundaries
    (`checkpoint_interval`), resuming from the snapshot if one exists — the
    synchronous-SPMD simplification of the reference's feedback-edge
    checkpointing (SURVEY.md §5: epoch boundary = consistent state)."""

    max_iter: int = 20
    learning_rate: float = 0.1
    global_batch_size: int = 32
    tol: float = 1e-6
    reg: float = 0.0
    elastic_net: float = 0.0
    dtype: jnp.dtype = jnp.float32
    checkpoint_dir: Optional[str] = None
    checkpoint_interval: int = 1
    checkpoint_key: Optional[str] = None
    """Job-identity namespace for the checkpoint file (see
    iteration.checkpoint_job_key) — estimator-level callers set it so jobs
    sharing a checkpoint dir cannot cross-restore; None keeps the legacy
    un-namespaced `ckpt.npz` for direct SGD users."""
    shard_features: bool = False
    """Also shard the feature dimension over the mesh `model` axis — the
    tensor-parallel layout for wide (e.g. sparse-Criteo-dim) models
    (SURVEY.md §2.3: feature-sharded linear training as the TP analogue).
    The X@coeff contraction then all-reduces over `model` while the
    gradient contraction all-reduces over `data`; both ride ICI."""

    def _use_2d(self, mesh: Mesh, loss_func: LossFunc) -> bool:
        """Route this fit through the explicit 2D (data × model) programs
        (parallel/sgd2d.py)? Requires a feature-sharded SPARSE
        fit on a mesh that actually has a model axis; `config.sparse_2d`
        = "off" keeps the GSPMD 1D program — the replicated-residency
        reference the 2D parity tests compare against. A 1-shard model
        axis still routes 2D (the axis collectives are identity-sized),
        which is what makes single-feature-shard bit-parity testable."""
        from .. import config

        return (
            self.shard_features
            and loss_func.sparse
            and config.sparse_2d == "auto"
            and mesh_lib.MODEL_AXIS in mesh.axis_names
        )

    def _stage_2d_grad(self, mesh: Mesh, d: int):
        """The zero gradient carry staged DIRECTLY as model-axis slices:
        the optimizer state's (d,) leaves must never materialize
        replicated on a beyond-HBM dim — staging through the admission
        funnel also ledgers d/nm per-device bytes under `optimizer`."""
        return h2d.stage_to_device(
            np.zeros((d,), self.dtype),
            mesh_lib.model_sharding(mesh),
            category="optimizer",
        )

    def _device_init(self, init_coeff):
        """The start coefficient as a device array of the engine's dtype. One
        that is there already stays there (a wide model's zeros are made on
        the device: uploaded from the host, 135 MB of them held a one-hot fit's
        train program back 150 ms, a sixth of the fit); a host one is cast and uploaded."""
        if isinstance(init_coeff, jax.Array):
            return init_coeff if init_coeff.dtype == self.dtype else init_coeff.astype(self.dtype)
        return jnp.asarray(np.asarray(init_coeff, self.dtype))

    def _hyper(self) -> np.ndarray:
        """The packed f32 hyper-parameter vector every kernel consumes —
        ONE host→device upload per dispatch instead of five scalars (see
        `_unpack_hyper`). max_iter stays f32-exact below 2^24 epochs."""
        return np.asarray(
            [self.max_iter, self.tol, self.learning_rate, self.reg, self.elastic_net],
            np.float32,
        )

    @staticmethod
    def _pack_sharding(mesh: Mesh):
        """Replicated pack layout for multi-axis meshes (see
        `_pack_train_result` on the GSPMD concatenate partial-sum bug);
        single-axis meshes need no constraint."""
        if len(mesh.axis_names) > 1:
            return NamedSharding(mesh, P())
        return None

    def optimize(
        self,
        init_coeff: np.ndarray,
        X: np.ndarray,
        y: np.ndarray,
        weights: Optional[np.ndarray],
        loss_func: LossFunc,
        mesh: Optional[Mesh] = None,
    ) -> Tuple[np.ndarray, float, int]:
        """Returns (final_coefficient, final_loss, num_epochs)."""
        result = self.optimize_async(init_coeff, X, y, weights, loss_func, mesh)
        _, coeff, criteria, epochs = read_train_result(result)
        return coeff, criteria, epochs

    def optimize_async(
        self,
        init_coeff: np.ndarray,
        X: np.ndarray,
        y: np.ndarray,
        weights: Optional[np.ndarray],
        loss_func: LossFunc,
        mesh: Optional[Mesh] = None,
        validate_labels: bool = False,
    ):
        """Dispatch the full training program WITHOUT reading results back.

        Returns an opaque async handle for `read_train_result`: on the
        fused paths a ("packed", device_vector, true_dim, has_flag) tuple
        whose single device array carries [flag?, coeff, criteria, epochs]
        (ONE readback materializes everything; every separate readback
        is another blocking round trip). With
        `validate_labels` the {0,1} binomial-label check is computed inside
        the training program and rides the same transfer. The checkpointed
        path is host-driven in epoch chunks and returns host values
        directly as ("host", coeff, criteria, epochs, flag, true_dim)."""
        # the host's work up to the launch is the `fit.stage` phase (the
        # batch layout inside it is `fit.layout`); the launch that follows
        # is `fit.launch`, in dispatch.timed_dispatch
        with tracing.phase("fit.stage"):
            launch = self._stage_async(
                init_coeff, X, y, weights, loss_func, mesh, validate_labels
            )
        return launch()

    def _stage_async(self, init_coeff, X, y, weights, loss_func, mesh, validate_labels):
        """`optimize_async` up to the launch: picks the route, stages its
        inputs, and returns the launch itself as a call without arguments
        that gives the async handle."""
        mesh = mesh or mesh_lib.default_mesh()
        # the model length is the feature dim — X may be sparse (indices,
        # values), whose second axis is the nnz width, not the dim
        d = int(np.shape(init_coeff)[0])
        from ..parallel import dispatch

        # the in-memory routes below are whole-fit programs whatever the
        # mode says (one dispatch, one packed readback); they count toward
        # `dispatch.whole_fit` only when it is on, so that a process that
        # turned the mode off counts no whole fit
        if dispatch.whole_fit_enabled() and self.checkpoint_dir is None:
            dispatch.account_whole_fit("sgd")
        if (
            not self.shard_features
            and self.checkpoint_dir is None
            and mesh_lib.num_data_shards(mesh) == 1
        ):
            launch = self._stage_flat(
                mesh, init_coeff, X, y, weights, loss_func, validate_labels
            )
            return lambda: ("packed", launch(), d, validate_labels)
        if (
            not self.shard_features
            and self.checkpoint_dir is None
            and _can_walk(X, y, weights, int(self.global_batch_size), self.max_iter, self.dtype, mesh)
        ):
            launch = self._stage_walk(
                mesh, init_coeff, X, y, weights, loss_func, validate_labels
            )
            return lambda: ("packed", launch(), d, validate_labels)
        if self.shard_features:
            # zero-pad the feature dim to divide over the model axis; padded
            # coefficients start 0, get zero gradients, and stay 0
            model_shards = int(mesh.shape.get(mesh_lib.MODEL_AXIS, 1))
            d_pad = -(-d // model_shards) * model_shards
            if d_pad != d:
                init_coeff = np.pad(np.asarray(init_coeff), (0, d_pad - d))
        else:
            d_pad = None
        X_b, y_b, w_b = self._batchify(mesh, X, y, weights, d_pad)
        if self.shard_features:
            init = h2d.stage_to_device(
                np.asarray(init_coeff, self.dtype), mesh_lib.model_sharding(mesh), category="optimizer"
            )
        else:
            init = self._device_init(init_coeff)
        if self.checkpoint_dir is not None:

            def host_driven():
                coeff, criteria, epochs = self._optimize_with_checkpoints(
                    X_b, y_b, w_b, init, loss_func, mesh
                )
                flag = None
                if validate_labels:
                    flag = float(jax.device_get(_binomial_labels_ok(y_b)))
                return ("host", coeff, criteria, epochs, flag, d)

            return host_driven
        if self._use_2d(mesh, loss_func) and isinstance(X_b, tuple):
            from ..parallel import sgd2d

            carry = (
                jnp.asarray(init, self.dtype),
                self._stage_2d_grad(mesh, d_pad),
                jnp.asarray(0.0, self.dtype),
                jnp.asarray(0, jnp.int32),
            )
            launch = partial(
                dispatch.timed_dispatch,
                sgd2d.sgd2d_whole_fit,
                mesh, X_b, y_b, w_b, carry,
                jnp.asarray(np.inf, jnp.float32),
                loss_func, self._hyper(), validate_labels,
                start=0, end=self.max_iter,
            )
            nm = mesh_lib.num_model_shards(mesh)
            return lambda: (
                "packed2d", launch()[2], d, validate_labels, nm, d_pad // nm
            )
        launch = partial(
            dispatch.timed_dispatch,
            _sgd_train,
            X_b,
            y_b,
            w_b,
            jnp.asarray(init, self.dtype),
            loss_func,
            self._hyper(),
            validate_labels,
            self._pack_sharding(mesh),
            start=0, end=self.max_iter,
        )
        return lambda: ("packed", launch(), d, validate_labels)

    def optimize_stream(
        self,
        init_coeff: Optional[np.ndarray],
        chunks,
        loss_func: LossFunc,
        mesh: Optional[Mesh] = None,
        memory_budget_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
    ):
        """Out-of-core SGD over a one-shot stream of (X, y, w) host chunks.

        The cache-then-replay contract of the reference's ReplayOperator
        (flink-ml-iteration/.../operator/ReplayOperator.java:125-246) +
        spillable DataCache (datacache/nonkeyed/DataCacheWriter.java): the
        single pass over the stream re-chunks rows into globalBatchSize
        batches, packs each as ONE [X | y | w] segment, and appends it to
        the native spillable cache; every epoch then replays its batch
        from the cache THROUGH the device epoch cache
        (data/devicecache.py): within `config.device_cache_bytes` a batch
        uploads once — a single dtype-packed transfer straight into its
        data-parallel sharded layout — and later epochs read the
        device-resident shards back with zero H2D bytes. Over-budget
        batches stay in the host cache and re-stage on access (budget 0 =
        the eager re-upload path; any budget is bit-identical), so
        datasets larger than device memory (and, with spill, larger than
        the host memory budget) train fine.

        Batch schedule and padding match `optimize` exactly, so a stream
        fit produces the same coefficients as an in-memory fit of the
        concatenated stream. Returns (final_coefficient, final_loss,
        num_epochs, cache_stats)."""
        from .. import config
        from ..native.datacache import DataCache

        if self.shard_features:
            raise NotImplementedError(
                "feature-sharded (tensor-parallel) training requires the "
                "in-memory path; stream mode is data-parallel only"
            )
        mesh = mesh or mesh_lib.default_mesh()
        B = int(self.global_batch_size)
        shards = mesh_lib.num_data_shards(mesh)
        b_pad = -(-B // shards) * shards
        cache = DataCache(
            memory_budget_bytes
            if memory_budget_bytes is not None
            else config.datacache_memory_budget_bytes,
            spill_dir if spill_dir is not None else config.datacache_spill_dir,
        )
        segs = []  # per batch: one packed [X | y | w] segment id
        pend = None  # carried remainder rows (X, y, w)
        d = None

        def emit(Xb, yb, wb):
            """Pad a B-row batch to b_pad with weight-0 rows and cache it
            as ONE packed (b_pad, d+2) segment — the layout the staging
            path uploads in a single transfer (`_unpack_stream_batch`)."""
            if b_pad != Xb.shape[0]:
                extra = b_pad - Xb.shape[0]
                Xb = np.pad(Xb, [(0, extra), (0, 0)])
                yb = np.pad(yb, (0, extra))
                wb = np.pad(wb, (0, extra))
            packed = np.concatenate([Xb, yb[:, None], wb[:, None]], axis=1)
            segs.append(cache.append_array(np.ascontiguousarray(packed)))

        # Resume WITHOUT re-ingest (docs/fault_tolerance.md "Multi-host
        # snapshots"): a sharded snapshot carries the stream cache's
        # CONTENTS as a stable `cache` section — the packed segments are
        # rebuilt straight from the snapshot shards and the input stream
        # is never consumed (the epoch cache's data source survives the
        # preemption, not just its cursor).
        restored_segs = None
        if self.checkpoint_dir is not None and config.snapshot_cache_contents:
            from ..ckpt import snapshot as _snapshot
            from ..data.devicecache import restore_cache_contents

            peek = _snapshot.load_job_snapshot(
                self.checkpoint_dir,
                self.checkpoint_key,
                expect_meta={"globalBatchSize": int(self.global_batch_size)},
            )
            if peek is not None and "dim" in peek.meta:
                restored_segs = restore_cache_contents(peek, cache)
                if restored_segs is not None:
                    d = int(peek.meta["dim"])
        if restored_segs is not None:
            segs = restored_segs
        else:
            for chunk in chunks:
                X, y, w = chunk
                X = np.asarray(X, self.dtype)
                y = np.asarray(y, self.dtype)
                w = (
                    np.ones(X.shape[0], self.dtype)
                    if w is None
                    else np.asarray(w, self.dtype)
                )
                d = X.shape[1] if d is None else d
                if pend is not None:
                    X = np.concatenate([pend[0], X])
                    y = np.concatenate([pend[1], y])
                    w = np.concatenate([pend[2], w])
                    pend = None
                off = 0
                while X.shape[0] - off >= B:
                    emit(X[off : off + B], y[off : off + B], w[off : off + B])
                    off += B
                if off < X.shape[0]:
                    pend = (X[off:], y[off:], w[off:])
            if pend is not None:
                Xr, yr, wr = pend
                extra = B - Xr.shape[0]
                emit(
                    np.pad(Xr, [(0, extra), (0, 0)]),
                    np.pad(yr, (0, extra)),
                    np.pad(wr, (0, extra)),
                )
        if not segs:
            raise ValueError("optimize_stream received an empty stream")
        if init_coeff is None:
            init_coeff = np.zeros(d, self.dtype)

        row_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
        mat_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS, None))
        hyper = self._hyper()
        nb = len(segs)
        carry = (
            jnp.asarray(init_coeff, self.dtype),
            jnp.zeros((d,), self.dtype),
            jnp.asarray(0.0, self.dtype),
            jnp.asarray(0, jnp.int32),
        )
        epoch, criteria = 0, float("inf")
        # segment count + batch size pin the epoch→segment mapping; a
        # snapshot written against a different stream layout is refused
        # (`dim` rides along so a cache-contents resume can rebuild its
        # carry templates before touching any data)
        ckpt_meta = {
            "numSegments": nb,
            "globalBatchSize": int(self.global_batch_size),
            "dim": int(d),
        }
        # Cache CONTENTS as a stable snapshot section (sharded path only):
        # captured eagerly, BEFORE the epoch loader's pump worker exists —
        # the native cache is serial-access, so saves inside the training
        # loop must close over these arrays instead of re-reading it. The
        # coordinator writes the section ONCE per job key and reuses it by
        # reference across cuts.
        stable_sections = None
        stable_specs = {}
        if (
            self.checkpoint_dir is not None
            and config.snapshot_hosts is not None
            and config.snapshot_cache_contents
        ):
            from ..data.devicecache import cache_contents_section

            contents = cache_contents_section(cache, segs)
            stable_sections = {"cache": lambda: contents}
            stable_specs = {"cache": "data"}
        if self.checkpoint_dir is not None:
            from ..ckpt import snapshot as _snapshot

            snap = _snapshot.load_job_snapshot(
                self.checkpoint_dir,
                self.checkpoint_key,
                templates={"model": carry},
                expect_meta=ckpt_meta,
            )
            if snap is not None:
                carry = _snapshot.stage_section(snap, "model", mesh=mesh)
                epoch, criteria = snap.epoch, snap.criteria

        # Input pipeline (data/devicecache.py + parallel/prefetch.py): the
        # device epoch cache serves replayed batches straight from HBM
        # (epoch 0 uploads each batch once, later epochs move zero H2D
        # bytes within budget), and misses are staged by the shared
        # single-worker prefetcher — cache read + pack-upload of batch
        # b+1 ride under batch b's compute (native cache access stays
        # serial; the overlap the reference gets from DataCacheReader on
        # Flink's async mailbox). On top of that, the convergence scalar
        # is drained through a bounded-depth queue instead of a per-epoch
        # float() sync: dispatched epochs past the tol-fire point are
        # criteria-guarded identity programs, so the stop epoch and
        # coefficients are exact (see _stream_epoch_impl).
        from .. import config
        from ..ckpt import faults
        from ..data.devicecache import CachedEpochLoader
        from ..parallel import dispatch
        from ..utils.packing import packed_device_get

        def fetch(k):
            packed_dev = h2d.stage_to_device(cache.read_array(segs[k]), mat_sharding)
            return _unpack_stream_batch(packed_dev, d, mat_sharding, row_sharding)

        interval = max(1, int(self.checkpoint_interval))

        # Whole-fit resident program (config.whole_fit): stage the cached
        # stream segments ONCE as a stacked HBM-resident (nb, b_pad, d+2)
        # array — the device epoch cache's contents as the in-program data
        # source — and run the entire fit as one dispatch + one packed
        # readback. Falls back to the per-epoch dispatch pipeline when a
        # checkpoint boundary lands mid-fit or the stack exceeds the
        # device-cache budget (reason-counted fallbacks).
        take_whole, _ = dispatch.whole_fit_plan(
            start_epoch=epoch,
            max_iter=self.max_iter,
            checkpoint_interval=interval if self.checkpoint_dir is not None else None,
            data_bytes=nb * b_pad * (d + 2) * np.dtype(self.dtype).itemsize,
        )
        if take_whole and cache.spilled_segments > 0:
            # the host cache already spilled: the data is demonstrably
            # out-of-core scale, so the transient host-side stack (and
            # the HBM-resident copy) must not be attempted
            dispatch.account_whole_fit_fallback("device_cache_budget")
            take_whole = False
        if take_whole:
            try:
                return self._stream_whole_fit(
                    cache, segs, carry, epoch, criteria, loss_func, hyper,
                    mesh, d, b_pad, interval, ckpt_meta,
                    stable_sections, stable_specs,
                )
            finally:
                cache.close()

        donate_ok = dispatch.supports_donation()
        queue = dispatch.DrainQueue(config.iteration_dispatch_depth)
        crit_dev = jnp.asarray(criteria, jnp.float32)
        final_epoch, final_crit = epoch, criteria
        stopped = criteria <= self.tol

        def handle(drained):
            nonlocal final_epoch, final_crit, stopped
            for entry, e_act, crit in drained:
                advanced = e_act > final_epoch
                final_epoch, final_crit = e_act, crit
                if (
                    advanced
                    and self.checkpoint_dir is not None
                    and e_act == entry.end
                    and e_act % interval == 0
                ):
                    from ..ckpt import snapshot as _snapshot

                    _snapshot.save_job_snapshot(
                        self.checkpoint_dir,
                        self.checkpoint_key,
                        {"model": entry.carry},
                        epoch=e_act,
                        criteria=crit,
                        specs=stable_specs or None,
                        # the device-epoch-cache key cursor: the segment
                        # the next epoch after this snapshot replays
                        meta={**ckpt_meta, "cacheCursor": e_act % nb},
                        stable_sections=stable_sections,
                    )
                if crit <= self.tol:
                    stopped = True
                faults.tick("epoch")

        loader = CachedEpochLoader(fetch)
        batch_iter = loader.epoch(p % nb for p in range(epoch, self.max_iter))
        try:
            planned = epoch
            donate_next = False
            while planned < self.max_iter and not stopped:
                with tracing.span("iteration.epoch", epoch=planned, mode="stream"):
                    batch_dev = next(batch_iter)
                    retain = (
                        self.checkpoint_dir is not None
                        and (planned + 1) % interval == 0
                    )
                    step = (
                        _stream_epoch_donating
                        if (donate_next and donate_ok)
                        else _stream_epoch
                    )
                    carry, crit_dev, packed = dispatch.timed_dispatch(
                        step, *batch_dev, carry, crit_dev, loss_func, hyper,
                        start=planned, end=planned + 1,
                    )
                handle(
                    queue.push(
                        dispatch.InFlight(
                            planned, planned + 1, carry if retain else None, packed
                        )
                    )
                )
                planned += 1
                donate_next = not retain
            handle(queue.drain_all())
            coeff, grad, wsum, _ = carry
            coeff = _final_update(
                coeff, grad, wsum,
                jnp.asarray(self.learning_rate, self.dtype),
                jnp.asarray(self.reg, self.dtype),
                jnp.asarray(self.elastic_net, self.dtype),
            )
            (coeff_h,) = packed_device_get(coeff, sync_kind="fit")
            stats = {
                "numSegments": cache.num_segments,
                "spilledSegments": cache.spilled_segments,
                "memoryUsedBytes": cache.memory_used,
                "deviceCache": loader.cache.stats,
            }
        finally:
            batch_iter.close()  # cancels speculative staging, stops the worker
            cache.close()
        return np.asarray(coeff_h), final_crit, final_epoch, stats

    def _stream_whole_fit(
        self, cache, segs, carry, start_epoch, criteria, loss_func, hyper,
        mesh, d, b_pad, interval, ckpt_meta,
        stable_sections=None, stable_specs=None,
    ):
        """Whole-fit arm of `optimize_stream` (see the call site): one
        stacked upload, one resident program (`_sgd_stream_whole_fit`),
        one packed readback — plus the fit-end snapshot when the cadence
        lands exactly on maxIter. Bit-identical to the per-epoch path by
        construction (pinned in tests/test_dispatch_pipeline.py)."""
        from .. import config
        from ..ckpt import faults
        from ..parallel import dispatch
        from ..utils.packing import packed_device_get

        nb = len(segs)
        stacked_sharding = NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS, None))
        stacked = np.empty((nb, b_pad, d + 2), np.dtype(self.dtype))
        for k, seg in enumerate(segs):
            stacked[k] = cache.read_array(seg)
        packed_all = h2d.stage_to_device(
            stacked, stacked_sharding, category="streamSegments"
        )
        dispatch.account_whole_fit("stream")
        with tracing.span(
            "iteration.run", mode="whole_fit", epochs=self.max_iter
        ):
            carry, _, packed = dispatch.timed_dispatch(
                _sgd_stream_whole_fit,
                packed_all, carry, jnp.asarray(criteria, jnp.float32),
                loss_func, hyper, d, self._pack_sharding(mesh),
                start=start_epoch, end=self.max_iter,
            )
            (host,) = packed_device_get(packed, sync_kind="fit")
            _, coeff_h, final_crit, final_epoch = unpack_train_result(
                np.asarray(host), d
            )
            if (
                self.checkpoint_dir is not None
                and final_epoch > start_epoch
                and final_epoch % interval == 0
            ):
                from ..ckpt import snapshot as _snapshot

                _snapshot.save_job_snapshot(
                    self.checkpoint_dir,
                    self.checkpoint_key,
                    {"model": carry},
                    epoch=final_epoch,
                    criteria=final_crit,
                    specs=stable_specs or None,
                    meta={**ckpt_meta, "cacheCursor": final_epoch % nb},
                    stable_sections=stable_sections,
                )
            faults.tick("epoch")  # one drained readback = one tick
        stats = {
            "numSegments": cache.num_segments,
            "spilledSegments": cache.spilled_segments,
            "memoryUsedBytes": cache.memory_used,
            "deviceCache": {
                "entries": nb,
                "residentBytes": int(packed_all.nbytes),
                "budgetBytes": (
                    -1
                    if config.device_cache_bytes is None
                    else config.device_cache_bytes
                ),
            },
            "wholeFit": True,
        }
        return np.asarray(coeff_h), final_crit, final_epoch, stats

    def _stage_flat(self, mesh, init_coeff, X, y, weights, loss_func, validate_labels):
        """Single-data-shard staging: no batched re-layout, no weights
        synthesis program — see `_sgd_train_flat`. Ragged row counts are
        padded to a batch multiple (the only case that copies). Host inputs
        are placed on the mesh's device (a 1-device mesh may deliberately
        pin a fit to a non-default chip); already-device-resident inputs
        stay where they are. A sparse table is planned here, once a fit,
        where `sparse_epoch.plan_fit` admits it, over the rows the fit's
        `max_iter` epochs reach. The small inputs run no device program
        here: the row count, an absent weight column's placeholder and a
        host start coefficient go up as host values with the launch, as the
        walk's first leg does (placed first, each is an eager program and
        about 0.5 ms of an idle chip on a v5e host); a start that lies on
        the device stays there. Returns the launch, a call
        without arguments that gives the packed result device vector."""
        n = int(np.shape(X[0] if isinstance(X, tuple) else X)[0])
        B = int(self.global_batch_size)
        num_batches = max(1, -(-n // B))
        n_pad = num_batches * B

        def stage(arr, dtype=None):
            if arr is None:
                return None
            dtype = dtype or self.dtype
            if isinstance(arr, jax.Array):
                return arr.astype(dtype) if arr.dtype != dtype else arr
            arr = np.asarray(arr)
            return h2d.stage_to_device(
                arr.astype(dtype) if arr.dtype != dtype else arr,
                mesh_lib.data_sharding(mesh, arr.ndim),
            )

        if isinstance(X, tuple):
            # sparse padded-CSR: indices keep their integer dtype; padding
            # rows get index -1 (masked in the sparse losses)
            X_f = (stage(X[0], np.int32), stage(X[1]))
        else:
            X_f = stage(X)
        y_f, w_f = stage(y), stage(weights)
        if y_f is None:
            y_f = jnp.zeros((n,), self.dtype)
        if n_pad != n:
            if isinstance(X_f, tuple):
                X_f = (
                    jnp.pad(X_f[0], [(0, n_pad - n), (0, 0)], constant_values=-1),
                    jnp.pad(X_f[1], [(0, n_pad - n), (0, 0)]),
                )
            else:
                X_f = jnp.pad(X_f, [(0, n_pad - n), (0, 0)])
            y_f = jnp.pad(y_f, (0, n_pad - n))
            if w_f is not None:
                w_f = jnp.pad(w_f, (0, n_pad - n))
        has_weights = w_f is not None
        if not has_weights:
            w_f = np.zeros((0,), self.dtype)
        if isinstance(init_coeff, jax.Array):
            init = self._device_init(init_coeff)
        else:
            init = np.asarray(init_coeff, self.dtype)
            metrics.inc_counter("fit.stage.launch_inputs")
        # the flat staged (or padded) arrays are this fit's training-data
        # residency — ledger them like the batched layouts in _batchify
        from ..obs import memledger

        memledger.track((X_f, y_f, w_f), "streamSegments")
        from ..parallel import dispatch

        one_pass = _can_one_pass(X_f, loss_func, mesh)
        plan = dictionaries = None
        if isinstance(X_f, tuple):
            plan, dictionaries = sparse_epoch.plan_fit(X_f, loss_func, mesh, B, self.max_iter)
        else:
            metrics.inc_counter("dense_epoch.one_pass" if one_pass else "dense_epoch.reduce")
        return partial(
            dispatch.timed_dispatch,
            _sgd_train_flat,
            X_f,
            y_f,
            w_f,
            init,
            loss_func,
            B,
            has_weights,
            np.int32(n),
            self._hyper(),
            validate_labels,
            one_pass,
            # the kernel's own code, interpreted, for a table that lies
            # anywhere else (a test that says `on_tpu` of a CPU array)
            one_pass and next(iter(X_f.devices())).platform != "tpu",
            plan,
            dictionaries,
            start=0, end=self.max_iter,
        )

    def _stage_walk(self, mesh, init_coeff, X, y, weights, loss_func, validate_labels):
        """The walk (`_can_walk` admits the fit): the epochs fall into legs,
        runs whose batches lie on one shard, and leg by leg the one-shard
        flat program runs over that shard's share where it lies
        (`addressable_shards[i].data`: no copy, no layout, no collective)
        from the end state of the leg before, which reaches the share's
        device as one vector of 2 * dim + 4 numbers by a device-to-device put.
        `_finish_walk` makes the fit's result of the last leg's, on its device.
        Every leg is enqueued before anything is read, inside one
        `fit.launch`: one blocking read a fit, as on every whole-fit route. A
        fit that `tol` stops early runs its later legs for no epoch. Where
        the labels are checked in the program every shard has a leg, those
        the epochs do not reach for no epoch: no label goes unseen, on this
        route as on the others. One chip works at a time; SGD's epochs are
        sequential whatever the layout, and what the batched route buys with
        every chip on every batch costs a layout of the whole table first.
        Each share is a one-shard table to `_can_one_pass` (the shares are
        one shape on one kind of device: the first is asked), so on the TPU
        a leg's epochs are the one-read kernel's. Returns the launch, a call
        without arguments that gives the packed result device vector."""
        B = int(self.global_batch_size)
        has_weights = weights is not None
        columns = [X, y, weights] if has_weights else [X, y]

        def first_row(shard):
            return shard.index[0].start or 0

        # a share: its piece of every column, in row order
        shares = [
            [shard.data for shard in share]
            for share in zip(*(sorted(col.addressable_shards, key=first_row) for col in columns))
        ]
        share_rows = X.shape[0] // len(shares)
        reached = max(1, -(-self.max_iter * B // share_rows))  # the shares the epochs reach
        legs = shares if validate_labels else shares[:reached]
        device = legs[0][0].device
        one_pass = _can_one_pass(legs[0][0], loss_func, mesh_lib.create_mesh(devices=[device]))
        interpret = one_pass and device.platform != "tpu"
        # the table is the caller's and stays where it is: ledgered as the
        # fit's training-data residency, like the flat route's
        from ..obs import memledger

        memledger.track(columns, "streamSegments")
        from ..parallel import dispatch

        metrics.inc_counter("layout.walk")
        metrics.inc_counter("layout.walk.legs", len(legs))
        metrics.inc_counter("dense_epoch.one_pass" if one_pass else "dense_epoch.reduce")
        dtype = np.dtype(self.dtype)
        no_weights = [] if has_weights else [np.zeros((0,), dtype)]
        first = _first_leg_carry(init_coeff, dtype)
        hyper = self._hyper()

        def walk():
            carry = first
            for i, share in enumerate(legs):
                leg_hyper = hyper.copy()
                leg_hyper[0] = min(self.max_iter, (i + 1) * (share_rows // B))
                # chip to chip: the stager places it and counts no upload. The
                # first leg's goes up with its launch: placed first like the
                # others it saves jax a second lowering (0.17 s of set-up) and
                # costs every fit 0.3 ms before its first leg starts
                if i:
                    carry = h2d.stage_to_device(carry, share[0].device)
                carry = _sgd_train_flat(
                    *share,
                    *no_weights,
                    None,
                    loss_func,
                    B,
                    has_weights,
                    np.int32(share_rows),
                    leg_hyper,
                    validate_labels,
                    one_pass,
                    interpret,
                    carry=carry,
                )
            return _finish_walk(carry, hyper, dtype, validate_labels)

        return partial(dispatch.timed_dispatch, walk, start=0, end=self.max_iter)

    def _optimize_with_checkpoints(self, X_b, y_b, w_b, init_coeff, loss_func, mesh):
        """Checkpointed training as a pipeline of epoch CHUNKS: K epochs
        per device program (chunk ends clamp to checkpoint boundaries so
        the snapshot cadence is exact), one packed (epoch, criteria)
        readback per chunk, and up to `config.iteration_dispatch_depth`
        chunks in flight before the oldest is drained. The per-epoch tol
        check runs inside each chunk's while condition, so the stop epoch
        and coefficients match the old one-epoch-per-dispatch loop exactly;
        chunks dispatched past the tol-fire epoch are identity programs.
        Carries of non-boundary chunks are donated (HBM ping-pong).

        Snapshots ride the JobSnapshot format (ckpt/snapshot.py): the
        carry section is tagged with its sharding specs, so a resume may
        land on a mesh of a DIFFERENT device count and `stage_section`
        re-shards the restored leaves onto it (elastic shrink/grow); the
        batch schedule (`numBatches`, `globalBatchSize`) rides in meta so
        a snapshot from a different data layout is refused, because the
        epoch→batch mapping would silently diverge."""
        from .. import config
        from ..ckpt import faults
        from ..ckpt import snapshot as _snapshot
        from ..parallel import dispatch
        from ..utils.packing import packed_device_get

        d = init_coeff.shape[0]  # X_b may be the sparse (indices, values) tuple
        nb = int(y_b.shape[0])
        hyper = self._hyper()
        use_2d = self._use_2d(mesh, loss_func) and isinstance(X_b, tuple)
        if use_2d:
            from ..parallel import sgd2d
        carry = (
            jnp.asarray(init_coeff, self.dtype),
            self._stage_2d_grad(mesh, d)
            if use_2d
            else jnp.zeros((d,), self.dtype),
            jnp.asarray(0.0, self.dtype),
            jnp.asarray(0, jnp.int32),
        )
        # coeff and grad live feature-sharded in the tensor-parallel
        # layout; everything else is replicated (snapshot leaves are full
        # host arrays either way — the tags drive the restore staging)
        carry_specs = (
            ("model", "model", "replicated", "replicated")
            if self.shard_features
            else "replicated"
        )
        ckpt_meta = {"numBatches": nb, "globalBatchSize": int(self.global_batch_size)}
        epoch, criteria = 0, float("inf")
        snap = _snapshot.load_job_snapshot(
            self.checkpoint_dir,
            self.checkpoint_key,
            templates={"model": carry},
            expect_meta=ckpt_meta,
        )
        if snap is not None:
            carry = _snapshot.stage_section(
                snap, "model", mesh=mesh, specs=carry_specs
            )
            epoch, criteria = snap.epoch, snap.criteria
            # the restored epoch counter must live in the carry (the chunk
            # kernel's loop condition reads carry[3])
            carry = carry[:3] + (jnp.asarray(epoch, jnp.int32),)

        interval = max(1, int(self.checkpoint_interval))

        # Whole-fit resident program (config.whole_fit): when no snapshot
        # boundary lands strictly inside the remaining fit, the entire
        # loop + final update + result pack run as ONE dispatch with ONE
        # packed readback; a fit-end boundary is honored by snapshotting
        # the returned carry after the drain. A mid-fit boundary falls
        # back to the chunked path below (reason-counted).
        take_whole, _ = dispatch.whole_fit_plan(
            start_epoch=epoch, max_iter=self.max_iter, checkpoint_interval=interval
        )
        if take_whole:
            dispatch.account_whole_fit("sgd")
            crit_dev = jnp.asarray(criteria, jnp.float32)
            with tracing.span(
                "iteration.run", mode="whole_fit", epochs=self.max_iter
            ):
                if use_2d:
                    carry, crit_dev, packed = dispatch.timed_dispatch(
                        sgd2d.sgd2d_whole_fit,
                        mesh, X_b, y_b, w_b, carry, crit_dev, loss_func, hyper,
                        start=epoch, end=self.max_iter,
                    )
                    (host,) = packed_device_get(packed, sync_kind="fit")
                    nm = mesh_lib.num_model_shards(mesh)
                    coeff_h, final_crit, final_epoch, _ = sgd2d.sgd2d_unpack_host(
                        np.asarray(host), nm, d // nm, False
                    )
                else:
                    carry, crit_dev, packed = dispatch.timed_dispatch(
                        _sgd_whole_fit,
                        X_b, y_b, w_b, carry, crit_dev, loss_func, hyper,
                        self._pack_sharding(mesh),
                        start=epoch, end=self.max_iter,
                    )
                    (host,) = packed_device_get(packed, sync_kind="fit")
                    _, coeff_h, final_crit, final_epoch = unpack_train_result(
                        np.asarray(host), d
                    )
                if final_epoch > epoch and final_epoch % interval == 0:
                    _snapshot.save_job_snapshot(
                        self.checkpoint_dir,
                        self.checkpoint_key,
                        {"model": carry},
                        epoch=final_epoch,
                        criteria=final_crit,
                        specs={"model": carry_specs},
                        meta=ckpt_meta,
                    )
                faults.tick("chunk")  # the whole fit is one drained chunk
            return np.asarray(coeff_h), final_crit, final_epoch

        K = config.iteration_chunk_for(self.max_iter)
        donate_ok = dispatch.supports_donation()
        queue = dispatch.DrainQueue(config.iteration_dispatch_depth)
        crit_dev = jnp.asarray(criteria, jnp.float32)
        final_epoch, final_crit = epoch, criteria
        stopped = criteria <= self.tol

        def handle(drained):
            nonlocal final_epoch, final_crit, stopped
            for entry, e_act, crit in drained:
                advanced = e_act > final_epoch
                final_epoch, final_crit = e_act, crit
                if advanced and e_act == entry.end and e_act % interval == 0:
                    _snapshot.save_job_snapshot(
                        self.checkpoint_dir,
                        self.checkpoint_key,
                        {"model": entry.carry},
                        epoch=e_act,
                        criteria=crit,
                        specs={"model": carry_specs},
                        meta=ckpt_meta,
                    )
                if crit <= self.tol:
                    stopped = True
                faults.tick("chunk")

        with tracing.span(
            "iteration.run", mode="chunked", chunk=K, depth=queue.depth
        ):
            planned = epoch
            donate_next = False
            while planned < self.max_iter and not stopped:
                end = min(
                    planned + K,
                    self.max_iter,
                    dispatch.next_boundary(planned, interval),
                )
                retain = end % interval == 0
                if use_2d:
                    # 2D chunks always borrow: the sharded carry must stay
                    # readable for a pending snapshot write, and the
                    # shard_map program re-enters its cached executable
                    def step(Xb, yb, wb, c, crit, lf, hy, ce):
                        return sgd2d.sgd2d_chunk(
                            mesh, Xb, yb, wb, c, crit, lf, hy, ce
                        )
                else:
                    step = (
                        _sgd_chunk_donating
                        if (donate_next and donate_ok)
                        else _sgd_chunk
                    )
                with tracing.span("iteration.chunk", epoch=planned, end=end):
                    carry, crit_dev, packed = dispatch.timed_dispatch(
                        step,
                        X_b, y_b, w_b, carry, crit_dev, loss_func, hyper,
                        jnp.asarray(end, jnp.int32),
                        start=planned, end=end,
                    )
                handle(
                    queue.push(
                        dispatch.InFlight(
                            planned, end, carry if retain else None, packed
                        )
                    )
                )
                planned = end
                donate_next = not retain
            handle(queue.drain_all())

        coeff, grad, wsum, _ = carry
        dtype = _feature_dtype(X_b)
        coeff = _final_update(
            coeff, grad, wsum,
            jnp.asarray(self.learning_rate, dtype),
            jnp.asarray(self.reg, dtype),
            jnp.asarray(self.elastic_net, dtype),
        )
        (coeff_h,) = packed_device_get(coeff, sync_kind="fit")
        return np.asarray(coeff_h), final_crit, final_epoch

    def _in_place(self, mesh: Mesh, X, y, weights):
        """`_batchify`'s three for a table `_can_train_in_place` admits, the
        table not touched: X is a `FlatBatches` view of the caller's array
        (of each of a padded-CSR pair's), and only the columns take the
        general form's (num_batches, batch) shape, 80 MB each for the 20M
        rows whose table is 8.3 GB (y a reshape; absent weights the ones
        `_default_weights` makes, as on the laid-out route). No `fit.layout`
        and no `layout.*` tick: no table is laid out. For a dense table the
        `dense_epoch.reduce` tick says, as for laid-out batches, that the
        one-read kernel is not taken; which form the fleet's epochs take is
        `fleet.product.*` (`_fleet_multiplies`, `_fleet_rows`)."""
        sparse = isinstance(X, tuple)
        n, B = int((X[0] if sparse else X).shape[0]), int(self.global_batch_size)
        num_batches = n // B
        row_sharding = NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS))
        batches = (n, num_batches, B, B)  # no row and no batch is padded
        y_b = _layout_batches(y, *batches, None, row_sharding)
        if weights is None:
            w_b = _default_weights(*batches, self.dtype, row_sharding)
        else:
            w_b = _layout_batches(weights, *batches, None, row_sharding)
        if not sparse:
            metrics.inc_counter("dense_epoch.reduce")
        # the table is the caller's and stays where it is: ledgered as the
        # fit's training-data residency, like the flat route's
        from ..obs import memledger

        memledger.track((X, y_b, w_b), "streamSegments")
        if sparse:
            return tuple(FlatBatches(leaf, B) for leaf in X), y_b, w_b
        return FlatBatches(X, B), y_b, w_b

    def _batchify(self, mesh: Mesh, X, y, weights, d_pad=None, replicate_data=False):
        """`_lay_out` as the `fit.layout` phase: the host's time to cast,
        stage and enqueue the layout programs, not the device's to run them."""
        with tracing.phase("fit.layout"):
            return self._lay_out(mesh, X, y, weights, d_pad, replicate_data)

    def _lay_out(self, mesh: Mesh, X, y, weights, d_pad, replicate_data):
        """Stage data into device-resident (num_batches, padded_batch, ...)
        arrays sharded over the data axis.

        Host inputs make exactly ONE flat host→device transfer each (dtype
        cast is the only host copy, and only when needed); device-resident
        inputs (e.g. benchmark tables generated on chip) transfer nothing.
        All padding/reshaping happens on device (`_layout_batches`), and
        absent weights are synthesized on device (`_default_weights`).

        `replicate_data` is the fleet-axis-sharded regime's layout
        (fleet.py): the mesh data axis is spent on the FLEET dimension, so
        the shared training data stays replicated and the batch layout is
        computed as for a single data shard — which is why a
        fleet-sharded member's fit is bit-identical to its solo fit on a
        ONE-device mesh (docs/performance.md §11)."""
        n = int(np.shape(X[0] if isinstance(X, tuple) else X)[0])
        B = int(self.global_batch_size)
        num_batches = max(1, -(-n // B))
        data_axis = None if replicate_data else mesh_lib.DATA_AXIS
        shards = 1 if replicate_data else mesh_lib.num_data_shards(mesh)
        b_pad = -(-B // shards) * shards

        def stage(arr, dtype=None):
            """One flat transfer, row-sharded across the mesh so no single
            chip stages the whole dataset; cast to the compute dtype with
            minimal host work (halves bytes on the wire for f64 input). Host
            rows are zero-padded to a shard-divisible count; `_layout_batches`
            strips that pad via the true n. Returns (array, owned): owned
            buffers were created here and may be donated to the layout."""
            dtype = dtype or self.dtype
            if isinstance(arr, jax.Array):
                if arr.dtype != dtype:
                    return arr.astype(dtype), True
                return arr, False
            arr = np.asarray(arr)
            if arr.dtype != dtype:
                arr = arr.astype(dtype)
            spec = P(data_axis, *([None] * (arr.ndim - 1)))
            sharding = NamedSharding(mesh, spec)
            rows = arr.shape[0]
            if shards == 1 or rows % shards == 0:
                return h2d.stage_to_device(arr, sharding), True
            n_stage = -(-rows // shards) * shards

            def shard_chunk(index):
                rs = index[0]
                start = rs.start or 0
                stop = rs.stop if rs.stop is not None else n_stage
                if stop <= rows:  # whole chunk is real data: zero-copy view
                    chunk = arr[start:stop]
                else:  # tail chunk: copy valid rows into a zero pad block
                    chunk = np.zeros((stop - start,) + arr.shape[1:], arr.dtype)
                    if start < rows:
                        chunk[: rows - start] = arr[start:rows]
                return chunk[(slice(None),) + tuple(index[1:])]

            return (
                h2d.stage_from_callback(
                    (n_stage,) + arr.shape[1:], sharding, shard_chunk
                ),
                True,
            )

        def layout(staged, n, num_batches, batch, b_pad, d_pad, sharding):
            arr, owned = staged
            if _can_exchange(arr, n, batch, shards, d_pad, mesh):
                metrics.inc_counter("layout.exchange")
                fn = _exchange_batches_donating if owned else _exchange_batches
                args = (batch, mesh)
            else:
                metrics.inc_counter("layout.general")
                fn = _layout_batches_donating if owned else _layout_batches
                args = (n, num_batches, batch, b_pad, d_pad, sharding)
            return fn(arr, *args)

        if isinstance(X, tuple):
            # sparse padded-CSR: neither leaf has a feature axis to shard —
            # indices reference the (possibly model-sharded) coefficient;
            # XLA inserts the gather/scatter collectives for the TP layout
            csr_sharding = NamedSharding(mesh, P(None, data_axis, None))
            X_b = (
                layout(stage(X[0], np.int32), n, num_batches, B, b_pad, None, csr_sharding),
                layout(stage(X[1]), n, num_batches, B, b_pad, None, csr_sharding),
            )
        else:
            # laid-out batches are read by the reduce form, whatever loop
            # reads them (`_can_one_pass` is asked by `_stage_flat` alone)
            metrics.inc_counter("dense_epoch.reduce")
            X_b = layout(
                stage(X),
                n,
                num_batches,
                B,
                b_pad,
                d_pad,
                NamedSharding(
                    mesh,
                    P(None, data_axis, mesh_lib.MODEL_AXIS)
                    if d_pad is not None
                    else P(None, data_axis, None),
                ),
            )
        row_sharding = NamedSharding(mesh, P(None, data_axis))
        y_b = layout(stage(y), n, num_batches, B, b_pad, None, row_sharding)
        if weights is None:
            # Padding rows get weight 0: they contribute nothing to
            # loss/grad/weight sums.
            w_b = _default_weights(n, num_batches, B, b_pad, self.dtype, row_sharding)
        else:
            w_b = layout(stage(weights), n, num_batches, B, b_pad, None, row_sharding)
        # the batched layouts are the fit-long training-data residency
        # (the staged flat uploads above are donated into them); ledger
        # them so hbm.live.streamSegments / peakHbmBytes see the fit's
        # dominant allocation — entries close when the fit drops them
        from ..obs import memledger

        memledger.track((X_b, y_b, w_b), "streamSegments")
        return X_b, y_b, w_b
