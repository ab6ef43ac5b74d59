"""KMeans — Lloyd's algorithm over the device mesh.

TPU-native re-design of clustering/kmeans/KMeans.java:87-310,
KMeansModel.java and KMeansModelData.java:53-116. The reference's per-epoch
flow (broadcast centroids -> per-point argmin assignment -> partial sums ->
countWindowAll(parallelism) funnel reduce -> parallelism-1 centroid update,
KMeans.java:135-212) becomes one jitted while-loop epoch: a pairwise
distance matmul on the MXU, a segment-sum of the rows, and a psum over the
mesh data axis — no funnel-to-one-task bottleneck. Termination is maxIter
(TerminateOnMaxIter.java:56). Init mirrors selectRandomCentroids
(KMeans.java:310): sample k distinct rows with the stage seed.
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...api import Estimator, Model
from ...common.param import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
)
from ...ops.distance import (
    ALL_PIECES,
    ONE_PIECE,
    DistanceMeasure,
    first_minimum,
    in_bfloat16,
    jit_find_closest,
)
from ...param import IntParam, ParamValidators, StringParam
from ...parallel import collectives
from ...parallel import mesh as mesh_lib
from ...parallel import prefetch as h2d
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params


class KMeansModelParams(HasDistanceMeasure, HasFeaturesCol, HasPredictionCol):
    K = IntParam("k", "The max number of clusters to create.", 2, ParamValidators.gt(1))

    def get_k(self) -> int:
        return self.get(self.K)

    def set_k(self, value: int):
        return self.set(self.K, value)


class KMeansParams(KMeansModelParams, HasSeed, HasMaxIter):
    INIT_MODE = StringParam(
        "initMode",
        "The initialization algorithm. Supported options: 'random'.",
        "random",
        ParamValidators.in_array(["random"]),
    )

    def get_init_mode(self) -> str:
        return self.get(self.INIT_MODE)

    def set_init_mode(self, value: str):
        return self.set(self.INIT_MODE, value)


# One block of rows meets all k centroids as a (block, k) matrix of
# distances. A block holds at most this many elements of it and of its
# (block, d) slice of the table, whatever n is.
_BLOCK_ELEMENTS = 1 << 25


def _block_rows(n: int, k: int, d: int) -> int:
    """Rows of one block of an (n, d) table against k centroids, from the
    shapes alone: a multiple of 256 that keeps block x (k + d) under
    `_BLOCK_ELEMENTS`, and never more than n."""
    return min(n, max(256, _BLOCK_ELEMENTS // (k + d) // 256 * 256))


def _num_blocks(n: int, k: int, d: int) -> int:
    return -(-n // _block_rows(n, k, d))


def _block_step(Xb, counted, centroids, measure, point_pieces=ALL_PIECES):
    """THE Lloyd block step, shared by every KMeans fit: each row of Xb
    (b, d) is assigned to its closest centroid (the lowest index on a tie),
    and the rows that `counted` (b,) marks add to the block's (sums (k, d),
    counts (k,)); a row not counted goes to a segment past the last cluster,
    which is dropped. The distances' cross term is a float32 product
    whatever `point_pieces` says; what it decides is the number of bfloat16
    passes (`ops/distance.py`, "Precision"): six where nothing is known of
    the points, three where the caller has seen that every value of the
    table is exact in bfloat16, the three left out being products of zeros.
    No term that holds anything is dropped. The sums are a segment-sum: on
    the v5e a fit of 2.7M x 784 rows against 4,096 centroids took 4.75 s
    with it and 5.65 s with `one_hot.T @ X` at `HIGHEST` (PERF.md, PR 27),
    and it adds float32 to float32 with no product to state a precision
    for."""
    k = centroids.shape[0]
    assign = jnp.where(counted, first_minimum(measure.closeness(Xb, centroids, point_pieces)), k)
    sums = jax.ops.segment_sum(Xb, assign, k + 1)[:k]
    counts = jax.ops.segment_sum(jnp.ones_like(assign, Xb.dtype), assign, k + 1)[:k]
    return sums, counts


def _one_block(X, w, i, block, centroids, measure, point_pieces=ALL_PIECES):
    """(sums, counts) of block i of the rows X (n, d), `block` rows each. n
    need not be a whole number of blocks: the last block is moved back to
    end at row n, and the rows the block before it has counted are masked
    out of it. `w` (n,) marks the rows that count at all (shard and bucket
    padding has weight 0); None counts every row."""
    n = X.shape[0]
    if block == n:
        counted = jnp.ones((n,), bool) if w is None else w > 0
        return _block_step(X, counted, centroids, measure, point_pieces)
    start = jnp.minimum(i * block, n - block)
    Xb = lax.dynamic_slice_in_dim(X, start, block, 0)
    counted = start + jnp.arange(block) >= i * block
    if w is not None:
        counted = counted & (lax.dynamic_slice_in_dim(w, start, block, 0) > 0)
    return _block_step(Xb, counted, centroids, measure, point_pieces)


def _accumulate_batch_impl(X, w, centroids, measure_name):
    """One pass of the rows held here, X (n, d), over the centroids: the
    (sums, counts) partials of a Lloyd iteration, block by block
    (`_block_rows`, `_one_block`), so that nothing of shape (n, k) exists.
    Each batch of both stream fits and the overlap schedule take it; the
    in-memory fit and the fleet walk the same blocks in `_lloyd_loop`."""
    measure = DistanceMeasure.get_instance(measure_name)
    n, d = X.shape
    k = centroids.shape[0]
    block = _block_rows(n, k, d)
    blocks = -(-n // block)
    if blocks == 1:
        return _one_block(X, w, 0, block, centroids, measure)

    def add(i, partials):
        s, c = _one_block(X, w, i, block, centroids, measure)
        return partials[0] + s, partials[1] + c

    zero = (jnp.zeros((k, d), X.dtype), jnp.zeros((k,), X.dtype))
    return lax.fori_loop(0, blocks, add, zero)


def _on_row_shards(local, mesh, replicated, X, weights):
    """`local(*replicated, X, w)` run by every shard of the mesh's data axis
    on its own rows of X and of the weights (w is None where there are
    none); what it returns has to be the same on every shard."""
    axis = mesh_lib.DATA_AXIS
    rows = (X,) if weights is None else (X, weights)
    specs = (P(),) * len(replicated) + (P(axis, None), P(axis))[: len(rows)]

    def shard(*args):
        w = args[-1] if weights is not None else None
        return local(*args[: len(replicated)], args[len(replicated)], w)

    return collectives.shard_map_over(mesh, specs, (P(), P()), fn=shard)(*replicated, *rows)


def _lloyd_partials(X, weights, centroids, measure_name, mesh):
    """(sums, counts) of one pass over a batch of rows. `mesh` is None
    where every device holds all the rows it is given (one shard, or a
    replicated table); else the rows are shared over the mesh's data axis,
    each shard runs the blocks of its own rows and one psum adds them."""
    if mesh is None:
        return _accumulate_batch_impl(X, weights, centroids, measure_name)

    def local(centroids, X, w):
        partials = _accumulate_batch_impl(X, w, centroids, measure_name)
        return collectives.all_reduce_sum(partials, mesh_lib.DATA_AXIS)

    return _on_row_shards(local, mesh, (centroids,), X, weights)


# the host-driven stream loop's program: one batch's partials
_accumulate_batch = lazy_jit(_lloyd_partials, static_argnames=("measure_name", "mesh"))


def _new_centroids(centroids, sums, counts):
    """The mean of each cluster's rows; an empty cluster keeps its centroid."""
    return jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centroids
    )


def _lloyd_loop(X, w, init_centroids, max_iter, measure_name, axis, point_pieces=ALL_PIECES):
    """`max_iter` Lloyd iterations over the rows held here as ONE flat loop:
    step t is block t mod blocks of iteration t div blocks, and the step
    that ends an iteration adds the shards' partials (`axis`; None where the
    rows are all here) and updates the centroids. One loop and not a loop of
    blocks inside a loop of iterations, because the TPU keeps a table of a
    few hundred columns with its rows on the lanes, and for a loop nested in
    a loop its compiler first copies the WHOLE table into row-major order: a
    second table, which a table over half the chip has no room for."""
    measure = DistanceMeasure.get_instance(measure_name)
    n, d = X.shape
    k = init_centroids.shape[0]
    block = _block_rows(n, k, d)
    blocks = -(-n // block)
    zero = (jnp.zeros((k, d), X.dtype), jnp.zeros((k,), X.dtype))

    def cond(state):
        return state[0] < max_iter * blocks

    def step(state):
        t, centroids, last_counts, partials = state
        s, c = _one_block(X, w, t % blocks, block, centroids, measure, point_pieces)
        partials = (partials[0] + s, partials[1] + c)

        def end_of_iteration():
            sums, counts = partials if axis is None else collectives.all_reduce_sum(partials, axis)
            return _new_centroids(centroids, sums, counts), counts, zero

        if blocks == 1:
            return (t + 1,) + end_of_iteration()
        return (t + 1,) + lax.cond(
            t % blocks == blocks - 1,
            end_of_iteration,
            lambda: (centroids, last_counts, partials),
        )

    init = (jnp.asarray(0, jnp.int32), init_centroids, zero[1], zero)
    _, centroids, counts, _ = lax.while_loop(cond, step, init)
    return centroids, counts


def _lloyd_train_impl(X, weights, init_centroids, max_iter, measure_name, mesh, point_pieces=ALL_PIECES):
    """The full Lloyd fit as one XLA program (`_lloyd_loop`). `mesh` is None
    where every device holds all the rows it is given (one shard, or a
    replicated table); else the rows are shared over the mesh's data axis
    and each shard walks the blocks of its own. Data and max_iter are
    runtime arguments so repeated fits with the same shapes reuse the
    compiled executable. `point_pieces` is what the caller knows of ALL the
    table's values (`_point_pieces`); the default knows nothing."""
    if mesh is None:
        return _lloyd_loop(X, weights, init_centroids, max_iter, measure_name, None, point_pieces)

    def local(init_centroids, max_iter, X, w):
        return _lloyd_loop(X, w, init_centroids, max_iter, measure_name, mesh_lib.DATA_AXIS, point_pieces)

    return _on_row_shards(local, mesh, (init_centroids, max_iter), X, weights)


def _lloyd_fit_impl(X, weights, init_centroids, max_iter, measure_name, mesh, point_pieces=ALL_PIECES):
    """`_lloyd_train_impl` with its result packed for ONE readback:
    [centroids.ravel | counts]."""
    centroids, counts = _lloyd_train_impl(
        X, weights, init_centroids, max_iter, measure_name, mesh, point_pieces
    )
    return jnp.concatenate([centroids.ravel(), counts])


# Nothing is donated: the table is the caller's where it is trained in
# place, and no output has its shape anyway.
_lloyd_fit = lazy_jit(_lloyd_fit_impl, static_argnames=("measure_name", "mesh", "point_pieces"))


def _lloyd_fleet_train_impl(X, weights, init_centroids, max_iters, measure_name, pack_sharding, mesh):
    """N Lloyd fits as ONE vmapped resident program (fleet.py): the member
    loop is `_lloyd_train_impl` verbatim, vmapped over the per-member
    (init_centroids[N,k,d], max_iters[N]) with the staged dataset closed
    over unbatched — input bytes are paid once for N models. The vmapped
    `while_loop` runs until every member hits its own maxIter and
    select-freezes finished members. A member's sums are a segment-sum,
    which `vmap` batches into one scatter-add: on the CPU a member is
    bit-identical to its solo fit (tests/test_fleet.py), though no backend
    promises a scatter-add's order (ROADMAP D4). Readback is ONE packed
    [N, k*d + k] array ([centroids.ravel | counts] per member)."""
    def member(c0, mi):
        return _lloyd_train_impl(X, weights, c0, mi, measure_name, mesh)

    centroids, counts = jax.vmap(member)(init_centroids, max_iters)
    n_members, k, d = init_centroids.shape
    packed = jnp.concatenate([centroids.reshape(n_members, k * d), counts], axis=1)
    if pack_sharding is not None:
        packed = jax.lax.with_sharding_constraint(packed, pack_sharding)
    return packed


_lloyd_fleet_train = lazy_jit(
    _lloyd_fleet_train_impl, static_argnames=("measure_name", "pack_sharding", "mesh")
)


class KMeansModel(Model, KMeansModelParams):
    fusable = True

    def __init__(self):
        self.centroids: np.ndarray = None  # (k, d)
        self.weights: np.ndarray = None  # (k,)
        self.cache_stats = None  # set by out-of-core (StreamTable) fits

    def _constant_sources(self):
        return (self.centroids,)

    def _kernel_constants(self):
        return {"centroids": np.asarray(self.centroids, np.float32)}

    def transform_kernel(self, consts, cols, ctx):
        from ...api import as_kernel_matrix

        X = as_kernel_matrix(cols[self.get_features_col()])
        cols[self.get_prediction_col()] = jit_find_closest(
            self.get_distance_measure()
        )(jnp.asarray(X, jnp.float32), consts["centroids"])
        return cols

    def set_model_data(self, *inputs: Table) -> "KMeansModel":
        (model_data,) = inputs
        row = model_data.collect()[0]
        self.centroids = np.stack(
            [np.asarray(c.to_array() if hasattr(c, "to_array") else c, dtype=np.float64)
             for c in row["centroids"]]
        )
        w = row["weights"]
        self.weights = np.asarray(w.to_array() if hasattr(w, "to_array") else w, dtype=np.float64)
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        return [
            Table(
                {
                    "centroids": [[DenseVector(c) for c in self.centroids]],
                    "weights": [DenseVector(self.weights)],
                }
            )
        ]

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
        # both input paths share the memoized publication upload, so the
        # centroids ride the ledgered `model` funnel exactly once per
        # model state instead of a fresh unaccounted upload per call
        centroids = self.device_constants()["centroids"]
        assign = jit_find_closest(self.get_distance_measure())(
            jnp.asarray(X, jnp.float32), centroids
        )
        if not isinstance(X, jax.Array):  # host in -> host out
            from ...utils.packing import packed_device_get

            # accounted single readback instead of a silent np.asarray pull
            assign = packed_device_get(assign, sync_kind="transform")[0].astype(
                np.int32
            )
        return [table.with_column(self.get_prediction_col(), assign)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, centroids=self.centroids, weights=self.weights)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        loaded = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_kmeans
        )
        if isinstance(loaded, dict):
            self.centroids, self.weights = loaded["centroids"], loaded["weights"]
        else:  # reference binary (KMeansModelData.ModelDataEncoder)
            self.centroids, self.weights = loaded


def _lloyd_stream_whole_fit_impl(packed_all, init_centroids, init_counts, start_epoch, max_iter, measure_name, mesh):
    """The whole out-of-core Lloyd fit as ONE resident program: the
    stacked [X | w] stream batches (nb, rows, d+1) live in HBM (the device
    epoch cache's contents staged once) and each epoch's inner loop
    dynamic-slices batch partials in replay order — the same sequential
    `sums + s` fold of the same `_lloyd_partials` the host-driven loop
    performs, so centroids and counts are bit-identical to it (the
    `optimization_barrier` materializes the column views exactly as the
    per-batch staging path does). Requires every batch bucketed to the SAME
    row count; ragged streams fall back to the host-driven loop
    (dispatch.whole_fit_plan)."""
    nb, _, dp1 = packed_all.shape
    d = dp1 - 1
    k = init_centroids.shape[0]

    def batch_step(bi, acc):
        sums, counts, centroids = acc
        batch = lax.dynamic_index_in_dim(packed_all, bi, 0, False)
        Xb, wb = lax.optimization_barrier((batch[:, :d], batch[:, d]))
        s, c = _lloyd_partials(Xb, wb, centroids, measure_name, mesh)
        return sums + s, counts + c, centroids

    def epoch_step(_, state):
        centroids, _ = state
        sums, counts, _ = lax.fori_loop(
            0,
            nb,
            batch_step,
            (
                jnp.zeros((k, d), packed_all.dtype),
                jnp.zeros((k,), packed_all.dtype),
                centroids,
            ),
        )
        return _new_centroids(centroids, sums, counts), counts

    return lax.fori_loop(
        start_epoch, max_iter, epoch_step, (init_centroids, init_counts)
    )


_lloyd_stream_whole_fit = lazy_jit(
    _lloyd_stream_whole_fit_impl, static_argnames=("measure_name", "mesh")
)


def _sample_without_replacement(rng: np.random.RandomState, n: int, k: int) -> np.ndarray:
    """Seeded k-of-n sample. Below the threshold this is exactly the
    in-memory path's rng.choice draw (stream/in-memory init parity); above
    it, rejection sampling avoids RandomState.choice's O(n) permutation
    (16 GB of indices at n=2e9 — the scale this path exists for)."""
    if n <= 10_000_000:
        return rng.choice(n, size=k, replace=False)
    seen, out = set(), []
    while len(out) < k:
        v = int(rng.randint(0, n))
        if v not in seen:
            seen.add(v)
            out.append(v)
    return np.asarray(out, dtype=np.int64)


def _exact_in_bfloat16_impl(X):
    """Whether EVERY value of the table is its own bfloat16: finite, and at
    most 8 significant bits (pixel bytes, byte descriptors, small whole
    numbers). One pass over all rows, one boolean."""
    return jnp.all(jnp.isfinite(X) & (in_bfloat16(X) == X))


_exact_in_bfloat16 = lazy_jit(_exact_in_bfloat16_impl)


def _point_pieces(X_dev) -> int:
    """How many bfloat16 pieces the staged float32 table's values have, for
    the train program's cross term: `ONE_PIECE` where the table lies on a
    TPU and a look at all of its rows says so, `ALL_PIECES` where it does
    not, and where nothing is looked at (the CPU, whose float32 product is
    one pass whatever the points are). The look is one small program and one
    boolean read back, a host sync of the fit; every fit looks again, since
    nothing says that a table is the one the last fit saw, and no sample can
    promise what the short product stands on."""
    from ...obs import tracing

    if not mesh_lib.on_tpu(X_dev):
        return ALL_PIECES
    exact = bool(tracing.sync("look", _exact_in_bfloat16(X_dev)))
    return ONE_PIECE if exact else ALL_PIECES


@partial(lazy_jit, static_argnames=("n_pad", "sharding"))
def _stage_points(X, n_pad, sharding):
    """A device-born table brought to what the fit needs, in HBM and in one
    copy: cast to float32, rows padded to the shards, laid over the mesh.
    A table that needs none of the three is trained in place instead
    (`KMeans._stage`); each call here is one `lloyd.table_copy`."""
    X = X.astype(jnp.float32)
    if X.shape[0] != n_pad:
        X = jnp.pad(X, [(0, n_pad - X.shape[0]), (0, 0)])
    return jax.lax.with_sharding_constraint(X, sharding)


def _take_rows_impl(X, idx):
    """Rows `idx` of a table held by one device: for each, the group of 128
    rows around it is sliced out and a masked sum picks the row (exact: the
    other rows add zeros). A gather, or a slice of one row, would do, but
    the TPU keeps a table of a few hundred columns with its rows on the
    lanes and copies the WHOLE table into row-major order to serve either:
    a second table, which a table over half the chip has no room for."""
    n, d = X.shape
    group = min(128, n)

    def take(i, out):
        start = jnp.minimum(idx[i] // group * group, n - group)
        rows = lax.dynamic_slice_in_dim(X, start, group, 0)
        picked = (jnp.arange(group) == idx[i] - start)[:, None]
        row = jnp.sum(jnp.where(picked, rows, 0), axis=0, keepdims=True)
        return lax.dynamic_update_slice_in_dim(out, row, i, 0)

    return lax.fori_loop(0, idx.shape[0], take, jnp.zeros((idx.shape[0], d), X.dtype))


_take_rows = lazy_jit(_take_rows_impl)


@partial(lazy_jit, static_argnames=("d", "mat_sharding", "row_sharding"))
def _unpack_points(packed, d, mat_sharding, row_sharding):
    """Split the dtype-packed [X | w] stream batch on device, constrained
    to the accumulation shardings — the single-transfer layout the stream
    staging path uploads (see ops/optimizer._unpack_stream_batch)."""
    X = lax.with_sharding_constraint(packed[:, :d], mat_sharding)
    w = lax.with_sharding_constraint(packed[:, d], row_sharding)
    return X, w


@partial(lazy_jit, static_argnames=("n_pad", "sharding"))
def _unit_weights(n, n_pad, sharding):
    # n is a traced operand: one compiled program per n_pad, not per (n, n_pad)
    w = (jnp.arange(n_pad) < n).astype(jnp.float32)
    return jax.lax.with_sharding_constraint(w, sharding)


class KMeans(Estimator, KMeansParams):
    # out-of-core (StreamTable) fits snapshot (centroids, counts, rng)
    # at epoch boundaries through the JobSnapshot API; the in-memory
    # fit is ONE device program, so its preemption unit is the whole
    # fit (re-dispatch recomputes — nothing host-visible to snapshot)
    checkpointable = True
    def fit(self, *inputs) -> KMeansModel:
        (table,) = inputs
        from ...table import StreamTable

        if isinstance(table, StreamTable):
            return self._fit_stream(table)
        from ... import config
        from ...obs import tracing
        from ...ops.optimizer import _read_packed
        from ...parallel import dispatch
        from ...utils import metrics

        with tracing.phase("fit.extract"):
            mesh = mesh_lib.default_mesh()
            X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
            n, d = X.shape
            k, max_iter = self.get_k(), self.get_max_iter()
            measure = self.get_distance_measure()
            if n < k:
                raise ValueError(f"Number of points ({n}) is less than k ({k})")
        with tracing.phase("fit.stage"):
            # selectRandomCentroids (KMeans.java:310): sample k rows without replacement.
            rng = np.random.RandomState(self.get_seed() % (2**32))
            centroid_idx = rng.choice(n, size=k, replace=False)
            X_dev, w_dev, init_centroids = self._stage(X, centroid_idx, mesh)
            shards = mesh_lib.num_data_shards(mesh)
            row_mesh = mesh if shards > 1 else None
            max_iter_dev = jnp.asarray(max_iter, jnp.int32)
            overlapped = config.collective_overlap and row_mesh is not None
            # the overlap schedule keeps the six passes: nothing is looked at
            point_pieces = ALL_PIECES if overlapped else _point_pieces(X_dev)

        if overlapped:
            # overlap-scheduled Lloyd: epoch e's centroid-partial reduce
            # rides the chunked collective under epoch e+1's distance
            # matmul (parallel/overlap.py; the same block step)
            from ...parallel import overlap

            def train(X, w, init, max_iter, measure, mesh, point_pieces):
                return overlap.overlapped_lloyd_train(mesh, X, w, init, max_iter, measure)

        else:
            train = _lloyd_fit
        # the in-memory Lloyd loop has always been a whole-fit resident
        # program (one dispatch, one packed readback); counted when the
        # mode is on, like the fused SGD paths
        if dispatch.whole_fit_enabled():
            dispatch.account_whole_fit("lloyd")
        metrics.inc_counter("lloyd.iterations", max_iter)
        metrics.inc_counter("lloyd.product.short" if point_pieces == ONE_PIECE else "lloyd.product.full")
        metrics.inc_counter(
            "lloyd.blocks", max_iter * shards * _num_blocks(X_dev.shape[0] // shards, k, d)
        )
        # the Lloyd loop is one on-device while_loop (always maxIter
        # epochs): no per-epoch host boundary exists, so a single
        # `iteration.run` span carries the per-run summary
        with tracing.span("iteration.run", mode="device", epochs=max_iter):
            packed = dispatch.timed_dispatch(
                train, X_dev, w_dev, init_centroids, max_iter_dev, measure, row_mesh, point_pieces,
                start=0, end=max_iter,
            )
            host = _read_packed(packed)  # the fit's one readback
        model = KMeansModel()
        model.centroids = np.asarray(host[: k * d].reshape(k, d), dtype=np.float64)
        model.weights = np.asarray(host[k * d :], dtype=np.float64)
        update_existing_params(model, self)
        return model

    @staticmethod
    def _stage(X, centroid_idx, mesh):
        """(table, weights, initial centroids) on the device, as the train
        program takes them. A device-born float32 table that already lies
        over the mesh as the fit needs it, with no row to pad, is trained IN
        PLACE: it stays the caller's, and nothing table-sized is allocated.
        Anything else is brought there in one copy, counted as
        `lloyd.table_copy` when the copy is made on the device. The weights
        mark the padding rows and are None where there are none."""
        from ...utils import metrics

        n = X.shape[0]
        shards = mesh_lib.num_data_shards(mesh)
        n_pad = -(-n // shards) * shards
        mat_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS, None))
        if isinstance(X, jax.Array):  # device-born: stage entirely in HBM
            in_place = (
                X.dtype == jnp.float32
                and n_pad == n
                and X.sharding.is_equivalent_to(mat_sharding, X.ndim)
            )
            if in_place:
                X_dev = X
            else:
                metrics.inc_counter("lloyd.table_copy")
                X_dev = _stage_points(X, n_pad, mat_sharding)
            if len(X_dev.sharding.device_set) == 1:
                init_centroids = _take_rows(X_dev, jnp.asarray(centroid_idx, jnp.int32))
            else:
                init_centroids = jnp.take(X_dev, jnp.asarray(centroid_idx), axis=0)
        else:
            X_host = np.asarray(X, dtype=np.float32)
            init_centroids = jnp.asarray(X_host[centroid_idx])
            X_pad, _ = mesh_lib.pad_to_multiple(X_host, shards)
            X_dev = h2d.stage_to_device(X_pad, mat_sharding)
        w_dev = None
        if n_pad != n:
            w_dev = _unit_weights(n, n_pad, NamedSharding(mesh, P(mesh_lib.DATA_AXIS)))
        return X_dev, w_dev, init_centroids

    def _fit_stream(self, stream) -> KMeansModel:
        """Out-of-core Lloyd over a StreamTable: the first pass caches every
        batch through the native spillable data cache (cache-then-replay,
        ReplayOperator.java:125-246); epoch 0 stages each batch to device
        once and later epochs replay the device-resident shards through
        the HBM epoch cache (zero H2D bytes within
        `config.device_cache_bytes`; over-budget batches re-stage from the
        host cache, one in flight at a time). Initialization matches the
        in-memory path exactly: the same seeded global-row-index sample
        (selectRandomCentroids, KMeans.java:310) fetched back from the
        cache, so a stream fit reproduces an in-memory fit of the
        concatenated stream."""
        from ... import config
        from ...native.datacache import ReplayableStreamTable

        replay = (
            stream
            if isinstance(stream, ReplayableStreamTable)
            else ReplayableStreamTable(
                stream,
                config.datacache_memory_budget_bytes,
                config.datacache_spill_dir,
            )
        )
        col = self.get_features_col()
        k = self.get_k()

        batch_rows = []
        for t in replay:  # pass 0: cache + count
            batch_rows.append(t.num_rows)
        n = int(np.sum(batch_rows, dtype=np.int64)) if batch_rows else 0
        if n < k:
            raise ValueError(f"Number of points ({n}) is less than k ({k})")

        rng = np.random.RandomState(self.get_seed() % (2**32))
        centroid_idx = _sample_without_replacement(rng, n, k)  # in-memory order
        needed = np.sort(centroid_idx)
        bounds = np.cumsum([0] + batch_rows)
        picked = {}
        for bi, t in enumerate(replay):
            lo, hi = bounds[bi], bounds[bi + 1]
            if lo > needed[-1]:
                break  # every sampled row already fetched — skip the tail
            local = needed[(needed >= lo) & (needed < hi)] - lo
            if local.size:
                X = np.asarray(as_dense_matrix(t.column(col)), dtype=np.float32)
                for li in local:
                    picked[int(li + lo)] = X[li]
        init = np.stack([picked[int(i)] for i in centroid_idx])

        mesh = mesh_lib.default_mesh()
        shards = mesh_lib.num_data_shards(mesh)
        row_mesh = mesh if shards > 1 else None  # _lloyd_partials' `mesh`
        mat_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS, None))
        row_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
        centroids = jnp.asarray(init)
        measure = self.get_distance_measure()
        d = init.shape[1]
        nb = len(batch_rows)

        # Input pipeline (data/devicecache.py + parallel/prefetch.py):
        # epoch 0 stages each cached batch ONCE — bucketed to a
        # recompile-bounding row count (repeat-last-row pad at weight 0,
        # bit-invisible to the segment sums) and uploaded as a single
        # dtype-packed [X | w] transfer straight into the data-parallel
        # sharded layout — and later epochs iterate the device-resident
        # shards with zero H2D bytes inside `config.device_cache_bytes`.
        # Misses re-stage through the shared single-worker prefetcher, so
        # cache/disk reads and uploads of batch i+1 ride under batch i's
        # assignment contractions (native cache access stays serial).
        from ... import config
        from ...data.devicecache import CachedEpochLoader

        replay_pos = {"it": None, "pos": 0}

        def stage(bi):
            # batches replay strictly in order within an epoch, so the
            # worker walks one shared iterator, skipping cache-hit batches
            if replay_pos["it"] is None or bi < replay_pos["pos"]:
                replay_pos["it"], replay_pos["pos"] = iter(replay), 0
            t = None
            while replay_pos["pos"] <= bi:
                t = next(replay_pos["it"])
                replay_pos["pos"] += 1
            X = np.asarray(as_dense_matrix(t.column(col)), dtype=np.float32)
            rows = X.shape[0]
            bucket = h2d.next_bucket(rows) if config.input_bucketing else rows
            target = -(-bucket // shards) * shards
            packed = np.empty((target, d + 1), np.float32)
            packed[:rows, :d] = X
            packed[rows:, :d] = X[rows - 1 : rows]  # repeat-last-row pad
            packed[:rows, d] = 1.0
            packed[rows:, d] = 0.0  # weight-0: the pad is compute-invisible
            packed_dev = h2d.stage_to_device(packed, mat_sharding)
            return _unpack_points(packed_dev, d, mat_sharding, row_sharding)

        # Checkpoint/resume (ckpt/snapshot.py): an epoch boundary is the
        # only consistent cut — the (sums, counts) partials reset per
        # epoch, so the snapshot is just (centroids, epoch) plus the host
        # RNG state (init sampling re-derives deterministically from the
        # seed, but the generator's post-init state is job state and
        # travels with the job). Keyed by the stage's param-hash job key;
        # `numBatches` in meta refuses a snapshot from a different stream
        # layout (the epoch→batch replay mapping would diverge). Under
        # `config.snapshot_hosts` both save and restore ride the sharded
        # two-phase-commit coordinator (ckpt/coordinator.py): replicated
        # centroid/count leaves and the host RNG land on host 0's shard,
        # the manifest commit is the cut, and the restore below accepts
        # either format (kill-mid-commit chaos case pinned in
        # tests/test_fault_injection.py).
        from ...ckpt import faults
        from ...ckpt import snapshot as _snapshot
        from ...parallel.iteration import checkpoint_job_key

        ckpt_dir = config.iteration_checkpoint_dir
        interval = max(1, int(config.iteration_checkpoint_interval))
        job_key = checkpoint_job_key(self) if ckpt_dir is not None else None
        start_epoch = 0
        counts = jnp.zeros((k,), jnp.float32)
        if ckpt_dir is not None:
            snap = _snapshot.load_job_snapshot(
                ckpt_dir,
                job_key,
                templates={"model": (init, np.zeros(k, np.float32))},
                expect_meta={"numBatches": nb},
            )
            if snap is not None:
                restored_centroids, restored_counts = snap.sections["model"]
                centroids = jnp.asarray(restored_centroids)
                counts = jnp.asarray(restored_counts)
                start_epoch = snap.epoch
                if "rng" in snap.sections:
                    keys, pos = snap.sections["rng"]
                    rng.set_state(
                        ("MT19937", keys, int(pos[0]), int(pos[1]), float(pos[2]))
                    )

        def rng_section():
            _, keys, pos, has_gauss, cached = rng.get_state()
            return (np.asarray(keys), np.asarray([pos, has_gauss, cached], np.float64))

        # Whole-fit resident program (config.whole_fit): all cached batches
        # staged ONCE as a stacked (nb, rows, d+1) HBM array, the full
        # Lloyd loop — inner per-batch accumulation in replay order, outer
        # maxIter epochs — as one dispatch. Requires uniform bucketed batch
        # shapes and the stack within the device-cache budget; a mid-fit
        # checkpoint boundary keeps the host-driven loop (reason-counted).
        from ...obs import tracing
        from ...parallel import dispatch

        targets = [
            -(-(h2d.next_bucket(rows) if config.input_bucketing else rows) // shards)
            * shards
            for rows in batch_rows
        ]
        uniform = len(set(targets)) == 1
        take_whole, _ = dispatch.whole_fit_plan(
            start_epoch=start_epoch,
            max_iter=self.get_max_iter(),
            checkpoint_interval=interval if ckpt_dir is not None else None,
            data_bytes=nb * max(targets) * (d + 1) * 4,
            uniform_batches=uniform,
        )
        if take_whole and replay.stats.get("spilledSegments", 0) > 0:
            # host cache spilled = demonstrably out-of-core scale: do not
            # attempt the transient host stack / HBM-resident copy
            dispatch.account_whole_fit_fallback("device_cache_budget")
            take_whole = False
        if take_whole:
            target = targets[0]
            stacked = np.empty((nb, target, d + 1), np.float32)
            for bi, t in enumerate(replay):
                Xb = np.asarray(as_dense_matrix(t.column(col)), dtype=np.float32)
                rows = Xb.shape[0]
                stacked[bi, :rows, :d] = Xb
                stacked[bi, rows:, :d] = Xb[rows - 1 : rows]  # repeat-last-row pad
                stacked[bi, :rows, d] = 1.0
                stacked[bi, rows:, d] = 0.0  # weight-0: compute-invisible
            packed_dev = h2d.stage_to_device(
                stacked, NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS, None))
            )
            dispatch.account_whole_fit("lloyd")
            with tracing.span(
                "iteration.run", mode="whole_fit", epochs=self.get_max_iter()
            ):
                centroids, counts = dispatch.timed_dispatch(
                    _lloyd_stream_whole_fit,
                    packed_dev,
                    centroids,
                    counts,
                    jnp.asarray(start_epoch, jnp.int32),
                    jnp.asarray(self.get_max_iter(), jnp.int32),
                    measure,
                    row_mesh,
                    start=start_epoch, end=self.get_max_iter(),
                )
            final_epoch = self.get_max_iter()
            if (
                ckpt_dir is not None
                and final_epoch > start_epoch
                and final_epoch % interval == 0
            ):
                _snapshot.save_job_snapshot(
                    ckpt_dir,
                    job_key,
                    {"model": (centroids, counts), "rng": rng_section()},
                    epoch=final_epoch,
                    specs={"rng": "host"},
                    meta={"numBatches": nb},
                )
            faults.tick("epoch")  # one drained readback = one tick
            return self._finish_stream_fit(centroids, counts, replay)

        loader = CachedEpochLoader(stage)
        for epoch in range(start_epoch, self.get_max_iter()):
            sums = jnp.zeros((k, centroids.shape[1]), jnp.float32)
            counts = jnp.zeros((k,), jnp.float32)
            for batch in loader.epoch(range(nb)):
                s, c = _accumulate_batch(*batch, centroids, measure, row_mesh)
                sums = sums + s
                counts = counts + c
            centroids = _new_centroids(centroids, sums, counts)
            if ckpt_dir is not None and (epoch + 1) % interval == 0:
                _snapshot.save_job_snapshot(
                    ckpt_dir,
                    job_key,
                    {"model": (centroids, counts), "rng": rng_section()},
                    epoch=epoch + 1,
                    specs={"rng": "host"},
                    meta={"numBatches": nb},
                )
            faults.tick("epoch")

        return self._finish_stream_fit(centroids, counts, replay)

    def _finish_stream_fit(self, centroids, counts, replay) -> KMeansModel:
        """Shared tail of both stream arms: ONE packed readback of the
        final (centroids, counts) and the model build."""
        from ...utils.packing import packed_device_get

        host_centroids, host_counts = packed_device_get(centroids, counts)
        model = KMeansModel()
        model.centroids = np.asarray(host_centroids, dtype=np.float64)
        model.weights = np.asarray(host_counts, dtype=np.float64)
        update_existing_params(model, self)
        model.cache_stats = replay.stats
        return model
