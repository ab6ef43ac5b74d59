"""KMeans — Lloyd's algorithm over the device mesh.

TPU-native re-design of clustering/kmeans/KMeans.java:87-310,
KMeansModel.java and KMeansModelData.java:53-116. The reference's per-epoch
flow (broadcast centroids -> per-point argmin assignment -> partial sums ->
countWindowAll(parallelism) funnel reduce -> parallelism-1 centroid update,
KMeans.java:135-212) becomes one jitted while-loop epoch: a pairwise
distance matmul, a one-hot segment-sum (both MXU work), and a psum over the
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
from ...ops.distance import DistanceMeasure, jit_find_closest
from ...param import IntParam, ParamValidators, StringParam
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


def _lloyd_train_impl(X, weights, init_centroids, max_iter, measure_name):
    """The full Lloyd loop as one XLA program; X is (n, d) sharded over the
    data axis, the segment-sum contraction over n makes XLA reduce over ICI.
    Data and max_iter are runtime arguments so repeated fits with the same
    shapes reuse the compiled executable."""
    measure = DistanceMeasure.get_instance(measure_name)

    def cond(state):
        _, _, epoch = state
        return epoch < max_iter

    def step(state):
        centroids, _, epoch = state
        dists = measure.pairwise(X, centroids)  # (n, k)
        assign = jnp.argmin(dists, axis=1)  # (n,)
        one_hot = jax.nn.one_hot(assign, centroids.shape[0], dtype=X.dtype)  # (n, k)
        one_hot = one_hot * weights[:, None]
        counts = jnp.sum(one_hot, axis=0)  # (k,)
        # reduce form rather than `one_hot.T @ X`: the matmat's blocked
        # accumulation over n changes under vmap batching, which would break
        # the fleet contract (every fleet member bit-identical to its solo
        # fit — see ops/losses.py module docstring and fleet.py)
        sums = jnp.sum(one_hot[:, :, None] * X[:, None, :], axis=0)  # (k, d)
        new_centroids = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centroids
        )
        return (new_centroids, counts, epoch + 1)

    init = (init_centroids, jnp.zeros(init_centroids.shape[0], X.dtype), jnp.asarray(0, jnp.int32))
    centroids, counts, _ = jax.lax.while_loop(cond, step, init)
    return centroids, counts


_lloyd_train = lazy_jit(_lloyd_train_impl, static_argnames=("measure_name",))
# Donating variant for fit-owned buffers: the staged/padded dataset, the
# synthesized unit weights, and the initial centroids are all consumed by
# the train loop, so XLA may reuse their HBM in place instead of holding a
# second copy for the duration of the fit.
_lloyd_train_donating = lazy_jit(
    _lloyd_train_impl, static_argnames=("measure_name",), donate_argnums=(0, 1, 2)
)


def _lloyd_fleet_train_impl(X, weights, init_centroids, max_iters, measure_name, pack_sharding):
    """N Lloyd fits as ONE vmapped resident program (fleet.py): the member
    loop is `_lloyd_train_impl` verbatim, vmapped over the per-member
    (init_centroids[N,k,d], max_iters[N]) with the staged dataset closed
    over unbatched — input bytes are paid once for N models. The vmapped
    `while_loop` runs until every member hits its own maxIter and
    select-freezes finished members, and every contraction in the body is
    vmap-batching bit-stable (see `_lloyd_train_impl`), so each member's
    centroids are bit-identical to its solo fit. Readback is ONE packed
    [N, k*d + k] array ([centroids.ravel | counts] per member)."""
    def member(c0, mi):
        return _lloyd_train_impl(X, weights, c0, mi, measure_name)

    centroids, counts = jax.vmap(member)(init_centroids, max_iters)
    n_members, k, d = init_centroids.shape
    packed = jnp.concatenate([centroids.reshape(n_members, k * d), counts], axis=1)
    if pack_sharding is not None:
        packed = jax.lax.with_sharding_constraint(packed, pack_sharding)
    return packed


_lloyd_fleet_train = lazy_jit(
    _lloyd_fleet_train_impl, static_argnames=("measure_name", "pack_sharding")
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


def _accumulate_batch_impl(X, w, centroids, measure_name):
    """Per-batch Lloyd accumulation for out-of-core training: assign each
    row to its closest centroid and return (sums, counts) partials that the
    host adds across the replayed stream. w masks shard-padding rows. The
    un-jitted impl is shared with the whole-fit resident program, which
    inlines the same accumulation inside its epoch loop."""
    measure = DistanceMeasure.get_instance(measure_name)
    dists = measure.pairwise(X, centroids)
    assign = jnp.argmin(dists, axis=1)
    one_hot = jax.nn.one_hot(assign, centroids.shape[0], dtype=X.dtype) * w[:, None]
    return one_hot.T @ X, jnp.sum(one_hot, axis=0)


_accumulate_batch = lazy_jit(_accumulate_batch_impl, static_argnames=("measure_name",))


def _lloyd_stream_whole_fit_impl(packed_all, init_centroids, init_counts, start_epoch, max_iter, measure_name):
    """The whole out-of-core Lloyd fit as ONE resident program: the
    stacked [X | w] stream batches (nb, rows, d+1) live in HBM (the device
    epoch cache's contents staged once) and each epoch's inner loop
    dynamic-slices batch partials in replay order — the same sequential
    `sums + s` fold the host-driven loop performs, so centroids and counts
    are bit-identical to it (the `optimization_barrier` materializes the
    column views exactly as the per-batch staging path does). Requires
    every batch bucketed to the SAME row count; ragged streams fall back
    to the host-driven loop (dispatch.whole_fit_plan)."""
    nb, _, dp1 = packed_all.shape
    d = dp1 - 1
    k = init_centroids.shape[0]

    def batch_step(bi, acc):
        sums, counts, centroids = acc
        batch = lax.dynamic_index_in_dim(packed_all, bi, 0, False)
        Xb, wb = lax.optimization_barrier((batch[:, :d], batch[:, d]))
        s, c = _accumulate_batch_impl(Xb, wb, centroids, measure_name)
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
        centroids = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30), centroids
        )
        return centroids, counts

    return lax.fori_loop(
        start_epoch, max_iter, epoch_step, (init_centroids, init_counts)
    )


_lloyd_stream_whole_fit = lazy_jit(
    _lloyd_stream_whole_fit_impl, static_argnames=("measure_name",)
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


@partial(lazy_jit, static_argnames=("n_pad", "sharding"))
def _stage_points(X, n_pad, sharding):
    """Device-side row padding + sharding for device-born inputs (the
    benchmark generators produce tables in HBM) — no host round trip."""
    if X.shape[0] != n_pad:
        X = jnp.pad(X, [(0, n_pad - X.shape[0]), (0, 0)])
    return jax.lax.with_sharding_constraint(X, sharding)


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
        mesh = mesh_lib.default_mesh()
        X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
        n, d = X.shape
        k = self.get_k()
        if n < k:
            raise ValueError(f"Number of points ({n}) is less than k ({k})")

        # selectRandomCentroids (KMeans.java:310): sample k rows without replacement.
        rng = np.random.RandomState(self.get_seed() % (2**32))
        centroid_idx = rng.choice(n, size=k, replace=False)

        shards = mesh_lib.num_data_shards(mesh)
        n_pad = -(-n // shards) * shards
        mat_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS, None))
        row_sharding = NamedSharding(mesh, P(mesh_lib.DATA_AXIS))
        if isinstance(X, jax.Array):  # device-born: stage entirely in HBM
            X32 = X.astype(jnp.float32) if X.dtype != jnp.float32 else X
            init_centroids = jnp.take(X32, jnp.asarray(centroid_idx), axis=0)
            X_dev = _stage_points(X32, n_pad, mat_sharding)
        else:
            X_host = np.asarray(X, dtype=np.float32)
            init_centroids = jnp.asarray(X_host[centroid_idx])
            X_pad, _ = mesh_lib.pad_to_multiple(X_host, shards)
            X_dev = h2d.stage_to_device(X_pad, mat_sharding)
        w_dev = _unit_weights(n, n_pad, row_sharding)

        from ...obs import tracing
        from ...utils.packing import packed_device_get

        # the Lloyd loop is one on-device while_loop (always maxIter
        # epochs): no per-epoch host boundary exists, so a single
        # `iteration.run` span carries the per-run summary
        from ...parallel import dispatch

        # the staged/padded points, synthesized weights, and gathered init
        # centroids are all fit-owned buffers consumed by the train loop —
        # donate them so Lloyd ping-pongs in the same HBM instead of
        # holding a second copy of the dataset for the whole fit
        from ... import config

        if config.collective_overlap:
            # overlap-scheduled Lloyd: epoch e's centroid-partial reduce
            # rides the chunked collective under epoch e+1's distance
            # matmul (parallel/overlap.py; bit-identical to _lloyd_train)
            from ...parallel import overlap

            def train(X, w, init, max_iter, measure):
                return overlap.overlapped_lloyd_train(
                    mesh, X, w, init, max_iter, measure
                )

        else:
            train = (
                _lloyd_train_donating if dispatch.supports_donation() else _lloyd_train
            )
        # the in-memory Lloyd loop has always been a whole-fit resident
        # program (one dispatch, one packed readback); counted when the
        # mode is on, like the fused SGD paths
        if dispatch.whole_fit_enabled():
            dispatch.account_whole_fit("lloyd")
        with tracing.span(
            "iteration.run", mode="device", epochs=self.get_max_iter()
        ):
            centroids, counts = dispatch.timed_dispatch(
                train,
                X_dev,
                w_dev,
                init_centroids,
                jnp.asarray(self.get_max_iter(), jnp.int32),
                self.get_distance_measure(),
                start=0, end=self.get_max_iter(),
            )

            model = KMeansModel()
            # one packed readback: (centroids, counts) pulled separately
            # would be two blocking readbacks
            host_centroids, host_counts = packed_device_get(centroids, counts)
        model.centroids = np.asarray(host_centroids, dtype=np.float64)
        model.weights = np.asarray(host_counts, dtype=np.float64)
        update_existing_params(model, self)
        return model

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
                s, c = _accumulate_batch(*batch, centroids, measure)
                sums = sums + s
                counts = counts + c
            centroids = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1e-30),
                centroids,
            )
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
