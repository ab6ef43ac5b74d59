"""OnlineKMeans — streaming k-means with decayed centroid updates.

TPU-native re-design of clustering/kmeans/OnlineKMeans.java:44-60 and
OnlineKMeansModel.java:166. The reference runs an unbounded iteration whose
feedback edge carries model data and batches points with
countWindowAll(globalBatchSize); here the unbounded input is a StreamTable
of mini-batch Tables driven by the host loop (parallel/iteration.py
iterate_unbounded), and each batch update is one jitted
assign+segment-sum step. Update rule per batch (ModelDataLocalUpdater):
new centroid = weighted average of (decayed old centroid, batch mean);
new weight = decayFactor * old weight + batch count. Each processed batch
publishes a new model version (the reference's modelDataVersion gauge).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...api import Estimator, KernelContext, Model, as_kernel_matrix
from ...common.param import (
    HasBatchStrategy,
    HasDecayFactor,
    HasDistanceMeasure,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasPredictionCol,
    HasSeed,
)
from ...ops.distance import DistanceMeasure, jit_find_closest
from ...parallel import prefetch as h2d
from ...parallel.iteration import iterate_unbounded
from ...table import StreamTable, Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params
from .._online import OnlineUpdates, kernel_constant, on_device, on_host, published, weakly
from .kmeans import KMeansModelParams


def generate_random_model_data(k: int, dim: int, weight: float, seed: int = 0) -> Table:
    """KMeansModelData.generateRandomModelData: random N(0,1) centroids."""
    from ...linalg import DenseVector

    rng = np.random.RandomState(seed % (2**32))
    centroids = rng.standard_normal((k, dim))
    return Table(
        {
            "centroids": [[DenseVector(c) for c in centroids]],
            "weights": [DenseVector(np.full(k, weight))],
        }
    )


class OnlineKMeansParams(
    KMeansModelParams, HasBatchStrategy, HasGlobalBatchSize, HasDecayFactor, HasSeed
):
    pass


def _extract_model_data(table: Table):
    """(centroids (k, d), weights (k,)) from a KMeansModelData-shaped table,
    tolerating both vector-list and stacked-array column layouts."""
    row = table.collect()[0]
    c = row["centroids"]
    if isinstance(c, np.ndarray) and c.ndim == 2:
        centroids = np.asarray(c, dtype=np.float64)
    else:
        centroids = np.stack(
            [np.asarray(v.to_array() if hasattr(v, "to_array") else v, dtype=np.float64) for v in c]
        )
    w = row["weights"]
    weights = np.asarray(w.to_array() if hasattr(w, "to_array") else w, dtype=np.float64)
    return centroids, weights


from functools import partial


@partial(lazy_jit, static_argnames=("measure_name",))
def _batch_update(centroids, weights, X, decay, measure_name):
    measure = DistanceMeasure.get_instance(measure_name)
    assign = measure.find_closest(X, centroids)
    one_hot = jax.nn.one_hot(assign, centroids.shape[0], dtype=X.dtype)
    counts = one_hot.sum(axis=0)
    sums = one_hot.T @ X
    batch_means = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-16), centroids)
    decayed = weights * decay
    new_centroids = (
        centroids * decayed[:, None] + batch_means * counts[:, None]
    ) / jnp.maximum(decayed + counts, 1e-16)[:, None]
    return new_centroids, decayed + counts


class _PublishedKMeans(NamedTuple):
    """One immutable published model version. The ONLY mutable serving
    state of `OnlineKMeansModel` is the single `_published` reference to
    an instance of this — publication is one atomic assignment, so a
    reader (serve thread) that grabbed the reference keeps a consistent
    (version, centroids, weights) triple no matter how many swaps the
    trainer thread lands meanwhile. Torn (new centroids, old weights)
    states are unrepresentable. The arrays are kept on the side they were
    born on (`_online`, the one record form of the online models): the
    training loop's are device arrays, fresh ones every batch."""

    version: int
    centroids: Any  # float64 numpy, or the training loop's device arrays
    weights: Any


class OnlineKMeansModel(OnlineUpdates, Model, KMeansModelParams):
    """Serves predictions from the latest model version
    (OnlineKMeansModel.java; `model_version` mirrors the modelDataVersion
    gauge). Serves through the FUSED pipeline path: the centroid tensor is
    a versioned runtime operand of the compiled plan (not a baked
    constant), so a live `set_model_data`/`publish_model_arrays` is a
    zero-pause, zero-recompile pointer swap between batches — the
    reference's modelDataVersion publication contract on device
    (docs/model_lifecycle.md)."""
    fusable = True
    swap_capable = True

    def __init__(self):
        self._published = _PublishedKMeans(0, None, None)

    # -- atomic publication --------------------------------------------------
    # centroids/weights/model_version stay as attributes for API compat,
    # but all three read/write the ONE `_published` record; a device record
    # is read back when the host asks for it, not when it is published.
    @property
    def centroids(self) -> Optional[np.ndarray]:
        return on_host(self._published.centroids)

    @centroids.setter
    def centroids(self, value) -> None:
        pub = self._published
        self._publish(value, pub.weights, pub.version)

    @property
    def weights(self) -> Optional[np.ndarray]:
        return on_host(self._published.weights)

    @weights.setter
    def weights(self, value) -> None:
        pub = self._published
        self._publish(pub.centroids, value, pub.version)

    @property
    def model_version(self) -> int:
        return self._published.version

    @model_version.setter
    def model_version(self, value: int) -> None:
        pub = self._published
        self._publish(pub.centroids, pub.weights, int(value))

    def _publish(self, centroids, weights, version: int) -> None:
        self._published = _PublishedKMeans(int(version), published(centroids), published(weights))
        self.bump_model_data_version()

    def _publish_state(self, version: int, state) -> None:
        centroids, weights = state
        self._publish(centroids, weights, version)

    def model_arrays(self) -> tuple:
        pub = self._published
        return (pub.centroids, pub.weights)

    def publish_model_arrays(self, arrays: tuple, version: int) -> None:
        centroids, weights = arrays
        self._publish(centroids, weights, version)

    def set_model_data(self, *inputs) -> "OnlineKMeansModel":
        if len(inputs) == 1 and isinstance(inputs[0], Table):
            centroids, weights = _extract_model_data(inputs[0])
            self._publish(centroids, weights, self._published.version)
            return self
        (stream,) = inputs
        self._take_stream(stream)
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        pub = self._published  # one record read: centroids and weights of one version
        return [
            Table(
                {
                    "centroids": [[DenseVector(c) for c in on_host(pub.centroids)]],
                    "weights": [DenseVector(on_host(pub.weights))],
                }
            )
        ]

    # -- fused transform kernel (versioned runtime operand) ------------------
    def _kernel_constants(self) -> Dict[str, Any]:
        pub = self._published  # ONE record read: consts are version-consistent
        return self.kernel_constants_for((pub.centroids, pub.weights), pub.version)

    def kernel_constants_for(self, arrays: tuple, version: int = 0) -> Dict[str, Any]:
        centroids, _ = arrays
        # f32 cast mirrors the eager serve path (jnp.asarray(..., float32));
        # a device record stays where it is
        return {"centroids": kernel_constant(centroids)}

    def _constant_sources(self) -> tuple:
        pub = self._published
        return (pub.centroids, pub.weights)

    def kernel_ready(self, cols: Dict[str, Any]) -> bool:
        return self._published.centroids is not None

    def transform_kernel(self, consts, cols: Dict[str, Any], ctx: KernelContext) -> Dict[str, Any]:
        X = as_kernel_matrix(cols[self.get_features_col()])
        measure = DistanceMeasure.get_instance(self.get_distance_measure())
        assign = measure.find_closest(X.astype(jnp.float32), consts["centroids"])
        cols[self.get_prediction_col()] = assign.astype(jnp.int32)
        return cols

    def transform(self, *inputs: Table) -> List[Table]:
        from ... import config

        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_features_col()))
        n = X.shape[0]
        if config.input_bucketing:
            # serving-style shape bucketing: free-running online predict
            # batches pad to the power-of-two schedule (repeat-last-row —
            # real data, guard-safe) so the assignment kernel compiles
            # once per bucket, not once per incoming batch shape; the pad
            # is sliced back off below
            X = h2d.pad_rows(X, n, h2d.next_bucket(n))
        assign = jit_find_closest(self.get_distance_measure())(
            jnp.asarray(X, jnp.float32), jnp.asarray(self._published.centroids, jnp.float32)
        )
        from ...utils.packing import packed_device_get

        assign_h = packed_device_get(assign[:n], sync_kind="transform")[0]
        return [
            table.with_column(
                self.get_prediction_col(), assign_h.astype(np.int32)
            )
        ]

    def _save_extra(self, path: str) -> None:
        pub = self._published
        read_write.save_model_arrays(
            path, centroids=on_host(pub.centroids), weights=on_host(pub.weights),
            modelVersion=np.int64(pub.version),
        )

    def _load_extra(self, path: str) -> None:
        arrays = read_write.load_model_arrays(path)
        self.centroids = arrays["centroids"]
        self.weights = arrays["weights"]
        self.model_version = int(arrays.get("modelVersion", 0))


class OnlineKMeans(Estimator, OnlineKMeansParams):
    """Estimator (OnlineKMeans.java:44-60). Requires initial model data —
    from batch KMeans or `generate_random_model_data`."""
    # unbounded fit snapshots (state, stream offset) per global batch
    # through iterate_unbounded -> JobSnapshot
    checkpointable = True

    def __init__(self):
        self._initial_model_data: Optional[Table] = None

    def set_initial_model_data(self, model_data: Table) -> "OnlineKMeans":
        self._initial_model_data = model_data
        return self

    def fit(self, *inputs) -> OnlineKMeansModel:
        (stream,) = inputs
        if not isinstance(stream, StreamTable):
            raise TypeError("OnlineKMeans.fit expects a StreamTable")
        if self._initial_model_data is None:
            raise ValueError("OnlineKMeans requires initial model data")
        centroids, weights = _extract_model_data(self._initial_model_data)
        decay = self.get_decay_factor()
        features_col = self.get_features_col()
        batch_size = self.get_global_batch_size()

        def rebatch(batches) -> Iterator[np.ndarray]:
            """countWindowAll(globalBatchSize): regroup incoming rows into
            exact global batches."""
            buffer: List[np.ndarray] = []
            buffered = 0
            for batch in batches:
                X = as_dense_matrix(batch.column(features_col))
                buffer.append(X)
                buffered += X.shape[0]
                while buffered >= batch_size:
                    all_rows = np.concatenate(buffer)
                    yield all_rows[:batch_size]
                    rest = all_rows[batch_size:]
                    buffer = [rest] if rest.size else []
                    buffered = rest.shape[0] if rest.size else 0

        measure_name = self.get_distance_measure()

        def step(state, X) -> Tuple[jnp.ndarray, jnp.ndarray]:
            if not on_device(state[0]):  # a state restored from a checkpoint
                state = stage_state(state)
            return _batch_update(*state, X, decay_dev, measure_name)

        def stage_state(state):
            """(centroids, weights) uploaded once, float32."""
            return h2d.stage_to_device(
                tuple(np.asarray(a, dtype=np.float32) for a in state), category="online.state"
            )

        decay_dev = jnp.asarray(decay)

        from ... import config
        from ...parallel.iteration import checkpoint_job_key

        # shared input stager: one worker thread uploads global batch b+1
        # (accounted, h2d.*) while batch b's update step runs — the
        # micro-batch H2D leaves the critical path between steps. The
        # window is a flow.BoundedChannel under config.
        # online_overload_policy: "block" (default) is lossless
        # backpressure; "shed_oldest" keeps memory AND model staleness
        # bounded when the stream outruns the update step (sheds/lag
        # tracked as flow.shed / flow.lag.online.ingest).
        staged = h2d.Prefetcher(
            h2d.stage_to_device,
            policy=config.online_overload_policy,
            name="online.ingest",
        ).iterate(rebatch(stream))
        model = OnlineKMeansModel()
        model.centroids = centroids
        model.weights = weights
        model._follow(
            iterate_unbounded(
                staged,
                step,
                stage_state((centroids, weights)),
                job_key=checkpoint_job_key(self),
                publish=weakly(model._publish_state),
            )
        )
        update_existing_params(model, self)
        return model
