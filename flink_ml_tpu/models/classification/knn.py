"""Knn — k-nearest-neighbors classification by brute force.

TPU-native re-design of classification/knn/Knn.java (model = the cached
training matrix + labels) and KnnModel.java (per-row distance scan +
top-k majority vote). The per-row scan becomes ONE pairwise-distance
matmul (n_test, n_train) on the MXU plus a lax.top_k — the layout the
hardware wants.
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ...api import Estimator, Model
from ...common.param import HasFeaturesCol, HasLabelCol, HasPredictionCol
from ...ops.distance import cross_term
from ...param import IntParam, ParamValidators
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params
from .._linear import is_device_column


class KnnModelParams(HasFeaturesCol, HasPredictionCol):
    K = IntParam("k", "The number of nearest neighbors.", 5, ParamValidators.gt(0))

    def get_k(self) -> int:
        return self.get(self.K)

    def set_k(self, value: int):
        return self.set(self.K, value)


class KnnParams(KnnModelParams, HasLabelCol):
    pass


@lazy_jit
def _gather_labels(labels, idx):
    """Module-level jit (an inline jit would recompile per transform)."""
    return labels[idx]


@partial(lazy_jit, static_argnames=("k",))
def _top_k_indices(X_test, X_train, k):
    """Squared-euclidean pairwise distances -> top-k neighbor indices."""
    t2 = jnp.sum(X_test * X_test, axis=1, keepdims=True)
    r2 = jnp.sum(X_train * X_train, axis=1)[None, :]
    dists = t2 - 2.0 * cross_term(X_test, X_train) + r2
    _, idx = jax.lax.top_k(-dists, k)  # (n_test, k)
    return idx


def _majority_vote(neighbor_labels: np.ndarray) -> np.ndarray:
    """Per-row majority label over (n, k) neighbors, vectorized
    (KnnModel.java voting; ties break to the smallest label value, like
    np.unique + first-argmax). A per-row np.unique loop costs ~30us/row
    on this single-core host — the old transform's dominant term."""
    n, k = neighbor_labels.shape
    S = np.sort(neighbor_labels, axis=1)
    first = np.ones((n, k), dtype=bool)
    first[:, 1:] = S[:, 1:] != S[:, :-1]
    pos = np.arange(k)
    first_pos = np.where(first, pos, k)
    suffix = np.minimum.accumulate(first_pos[:, ::-1], axis=1)[:, ::-1]
    next_first = np.concatenate([suffix[:, 1:], np.full((n, 1), k)], axis=1)
    run_len = np.where(first, next_first - pos, 0)
    best = np.argmax(run_len, axis=1)  # first max = smallest tied label
    return S[np.arange(n), best].astype(np.float64)


class KnnModel(Model, KnnModelParams):
    fusable = False
    fusable_reason = "top-k search runs as its own chunked device driver; the k-neighbor label vote is host-side f64"

    def __init__(self):
        self.features: np.ndarray = None  # (n_train, d)
        self.labels: np.ndarray = None  # (n_train,)

    def set_model_data(self, *inputs: Table) -> "KnnModel":
        (model_data,) = inputs
        self.features = as_dense_matrix(model_data.column("features"))
        self.labels = np.asarray(model_data.column("labels"), dtype=np.float64)
        return self

    def get_model_data(self) -> List[Table]:
        return [Table({"features": self.features, "labels": self.labels})]

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
        k = min(self.get_k(), self.features.shape[0])
        idx_dev = _top_k_indices(
            jnp.asarray(X, jnp.float32), jnp.asarray(self.features, jnp.float32), k
        )
        # single readback either way; never pack int32 indices with float
        # labels (float32 promotion corrupts indices above 2**24)
        from ...utils.packing import packed_device_get

        if is_device_column(self.labels):
            neighbor_labels = packed_device_get(
                _gather_labels(jnp.asarray(self.labels), idx_dev),
                sync_kind="transform",
            )[0].astype(np.float64)
        else:
            neighbor_labels = np.asarray(self.labels, dtype=np.float64)[
                packed_device_get(idx_dev, sync_kind="transform")[0]
            ]
        pred = _majority_vote(neighbor_labels)
        return [table.with_column(self.get_prediction_col(), pred)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, features=self.features, labels=self.labels)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        arrays = read_write.load_arrays_or_reference(path, javacodec.load_reference_knn)
        self.features, self.labels = arrays["features"], arrays["labels"]


class Knn(Estimator, KnnParams):
    checkpointable = False
    checkpoint_reason = "fit materializes the training set as the model (no iterations); a restart recomputes the repack"
    def fit(self, *inputs: Table) -> KnnModel:
        """Packs the training set as the model (Knn.java) — lazily: device
        columns stay device-resident (no D2H pull at fit; transform's
        packed readback and save's materialization pay it if ever needed)."""
        (table,) = inputs
        model = KnnModel()
        model.features = as_dense_matrix(
            table.column(self.get_features_col()), allow_device=True
        )
        labels = table.column(self.get_label_col())
        model.labels = (
            labels if is_device_column(labels) else np.asarray(labels, dtype=np.float64)
        )
        update_existing_params(model, self)
        return model
