"""LogisticRegression — binary logistic classifier trained with distributed SGD.

TPU-native re-design of classification/logisticregression/
LogisticRegression.java:60 and LogisticRegressionModel.java:64,131-168.
Training runs the shared SGD engine (ops/optimizer.py) as one XLA
while-loop over the device mesh; inference is a single jitted
matvec+sigmoid over the whole table instead of a per-row broadcast-model
map function.

Sparse (SparseBatch) features train on the padded-CSR path without
densifying, and when the active mesh carries a `model` axis
(`parallel.mesh.create_mesh_2d`) the fit runs feature-sharded on the
true 2D (data × model) layout: the coefficient and optimizer carries
live as model-axis slices, so a Criteo-scale dim whose replicated
residency exceeds `config.hbm_budget_bytes` still trains (see
docs/performance.md "2D mesh").
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ...api import Estimator, Model
from ...common.param import (
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasMultiClass,
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasTol,
    HasWeightCol,
)
from ...ops.losses import BINARY_LOGISTIC_LOSS
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params
from .. import _linear


class LogisticRegressionModelParams(
    HasFeaturesCol, HasPredictionCol, HasRawPredictionCol
):
    pass


class LogisticRegressionParams(
    LogisticRegressionModelParams,
    HasLabelCol,
    HasWeightCol,
    HasMaxIter,
    HasReg,
    HasElasticNet,
    HasLearningRate,
    HasGlobalBatchSize,
    HasTol,
    HasMultiClass,
):
    pass


@lazy_jit
def _predict_from_dot(dot):
    """dot >= 0 -> label 1; rawPrediction = [1-p, p], p = sigmoid(dot)
    (LogisticRegressionModel.predictOneDataPoint:165-168)."""
    prob = 1.0 - 1.0 / (1.0 + jnp.exp(dot))
    pred = jnp.where(dot >= 0, 1.0, 0.0)
    raw = jnp.stack([1.0 - prob, prob], axis=1)
    return pred, raw


@lazy_jit
def _predict(X, coeff):
    return _predict_from_dot(X @ coeff)


class LogisticRegressionModel(Model, LogisticRegressionModelParams):
    fusable = True
    kernel_supports_sparse = True

    def __init__(self):
        self.coefficient: np.ndarray = None  # (d,)

    def _constant_sources(self):
        return (self.coefficient,)

    def _kernel_constants(self):
        return {"coefficient": np.asarray(self.coefficient, np.float32)}

    def transform_kernel(self, consts, cols, ctx):
        dot = _linear.raw_scores(cols[self.get_features_col()], consts["coefficient"])
        pred, raw = _predict_from_dot(dot)
        cols[self.get_prediction_col()] = pred
        cols[self.get_raw_prediction_col()] = raw
        return cols

    def set_model_data(self, *inputs: Table) -> "LogisticRegressionModel":
        (model_data,) = inputs
        rows = model_data.collect()
        self.coefficient = np.asarray(rows[0]["coefficient"].to_array(), dtype=np.float64)
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        return [Table({"coefficient": [DenseVector(self.coefficient)]})]

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        col = table.column(self.get_features_col())
        from ...table import SparseBatch

        def _coeff(device_in: bool):
            # both input paths share the memoized publication upload
            # (the ledgered `model` funnel) instead of a fresh
            # unaccounted jnp.asarray upload per host-input call
            return self.device_constants()["coefficient"]

        if isinstance(col, SparseBatch):  # wide sparse: never densify
            device_in = isinstance(col.indices, jax.Array)
            dot = _linear.raw_scores(col, _coeff(device_in))
            pred, raw = _predict_from_dot(dot)
        else:
            X = as_dense_matrix(col, allow_device=True)
            device_in = isinstance(X, jax.Array)
            pred, raw = _predict(jnp.asarray(X, jnp.float32), _coeff(device_in))
        if device_in:  # device data in -> device predictions out, no D2H
            cols = {self.get_prediction_col(): pred, self.get_raw_prediction_col(): raw}
        else:
            from ...utils.packing import packed_device_get

            # one packed, accounted readback (two np.asarray pulls would
            # each be their own blocking readback)
            pred_h, raw_h = packed_device_get(pred, raw, sync_kind="transform")
            cols = {
                self.get_prediction_col(): pred_h.astype(np.float64),
                self.get_raw_prediction_col(): raw_h.astype(np.float64),
            }
        return [table.with_columns(cols)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, coefficient=self.coefficient)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        loaded = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_logisticregression
        )
        self.coefficient = (
            loaded["coefficient"] if isinstance(loaded, dict) else loaded[0]
        )


class LogisticRegression(Estimator, LogisticRegressionParams):
    """Estimator (LogisticRegression.java:60)."""
    # SGD fit routes through run_sgd -> JobSnapshot checkpoints
    checkpointable = True

    def fit(self, *inputs: Table) -> LogisticRegressionModel:
        (table,) = inputs
        if self.get_multi_class() == "multinomial":
            raise ValueError(
                "Multinomial classification is not supported yet. "
                "Supported options: [auto, binomial]."
            )
        coeff, _, _ = _linear.run_sgd(
            self, table, BINARY_LOGISTIC_LOSS, self.get_weight_col(),
            validate_binomial=True,
        )
        model = LogisticRegressionModel()
        model.coefficient = coeff
        update_existing_params(model, self)
        return model
