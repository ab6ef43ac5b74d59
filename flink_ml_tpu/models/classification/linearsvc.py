"""LinearSVC — linear support vector classifier trained with distributed SGD.

TPU-native re-design of classification/linearsvc/LinearSVC.java,
LinearSVCModel.java:137-173 and LinearSVCModelParams.java:36-52 (hinge loss
+ threshold on the raw dot value; rawPrediction = [dot, -dot]).
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
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasTol,
    HasWeightCol,
)
from ...ops.losses import HINGE_LOSS
from ...param import FloatParam
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params
from .. import _linear


class LinearSVCModelParams(HasFeaturesCol, HasPredictionCol, HasRawPredictionCol):
    THRESHOLD = FloatParam(
        "threshold",
        "Threshold in binary classification prediction applied to rawPrediction.",
        0.0,
    )

    def get_threshold(self) -> float:
        return self.get(self.THRESHOLD)

    def set_threshold(self, value: float):
        return self.set(self.THRESHOLD, value)


class LinearSVCParams(
    LinearSVCModelParams,
    HasLabelCol,
    HasWeightCol,
    HasMaxIter,
    HasReg,
    HasElasticNet,
    HasLearningRate,
    HasGlobalBatchSize,
    HasTol,
):
    pass


@lazy_jit
def _predict_from_dot(dot, threshold):
    """prediction = dot >= threshold ? 1 : 0; rawPrediction = [dot, -dot]
    (LinearSVCModel.predictOneDataPoint:170-173)."""
    pred = jnp.where(dot >= threshold, 1.0, 0.0)
    raw = jnp.stack([dot, -dot], axis=1)
    return pred, raw


@lazy_jit
def _predict(X, coeff, threshold):
    return _predict_from_dot(X @ coeff, threshold)


class LinearSVCModel(Model, LinearSVCModelParams):
    fusable = True
    kernel_supports_sparse = True

    def __init__(self):
        self.coefficient: np.ndarray = None  # (d,)

    def _constant_sources(self):
        return (self.coefficient,)

    def _kernel_constants(self):
        return {
            "coefficient": np.asarray(self.coefficient, np.float32),
            "threshold": np.float32(self.get_threshold()),
        }

    def transform_kernel(self, consts, cols, ctx):
        from .. import _linear

        dot = _linear.raw_scores(cols[self.get_features_col()], consts["coefficient"])
        pred, raw = _predict_from_dot(dot, consts["threshold"])
        cols[self.get_prediction_col()] = pred
        cols[self.get_raw_prediction_col()] = raw
        return cols

    def set_model_data(self, *inputs: Table) -> "LinearSVCModel":
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
        from .. import _linear

        device_in = False
        if isinstance(col, SparseBatch):  # wide sparse: never densify
            dot = _linear.raw_scores(col, jnp.asarray(self.coefficient, jnp.float32))
            pred, raw = _predict_from_dot(dot, jnp.asarray(self.get_threshold(), jnp.float32))
            device_in = isinstance(col.indices, jax.Array)
        else:
            X = as_dense_matrix(col, allow_device=True)
            device_in = isinstance(X, jax.Array)
            pred, raw = _predict(
                jnp.asarray(X, jnp.float32),
                jnp.asarray(self.coefficient, jnp.float32),
                jnp.asarray(self.get_threshold(), jnp.float32),
            )
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
            path, javacodec.load_reference_coefficient
        )
        self.coefficient = loaded["coefficient"] if isinstance(loaded, dict) else loaded


class LinearSVC(Estimator, LinearSVCParams):
    """Estimator (LinearSVC.java)."""
    # SGD fit routes through run_sgd -> JobSnapshot checkpoints
    checkpointable = True

    def fit(self, *inputs: Table) -> LinearSVCModel:
        (table,) = inputs
        coeff, _, _ = _linear.run_sgd(
            self, table, HINGE_LOSS, self.get_weight_col(), validate_binomial=True
        )
        model = LinearSVCModel()
        model.coefficient = coeff
        update_existing_params(model, self)
        return model
