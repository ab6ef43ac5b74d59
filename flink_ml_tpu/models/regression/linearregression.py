"""LinearRegression — least-squares linear model trained with distributed SGD.

TPU-native re-design of regression/linearregression/LinearRegression.java:48
and LinearRegressionModel.java:146-160. Shares the SGD engine with the other
linear models; inference is one jitted matvec over the whole table
(predictOneDataPoint's per-row BLAS.dot becomes an MXU matmul).
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
    HasReg,
    HasTol,
    HasWeightCol,
)
from ...ops.losses import LEAST_SQUARE_LOSS
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.param_utils import update_existing_params
from .. import _linear


class LinearRegressionModelParams(HasFeaturesCol, HasPredictionCol):
    pass


class LinearRegressionParams(
    LinearRegressionModelParams,
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


class LinearRegressionModel(Model, LinearRegressionModelParams):
    fusable = True
    kernel_supports_sparse = True

    def __init__(self):
        self.coefficient: np.ndarray = None  # (d,)

    def _constant_sources(self):
        return (self.coefficient,)

    def _kernel_constants(self):
        # f32 to match the eager path's jnp.asarray(coeff, float32) under
        # either x64 setting
        return {"coefficient": np.asarray(self.coefficient, np.float32)}

    def transform_kernel(self, consts, cols, ctx):
        from .. import _linear

        col = cols[self.get_features_col()]
        cols[self.get_prediction_col()] = _linear.raw_scores(
            col, consts["coefficient"]
        )
        return cols

    def set_model_data(self, *inputs: Table) -> "LinearRegressionModel":
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
        from .. import _linear

        # both input paths share the memoized publication upload (the
        # ledgered `model` funnel) instead of a fresh unaccounted
        # jnp.asarray upload per host-input call
        coeff = self.device_constants()["coefficient"]
        pred = _linear.raw_scores(col, coeff)
        # device in -> device out (the LR/SVC convention): materializing
        # here would pull the whole prediction vector to the host
        if not _linear.is_device_column(col):
            from ...utils.packing import packed_device_get

            # one packed, accounted readback (np.asarray was a silent pull)
            (pred_h,) = packed_device_get(pred, sync_kind="transform")
            pred = pred_h.astype(np.float64)
        return [table.with_column(self.get_prediction_col(), pred)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, coefficient=self.coefficient)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        loaded = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_coefficient
        )
        self.coefficient = loaded["coefficient"] if isinstance(loaded, dict) else loaded


class LinearRegression(Estimator, LinearRegressionParams):
    """Estimator (LinearRegression.java:48)."""
    # SGD fit routes through run_sgd -> JobSnapshot checkpoints
    checkpointable = True

    def fit(self, *inputs: Table) -> LinearRegressionModel:
        (table,) = inputs
        coeff, _, _ = _linear.run_sgd(
            self, table, LEAST_SQUARE_LOSS, self.get_weight_col()
        )
        model = LinearRegressionModel()
        model.coefficient = coeff
        update_existing_params(model, self)
        return model
