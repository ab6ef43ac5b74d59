"""StandardScaler — standardize features by mean removal / std scaling.

TPU-native re-design of feature/standardscaler/StandardScaler.java (mean
and sample std via a distributed `aggregate` of [sum, squaredSum, count];
:121-137) and StandardScalerModel.java:85-131. Here the aggregation is a
jitted column reduction; std uses the same (n-1) sample formula; model
data always stores both mean and std, and withMean/withStd select what is
applied at transform time, as in the reference.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ...api import Estimator, Model
from ...common.param import HasInputCol, HasOutputCol
from ...param import BooleanParam
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params


class StandardScalerParams(HasInputCol, HasOutputCol):
    WITH_MEAN = BooleanParam(
        "withMean", "Whether centers the data with mean before scaling.", False
    )
    WITH_STD = BooleanParam(
        "withStd", "Whether scales the data with standard deviation.", True
    )

    def get_with_mean(self) -> bool:
        return self.get(self.WITH_MEAN)

    def set_with_mean(self, value: bool):
        return self.set(self.WITH_MEAN, value)

    def get_with_std(self) -> bool:
        return self.get(self.WITH_STD)

    def set_with_std(self, value: bool):
        return self.set(self.WITH_STD, value)


@lazy_jit
def _fit_stats(X):
    n = X.shape[0]
    mean = jnp.mean(X, axis=0)
    sq_sum = jnp.sum(X * X, axis=0)
    # sample std with Bessel correction (StandardScaler.java:121-131)
    var = (sq_sum - n * mean * mean) / jnp.maximum(n - 1, 1)
    return mean, jnp.sqrt(jnp.maximum(var, 0.0))


class StandardScalerModel(Model, StandardScalerParams):
    fusable = True

    def __init__(self):
        self.mean: np.ndarray = None
        self.std: np.ndarray = None

    def _constant_sources(self):
        return (self.mean, self.std)

    def kernel_static(self):
        return ()  # mean and scale are operands of the program

    def _kernel_constants(self):
        # scale derived in host f64 exactly as the eager path computes it
        return {"mean": self.mean, "scale": np.where(self.std > 0, self.std, 1.0)}

    def transform_kernel(self, consts, cols, ctx):
        from ...api import as_kernel_matrix

        out = as_kernel_matrix(cols[self.get_input_col()])
        if self.get_with_mean():
            out = out - consts["mean"]
        if self.get_with_std():
            out = out / consts["scale"]
        cols[self.get_output_col()] = out
        return cols

    def set_model_data(self, *inputs: Table) -> "StandardScalerModel":
        (model_data,) = inputs
        row = model_data.collect()[0]
        self.mean = np.asarray(row["mean"].to_array(), dtype=np.float64)
        self.std = np.asarray(row["std"].to_array(), dtype=np.float64)
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        return [Table({"mean": [DenseVector(self.mean)], "std": [DenseVector(self.std)]})]

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_input_col()), allow_device=True)
        if isinstance(X, jax.Array):
            # device path: memoized device-resident constants — repeated
            # transforms stop re-uploading mean/scale every call
            consts = self.device_constants()
            mean, scale = consts["mean"], consts["scale"]
        else:
            mean, scale = self.mean, np.where(self.std > 0, self.std, 1.0)
        out = X
        if self.get_with_mean():
            out = out - mean
        if self.get_with_std():
            out = out / scale
        return [table.with_column(self.get_output_col(), out)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, mean=self.mean, std=self.std)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        arrays = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_standardscaler
        )
        self.mean, self.std = arrays["mean"], arrays["std"]


class StandardScaler(Estimator, StandardScalerParams):
    checkpointable = False
    checkpoint_reason = "single-pass moment aggregation; a restart recomputes the fit"
    def fit(self, *inputs: Table) -> StandardScalerModel:
        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_input_col()), allow_device=True)
        mean, std = _fit_stats(jnp.asarray(X))
        from ...utils.packing import packed_device_get

        host_mean, host_std = packed_device_get(mean, std)
        model = StandardScalerModel()
        model.mean = np.asarray(host_mean, dtype=np.float64)
        model.std = np.asarray(host_std, dtype=np.float64)
        update_existing_params(model, self)
        return model
