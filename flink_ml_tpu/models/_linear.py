"""Shared machinery for linear-model estimators (LogisticRegression,
LinearSVC, LinearRegression): train-data extraction, SGD wiring, and the
broadcast-model batched predict path.

Reference pattern: each linear estimator maps rows to LabeledPointWithWeight
(classification/logisticregression/LogisticRegression.java:70-92), derives
the init model from the feature dimension (:94-105), runs common SGD
(:107-114), and its Model broadcasts the coefficient and maps rows
(LogisticRegressionModel.java:64,131). Here train data is columnar and
already batched; the model coefficient is a device array applied with one
matvec per table.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import tracing
from ..ops.losses import LossFunc, sparse_variant
from ..utils.lazyjit import lazy_jit
from ..ops.optimizer import SGD, read_train_result
from ..table import SparseBatch, Table, as_dense_matrix


def extract_train_data(
    table: Table,
    features_col: str,
    label_col: Optional[str],
    weight_col: Optional[str],
    keep_sparse: bool = False,
) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
    """With `keep_sparse`, a SparseBatch features column stays sparse and is
    returned as the (indices, values, dim) triple the SGD engine trains on
    natively — a wide (Criteo-dim) model would not fit densified."""
    col = table.column(features_col)
    if keep_sparse and isinstance(col, SparseBatch):
        X = (col.indices, col.values, col.size)
    else:
        X = as_dense_matrix(col, allow_device=True)
    y = None
    if label_col is not None:
        y = _as_host_or_device_vector(table.column(label_col))
    w = None
    if weight_col is not None:
        w = _as_host_or_device_vector(table.column(weight_col))
    return X, y, w


def _as_host_or_device_vector(col):
    """Device-resident columns stay on device; host columns become float64
    numpy (the SGD engine casts once to its compute dtype on transfer)."""
    import jax

    if isinstance(col, jax.Array):
        return col
    return np.asarray(col, dtype=np.float64)


def run_sgd(
    params,
    table,
    loss_func: LossFunc,
    weight_col: Optional[str],
    validate_binomial: bool = False,
):
    """Wire a Has*-param stage into the SGD optimizer; returns
    (coefficient, final_loss, num_epochs). Checkpoint/resume follows the
    process-wide `config.iteration_checkpoint_dir`.

    A bounded `Table` trains in-memory/device-resident; a `StreamTable`
    trains out-of-core through the native spillable data cache
    (cache-then-replay, the ReplayOperator contract — SGD.optimize_stream)
    with an identical batch schedule, so both paths produce the same
    coefficients for the same data."""
    from ..table import StreamTable

    if isinstance(table, StreamTable):
        chunks = _stream_chunks(
            table,
            params.get_features_col(),
            params.get_label_col(),
            weight_col,
            validate_binomial,
        )
        coeff, loss, epochs, _ = _optimizer_for(params).optimize_stream(
            None, chunks, loss_func
        )
        return coeff, loss, epochs
    with tracing.phase("fit.extract"):
        optimizer = _optimizer_for(params)
        X, y, w = extract_train_data(
            table, params.get_features_col(), params.get_label_col(), weight_col,
            keep_sparse=True,
        )
        validate_on_device = False
        if validate_binomial:
            if isinstance(y, jax.Array):
                # device labels: the {0,1} validity check is computed INSIDE the
                # training program and read back fused with the packed training
                # result — a standalone bool() here would cost its own host
                # round trip before training even starts
                validate_on_device = True
            else:
                validate_binomial_labels(y)
        if isinstance(X, tuple):  # sparse: train on padded CSR, no densify
            indices, values, dim = X
            X = (indices, values)
            loss_func = sparse_variant(loss_func.name)
            # a mesh with a model axis declares the feature-sharded intent:
            # wide sparse estimator fits take the 2D (data × model) layout
            # automatically (coeff + optimizer carries as model-axis slices,
            # see ops.optimizer.SGD._use_2d / docs/performance.md "2D mesh")
            from ..parallel import mesh as mesh_lib

            optimizer.shard_features = (
                mesh_lib.MODEL_AXIS in mesh_lib.default_mesh().axis_names
            )
            # the model starts at zero where its table lives, in the engine's
            # own dtype: a one-hot model is tens of millions wide, and zeros
            # made on the host would be cast and uploaded before every fit
            on_device = isinstance(indices, jax.Array) and not optimizer.shard_features
            init_coeff = (jnp if on_device else np).zeros(dim, dtype=optimizer.dtype)
        else:
            # a dense model is narrow: its zeros go up with the launch
            init_coeff = np.zeros(X.shape[1], dtype=optimizer.dtype)
    result = optimizer.optimize_async(
        init_coeff, X, y, w, loss_func, validate_labels=validate_on_device
    )
    flag_val, coeff, criteria, epochs = read_train_result(result)
    _raise_if_invalid(flag_val)
    return coeff, criteria, epochs


def _optimizer_for(params) -> SGD:
    """The SGD engine with a Has*-param stage's hyperparameters."""
    from .. import config
    from ..parallel.iteration import checkpoint_job_key

    return SGD(
        max_iter=params.get_max_iter(),
        learning_rate=params.get_learning_rate(),
        global_batch_size=params.get_global_batch_size(),
        tol=params.get_tol(),
        reg=params.get_reg(),
        elastic_net=params.get_elastic_net(),
        checkpoint_dir=config.iteration_checkpoint_dir,
        checkpoint_interval=config.iteration_checkpoint_interval,
        # namespace the shared checkpoint dir per estimator identity so two
        # different jobs can no longer silently cross-restore
        checkpoint_key=(
            checkpoint_job_key(params)
            if config.iteration_checkpoint_dir is not None
            else None
        ),
    )


@lazy_jit
def sparse_raw_scores(indices, values, coeff):
    """Per-row dot of padded-CSR features with the coefficient — the sparse
    inference hot loop (LogisticRegressionModel.java:131), sharing the
    masking convention with the training losses via losses.sparse_dot."""
    from ..ops.losses import sparse_dot

    dot, _, _ = sparse_dot(indices, values, coeff)
    return dot


def raw_scores(col, coeff):
    """X @ coeff for any features layout (dense host/device, SparseBatch) —
    wide sparse batches are never densified."""
    if isinstance(col, SparseBatch):
        return sparse_raw_scores(
            jnp.asarray(col.indices), jnp.asarray(col.values), coeff
        )
    X = as_dense_matrix(col, allow_device=True)
    return jnp.asarray(X, coeff.dtype) @ coeff


def is_device_column(col) -> bool:
    """True when a features column is device-resident — transforms follow
    the device-in -> device-out convention (no forced D2H readback)."""
    if isinstance(col, SparseBatch):
        return isinstance(col.indices, jax.Array)
    return isinstance(col, jax.Array)


@lazy_jit
def _labels_ok(y):
    """Device-side {0,1} label check (LogisticRegression.java:78-87)."""
    return jnp.all((y == 0.0) | (y == 1.0)).astype(jnp.float32)


def _raise_if_invalid(flag) -> None:
    if flag is not None and not bool(flag):
        raise ValueError(
            "Multinomial classification is not supported yet. "
            "Supported options: [auto, binomial]."
        )


def _stream_chunks(stream, features_col, label_col, weight_col, validate_binomial):
    """Yield (X, y, w) host chunks from a StreamTable's mini-batch Tables,
    validating labels per batch when asked."""
    for batch in stream:
        X, y, w = extract_train_data(batch, features_col, label_col, weight_col)
        if validate_binomial:
            validate_binomial_labels(y)
        yield np.asarray(X), np.asarray(y), None if w is None else np.asarray(w)


def validate_binomial_labels(y) -> None:
    """The reference only supports {0, 1} labels for binary linear
    classifiers (LogisticRegression.java:78-87). Device-resident labels are
    validated on device (one scalar readback, no bulk transfer)."""
    if isinstance(y, jax.Array):
        from ..utils.packing import packed_device_get

        ok = bool(packed_device_get(_labels_ok(y), sync_kind="fit")[0])
    else:
        ok = bool(np.all((y == 0.0) | (y == 1.0)))
    _raise_if_invalid(ok)
