"""OnlineLogisticRegression — streaming binary classifier trained with
FTRL-Proximal.

TPU-native re-design of classification/logisticregression/
OnlineLogisticRegression.java (FtrlIterationBody: l1 = elasticNet*reg,
l2 = (1-elasticNet)*reg; CalculateLocalGradient: per-dim gradient mean
g[i] = sum((p - y) * x[i]) / count_nonzero[i]; UpdateModel: the
tf.keras-style FTRL z/n update) and OnlineLogisticRegressionModel.java:133
(modelDataVersion gauge, modelVersionCol output). Each global batch is one
jitted gradient + FTRL step; versions publish per batch through the
host-driven unbounded loop.

The state (w, z, n) is float32 on the device from the first batch on, and a
published version is a record of the device coefficient: nothing is read back
a batch. A dense features column is folded by `_ftrl_step`, a sweep of all
`d` coordinates; a `SparseBatch` column by `_ftrl_touched`, padded CSR end to
end, which updates the coordinates the batch holds and leaves every other
w, z, n as it is, to the bit (McMahan et al., KDD 2013, Algorithm 1: "for all
i in I"; with g = 0 the sweep recomputes what it read). `ftrl.slots_updated`
says which of the two ran.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...api import Estimator, KernelContext, Model, as_kernel_matrix
from ...common.param import (
    HasBatchStrategy,
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasModelVersionCol,
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasWeightCol,
)
from ...ops import slot_write
from ...ops.losses import sparse_dot
from ...param import DoubleParam, ParamValidators
from ...parallel.iteration import iterate_unbounded
from ...table import SparseBatch, StreamTable, Table, as_dense_matrix
from ...utils import read_write
from ...utils.lazyjit import lazy_jit
from ...utils.param_utils import update_existing_params
from .._online import OnlineUpdates, kernel_constant, on_device, on_host, published, weakly


class OnlineLogisticRegressionModelParams(
    HasFeaturesCol, HasPredictionCol, HasRawPredictionCol, HasModelVersionCol
):
    pass


class OnlineLogisticRegressionParams(
    OnlineLogisticRegressionModelParams,
    HasLabelCol,
    HasWeightCol,
    HasBatchStrategy,
    HasGlobalBatchSize,
    HasReg,
    HasElasticNet,
):
    ALPHA = DoubleParam("alpha", "The alpha parameter of ftrl.", 0.1, ParamValidators.gt(0.0))
    BETA = DoubleParam("beta", "The beta parameter of ftrl.", 0.1, ParamValidators.gt(0.0))

    def get_alpha(self) -> float:
        return self.get(self.ALPHA)

    def set_alpha(self, value: float):
        return self.set(self.ALPHA, value)

    def get_beta(self) -> float:
        return self.get(self.BETA)

    def set_beta(self, value: float):
        return self.set(self.BETA, value)


def _ftrl_proximal(coeff, z, n, g, alpha, beta, l1, l2):
    """The FTRL-Proximal update of (w, z, n) by the gradient g, elementwise
    (OnlineLogisticRegression.UpdateModel.processElement)."""
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
    z = z + g - sigma * coeff
    n = n + g * g
    new_coeff = jnp.where(
        jnp.abs(z) <= l1,
        0.0,
        (jnp.sign(z) * l1 - z) / ((beta + jnp.sqrt(n)) / alpha + l2),
    )
    return new_coeff, z, n


@lazy_jit
def _ftrl_step(coeff, z, n, X, y, alpha, beta, l1, l2):
    """One global batch: mean per-dim gradient then the FTRL-Proximal update
    (OnlineLogisticRegression.UpdateModel.processElement)."""
    p = 1.0 / (1.0 + jnp.exp(-(X @ coeff)))
    grad_sum = X.T @ (p - y)
    # per-dim mean over rows where the feature is present (nonzero), the
    # reference's sparse-aware denominator; dense rows count everywhere
    weight_sum = jnp.sum(X != 0.0, axis=0).astype(X.dtype)
    g = jnp.where(weight_sum > 0, grad_sum / jnp.maximum(weight_sum, 1.0), grad_sum)
    return _ftrl_proximal(coeff, z, n, g, alpha, beta, l1, l2)


def _run_totals(first, *columns):
    """Running sums down `columns` that start again wherever `first` is set:
    at the last entry of a run of equal sorted ids, the run's totals."""

    def combine(a, b):
        return (a[0] | b[0], *(jnp.where(b[0], y, x + y) for x, y in zip(a[1:], b[1:])))

    return lax.associative_scan(combine, (first, *columns))[1:]


_SORTED_NO_REPEAT = dict(indices_are_sorted=True, unique_indices=True)


def _touched_coordinates(coeff, indices, values, y):
    """The coordinates a batch of padded-CSR rows holds (-1 ids are padding),
    each once and in rising order at the front of arrays as long as the batch
    has entries: (coordinate, mean gradient, coefficient, how many). The
    entries are sorted by id; a run of equal ids is one coordinate, its
    gradient sum and its count of holding rows the run's totals at the run's
    last entry. A second sort brings those last entries to the front; what
    is behind them reads past the model's end, each slot another id, so that
    the whole array stays sorted and without a repeat."""
    d = coeff.shape[0]
    dot, safe, vals = sparse_dot(indices, values, coeff)
    p = 1.0 / (1.0 + jnp.exp(-dot))
    contrib = vals * (p - y.astype(coeff.dtype))[:, None]
    # padding sorts behind every id, as one run past the end
    coord = jnp.where(indices >= 0, indices, d).ravel()
    coord, contrib, w = lax.sort((coord, contrib.ravel(), coeff[safe].ravel()), num_keys=1)
    edge = coord[1:] != coord[:-1]
    true = jnp.ones((1,), bool)
    grad_sum, count = _run_totals(jnp.concatenate([true, edge]), contrib, jnp.ones_like(contrib))
    writer = jnp.concatenate([edge, true]) & (coord < d)
    past = d + lax.iota(coord.dtype, coord.shape[0])
    coord, g, w = lax.sort((jnp.where(writer, coord, past), grad_sum / count, w), num_keys=1)
    return coord, g, w, jnp.sum(writer)


def _ftrl_at(z, n, coord, g, w, hyper):
    """The FTRL-Proximal update of `_ftrl_step` at the coordinates `coord`
    (sorted, no repeats; one past the model's end reads its last element):
    the new coefficients, z and n there."""
    z_at = z.at[coord].get(mode="clip", **_SORTED_NO_REPEAT)
    n_at = n.at[coord].get(mode="clip", **_SORTED_NO_REPEAT)
    return _ftrl_proximal(w, z_at, n_at, g, hyper[0], hyper[1], hyper[2], hyper[3])


def _ftrl_touched(z, n, coeff, indices, values, y, hyper, writer=None):
    """One global batch of padded-CSR rows: the same mean per-dim gradient and
    FTRL-Proximal update as `_ftrl_step`, over the coordinates the batch
    holds; every other coordinate keeps w, z, n to the bit. (z, n, w) in and
    out, in that order: jax gives a donated argument to the first result of
    its shape, and z and n are the donated ones.

    What the v5e charges at 2e8 coordinates shapes it (PERF.md §5, §6): a
    gather costs 16 ns an element, so z and n are read at the batch's
    DISTINCT coordinates where those fill no more than a quarter of the
    batch's entries (skewed click-log fields repeat: 159,744 entries hold
    37,000 coordinates), and at all of the entries' slots otherwise. XLA's
    scatter passes over its whole operand whatever it writes, so with a
    `writer` (`_slot_writer`'s word) `ops/slot_write` writes the state at the
    distinct coordinates alone: the coefficient, which is not donated because
    a published record holds it, as a fresh buffer made in one pass over it,
    and z and n in place on the quarter's side, where a tile's read and
    write a slot cost less than a pass (PERF.md §6). Otherwise (the
    CPU, state on several devices, and z and n on the side of all the
    entries) XLA scatters z and n in place and the coefficient into its
    copy."""
    coord, g, w, distinct = _touched_coordinates(coeff, indices, values, y)
    entries = coord.shape[0]
    quarter = max(entries // 4, 1)
    interpret = writer == "interpret"

    def update_of(size, in_place):
        def update(z_, n_):
            w_new, z_new, n_new = _ftrl_at(z_, n_, coord[:size], g[:size], w[:size], hyper)
            if in_place:
                z_, n_ = slot_write.write_in_place((z_, n_), coord[:size], (z_new, n_new), distinct, interpret)
            else:
                z_ = z_.at[coord[:size]].set(z_new, mode="drop", **_SORTED_NO_REPEAT)
                n_ = n_.at[coord[:size]].set(n_new, mode="drop", **_SORTED_NO_REPEAT)
            return jnp.pad(w_new, (0, entries - size)), z_, n_

        return update

    small = update_of(quarter, writer is not None)
    w_new, z, n = lax.cond(distinct <= quarter, small, update_of(entries, False), z, n)
    if writer is None:
        return z, n, coeff.at[coord].set(w_new, mode="drop", **_SORTED_NO_REPEAT)
    return z, n, slot_write.copy_writing(coeff, coord, w_new, distinct, interpret)


def _slot_writer(*state):
    """How `_ftrl_touched` writes the state: by `ops/slot_write`'s kernels
    ("kernel") where every array is float32 on one TPU and holds a whole tile
    of them (a shorter vector is laid out in smaller tiles, which their
    copies do not take), else by XLA's scatter (None), so the CPU's program
    is XLA's. Read off the arrays, nothing a user sets; "interpret" runs the
    kernels' own code where `mesh.on_tpu` says yes of an array that lies
    elsewhere."""
    from ...parallel import mesh as mesh_lib

    if not all(
        a.dtype == jnp.float32 and a.shape[0] >= slot_write.TILE and len(a.devices()) == 1 and mesh_lib.on_tpu(a)
        for a in state
    ):
        return None
    return "kernel" if all(d.platform == "tpu" for a in state for d in a.devices()) else "interpret"


_touched_kernel: list = []


def _ftrl_touched_step(coeff, z, n, indices, values, y, hyper):
    """`_ftrl_touched` jitted once, z and n donated: (w, z, n) -> (w, z, n).
    A batch whose state the slot writer writes counts `ftrl.write.slots`."""
    from ...utils import metrics

    if not _touched_kernel:
        from ...parallel.dispatch import supports_donation

        donate = {"donate_argnames": ("z", "n")} if supports_donation() else {}
        _touched_kernel.append(lazy_jit(_ftrl_touched, static_argnames=("writer",), **donate))
    writer = _slot_writer(coeff, z, n)
    if writer is not None:
        metrics.inc_counter("ftrl.write.slots")
    z, n, coeff = _touched_kernel[0](z, n, coeff, indices, values, y, hyper, writer=writer)
    return coeff, z, n


def _serve_scores(coeff, version, X):
    """The serving computation shared by the fused transform kernel and the
    eager device path (jitted once through `_jit_serve`): sigmoid scores,
    hard prediction, two-class raw scores and the per-row model-version
    stamp — all from ONE (coefficient, version) operand pair, so every row
    of a batch is scored by exactly one model version."""
    dot = X @ coeff
    prob = 1.0 / (1.0 + jnp.exp(-dot))
    pred = jnp.where(dot >= 0, 1.0, 0.0)
    raw = jnp.stack([1.0 - prob, prob], axis=1)
    vercol = jnp.full(X.shape[0], version, dtype=jnp.int32)
    return pred, raw, vercol


_jit_serve = lazy_jit(_serve_scores)


class _PublishedLR(NamedTuple):
    """One immutable published model version — the single-reference
    publication record (see `_PublishedKMeans`): swapping it is atomic,
    and a reader's snapshot is always a consistent (version, coefficient)
    pair. The coefficient is kept on the side it was born on (`_online`):
    the training loop's is a device array that no later batch writes to."""

    version: int
    coefficient: Any  # float64 numpy, or the training loop's device array


class OnlineLogisticRegressionModel(OnlineUpdates, Model, OnlineLogisticRegressionModelParams):
    """Serves through the FUSED pipeline path with the coefficient vector
    as a versioned runtime operand: a live `set_model_data`/
    `publish_model_arrays` is a zero-pause, zero-recompile pointer swap
    between batches, and the `modelVersionCol` output stamps every served
    row with the exact version that scored it (the reference's
    modelDataVersion contract — docs/model_lifecycle.md)."""
    fusable = True
    swap_capable = True

    def __init__(self):
        self._published = _PublishedLR(0, None)
        self._trained: Optional[Tuple[int, tuple]] = None

    @property
    def coefficient(self) -> Optional[np.ndarray]:
        """The published coefficient on the host (a device record is read
        back here, when asked, and not when it is published)."""
        return on_host(self._published.coefficient)

    @coefficient.setter
    def coefficient(self, value) -> None:
        self._publish(value, self._published.version)

    @property
    def model_version(self) -> int:
        return self._published.version

    @model_version.setter
    def model_version(self, value: int) -> None:
        self._publish(self._published.coefficient, int(value))

    def _publish(self, coefficient, version: int) -> None:
        self._published = _PublishedLR(int(version), published(coefficient))
        self.bump_model_data_version()

    def _publish_state(self, version: int, coefficient) -> None:
        self._publish(coefficient, version)

    def _publish_trained(self, version: int, state) -> None:
        """The estimator's loop publishes version `version`: the coefficient
        of the state after `version` batches."""
        self._trained = (int(version), state)
        self._publish(state[0], version)

    def training_state(self) -> Optional[Tuple[int, tuple]]:
        """(version, (w, z, n)) as the estimator's loop last published them,
        or None for a model no loop trains: copies on the device, the
        caller's to keep (the loop's own z and n are given up to the next
        batch's step)."""
        if self._trained is None:
            return None
        version, state = self._trained
        return version, tuple(jnp.copy(a) for a in state)

    def model_arrays(self) -> tuple:
        return (self._published.coefficient,)

    def publish_model_arrays(self, arrays: tuple, version: int) -> None:
        (coefficient,) = arrays
        self._publish(coefficient, version)

    def set_model_data(self, *inputs) -> "OnlineLogisticRegressionModel":
        if len(inputs) == 1 and isinstance(inputs[0], Table):
            row = inputs[0].collect()[0]
            coefficient = np.asarray(row["coefficient"].to_array(), dtype=np.float64)
            version = self._published.version
            if "modelVersion" in inputs[0].column_names:
                version = int(row["modelVersion"])
            self._publish(coefficient, version)
            return self
        (stream,) = inputs
        self._take_stream(stream)
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        pub = self._published  # one record read: a consistent (version, coeff)
        return [
            Table(
                {
                    "coefficient": [DenseVector(on_host(pub.coefficient))],
                    "modelVersion": [pub.version],
                }
            )
        ]

    # -- fused transform kernel (versioned runtime operands) -----------------
    def _kernel_constants(self) -> Dict[str, Any]:
        pub = self._published  # ONE record read: consts are version-consistent
        return self.kernel_constants_for((pub.coefficient,), pub.version)

    def kernel_constants_for(self, arrays: tuple, version: int = 0) -> Dict[str, Any]:
        (coefficient,) = arrays
        return {
            # f32 mirrors the device column dtype of the serving path; a
            # device record stays where it is
            "coefficient": kernel_constant(coefficient),
            "version": np.int32(version),
        }

    def _constant_sources(self) -> tuple:
        return (self._published.coefficient,)

    def kernel_output_cols(self) -> List[str]:
        return [
            self.get_prediction_col(),
            self.get_raw_prediction_col(),
            self.get_model_version_col(),
        ]

    def kernel_ready(self, cols: Dict[str, Any]) -> bool:
        return self._published.coefficient is not None

    def transform_kernel(self, consts, cols: Dict[str, Any], ctx: KernelContext) -> Dict[str, Any]:
        X = as_kernel_matrix(cols[self.get_features_col()]).astype(jnp.float32)
        pred, raw, vercol = _serve_scores(consts["coefficient"], consts["version"], X)
        cols[self.get_prediction_col()] = pred
        cols[self.get_raw_prediction_col()] = raw
        cols[self.get_model_version_col()] = vercol
        return cols

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        col = table.column(self.get_features_col())
        if isinstance(col, jax.Array):
            # device input: the SAME jitted computation the fused kernel
            # runs (bit-parity with the fused path), consts from the same
            # published-version snapshot, outputs pulled in ONE packed
            # readback
            from ...utils.packing import packed_device_get

            consts = self.device_constants()
            X = as_kernel_matrix(col).astype(jnp.float32)
            out = _jit_serve(consts["coefficient"], consts["version"], X)
            pred, raw, vercol = packed_device_get(*out, sync_kind="transform")
            return [
                table.with_columns(
                    {
                        self.get_prediction_col(): pred,
                        self.get_raw_prediction_col(): raw,
                        self.get_model_version_col(): vercol,
                    }
                )
            ]
        pub = self._published  # one record read: a consistent (version, coeff)
        X = as_dense_matrix(col)
        dot = X @ on_host(pub.coefficient)
        prob = 1.0 / (1.0 + np.exp(-dot))
        pred = np.where(dot >= 0, 1.0, 0.0)
        raw = np.stack([1.0 - prob, prob], axis=1)
        return [
            table.with_columns(
                {
                    self.get_prediction_col(): pred,
                    self.get_raw_prediction_col(): raw,
                    self.get_model_version_col(): np.full(
                        X.shape[0], pub.version, dtype=np.int64
                    ),
                }
            )
        ]

    def _save_extra(self, path: str) -> None:
        pub = self._published
        read_write.save_model_arrays(
            path, coefficient=on_host(pub.coefficient), modelVersion=np.int64(pub.version)
        )

    def _load_extra(self, path: str) -> None:
        arrays = read_write.load_model_arrays(path)
        self.coefficient = arrays["coefficient"]
        self.model_version = int(arrays.get("modelVersion", 0))


class OnlineLogisticRegression(Estimator, OnlineLogisticRegressionParams):
    """Estimator (OnlineLogisticRegression.java). Requires initial model
    data (e.g. from batch LogisticRegression)."""
    # unbounded fit snapshots (coeff, z, n, stream offset) per global
    # batch through iterate_unbounded -> JobSnapshot
    checkpointable = True

    def __init__(self):
        self._initial_model_data: Optional[Table] = None

    def set_initial_model_data(self, model_data: Table) -> "OnlineLogisticRegression":
        self._initial_model_data = model_data
        return self

    def fit(self, *inputs) -> OnlineLogisticRegressionModel:
        (stream,) = inputs
        if not isinstance(stream, StreamTable):
            raise TypeError("OnlineLogisticRegression.fit expects a StreamTable")
        if self._initial_model_data is None:
            raise ValueError("OnlineLogisticRegression requires initial model data")
        row = self._initial_model_data.collect()[0]
        coeff = np.asarray(row["coefficient"].to_array(), dtype=np.float64)
        d = coeff.shape[0]
        reg, en = self.get_reg(), self.get_elastic_net()
        l1, l2 = en * reg, (1.0 - en) * reg
        alpha, beta = self.get_alpha(), self.get_beta()
        features_col = self.get_features_col()
        label_col = self.get_label_col()
        batch_size = self.get_global_batch_size()

        def columns(batch) -> tuple:
            """A mini-batch as the arrays a step takes, rows first: (X, y)
            for a dense features column, (indices, values, y) for a
            SparseBatch, which is never densified. Device arrays stay."""
            col = batch.column(features_col)
            y = batch.column(label_col)
            if not on_device(y):
                y = np.asarray(y, dtype=np.float64)
            if isinstance(col, SparseBatch):
                if col.size != d:
                    raise ValueError(
                        f"a sparse batch of size {col.size} for a model of {d} coefficients"
                    )
                return (col.indices, col.values, y)
            return (as_dense_matrix(col), y)

        def rebatch(batches) -> Iterator[tuple]:
            """countWindowAll(globalBatchSize): regroup incoming rows into
            exact global batches, in stream order. A batch that already has
            globalBatchSize rows passes through as it is."""
            held: List[tuple] = []
            buffered = 0
            for batch in batches:
                chunk = columns(batch)
                if held and len(chunk) != len(held[0]):
                    raise TypeError("a stream mixes dense and sparse feature batches")
                if not held and chunk[-1].shape[0] == batch_size:
                    yield chunk
                    continue
                held.append(chunk)
                buffered += chunk[-1].shape[0]
                while buffered >= batch_size:
                    joined = _join_rows(held)
                    yield tuple(a[:batch_size] for a in joined)
                    buffered -= batch_size
                    held = [tuple(a[batch_size:] for a in joined)] if buffered else []

        hyper = jnp.asarray([alpha, beta, l1, l2], jnp.float32)

        def step(state, batch):
            """One global batch, by the kind of its features column."""
            from ...obs import memledger
            from ...utils import metrics

            if not on_device(state[0]):  # a state restored from a checkpoint
                state = _stage_state(state)
            y = batch[-1]
            metrics.inc_counter("ftrl.batches")
            metrics.inc_counter("ftrl.rows", int(y.shape[0]))
            if len(batch) == 2:
                metrics.inc_counter("ftrl.slots_updated", d)
                state = _ftrl_step(*state, batch[0], y, alpha, beta, l1, l2)
            else:
                indices, values, _ = batch
                metrics.inc_counter("ftrl.slots_updated", int(indices.size))
                state = _ftrl_touched_step(*state, indices, values, y, hyper)
            return memledger.track(state, "online.state")

        from ... import config
        from ...parallel import prefetch as h2d
        from ...parallel.iteration import checkpoint_job_key

        init = _initial_state(coeff)
        # shared input stager: the (X, y) upload of global batch b+1 runs
        # on the worker thread (accounted, h2d.*) while batch b's FTRL
        # step executes — micro-batch H2D off the critical path; a batch
        # that is already on the device passes through untouched. The
        # window is a flow.BoundedChannel under config.
        # online_overload_policy: "block" (default) is lossless
        # backpressure; "shed_oldest" bounds memory AND model staleness
        # when the stream outruns FTRL (flow.shed / flow.lag.online.ingest).
        staged = h2d.Prefetcher(
            h2d.stage_to_device,
            policy=config.online_overload_policy,
            name="online.ingest",
        ).iterate(rebatch(stream))
        model = OnlineLogisticRegressionModel()
        model.coefficient = coeff
        model._follow(
            iterate_unbounded(
                staged, step, init, job_key=checkpoint_job_key(self),
                publish=weakly(model._publish_trained),
            )
        )
        update_existing_params(model, self)
        return model


def _stage_state(state) -> tuple:
    """Host arrays uploaded once, float32, ledgered as `online.state`."""
    from ...parallel import prefetch as h2d

    return h2d.stage_to_device(
        tuple(np.asarray(a, dtype=np.float32) for a in state), category="online.state"
    )


def _initial_state(coeff) -> tuple:
    """(w, z, n) before the first batch: the initial coefficient uploaded, z
    and n born on the device as zeros."""
    from ...obs import memledger

    (w,) = _stage_state((coeff,))
    return memledger.track((w, jnp.zeros_like(w), jnp.zeros_like(w)), "online.state")


def _join_rows(chunks: List[tuple]) -> tuple:
    """The chunks' columns joined along the rows, on the side they are on;
    sparse chunks of unequal width are padded to the widest (-1, 0)."""
    if len(chunks) == 1:
        return chunks[0]
    joined = []
    for position, parts in enumerate(zip(*chunks)):
        xp = jnp if any(on_device(a) for a in parts) else np
        if parts[0].ndim == 2 and len(chunks[0]) == 3:
            width = max(a.shape[1] for a in parts)
            fill = -1 if position == 0 else 0
            parts = [
                a if a.shape[1] == width
                else xp.pad(a, ((0, 0), (0, width - a.shape[1])), constant_values=fill)
                for a in parts
            ]
        joined.append(xp.concatenate(parts))
    return tuple(joined)
