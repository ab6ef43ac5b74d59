"""NaiveBayes — multinomial naive Bayes over categorical feature values.

TPU-native re-design of classification/naivebayes/NaiveBayes.java
(GenerateModelFunction smoothing math matched exactly:
theta[i][j][v] = log(count(label i, feature j = v) + smoothing)
              - log(count(label i) + smoothing * numCategories[j]);
pi[i] = log(count(label i) * featureSize + smoothing)
      - log(totalDocs * featureSize + numLabels * smoothing)),
NaiveBayesModel.java calculateProb (sum of per-feature log-probs + pi,
argmax by label) and NaiveBayesModelData.java:57-69. Unseen feature values
at predict time raise, as the reference's map lookup does.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from ...api import Estimator, Model
from ...common.param import HasFeaturesCol, HasLabelCol, HasPredictionCol
from ...param import DoubleParam, ParamValidators, StringParam
from ...table import Table, as_dense_matrix
from ...utils import read_write
from ...utils.param_utils import update_existing_params


# Largest per-feature category count served by the device kernels; bigger
# category sets fall back to the host path (the (chunk, d, m) compare
# volume grows linearly in m).
DEVICE_MAX_CATEGORIES = 512
# Bound on chunk * d * m elements per device program (~2 GB of f32 temps).
_CHUNK_BUDGET = 5 * 10**8


def _nb_chunk_rows(d: int, m: int) -> int:
    # cap at 2^24 rows so per-chunk f32 count accumulation stays integer-
    # exact regardless of d * m (cross-chunk sums are f64 on host)
    return max(1, min(_CHUNK_BUDGET // max(1, d * m), 1 << 24))


def _nb_sorted_cat_counts_impl(X):
    """Column sort + per-column distinct counts — the device analogue of
    `np.unique` per column."""
    import jax.numpy as jnp

    Xs = jnp.sort(X, axis=0)
    first = jnp.concatenate(
        [jnp.ones((1, X.shape[1]), bool), Xs[1:] != Xs[:-1]], axis=0
    )
    return Xs, first.sum(axis=0)


def _nb_extract_cats_impl(Xs, m_max: int):
    """(d, m_max) per-column sorted distinct values (+inf padding) from the
    column-sorted matrix: firsts compact via one sort over positions; the
    only gather is (m_max, d) — tiny."""
    import jax.numpy as jnp

    n, d = Xs.shape
    first = jnp.concatenate([jnp.ones((1, d), bool), Xs[1:] != Xs[:-1]], axis=0)
    pos = jnp.where(first, jnp.arange(n)[:, None], n)
    pos_sorted = jnp.sort(pos, axis=0)[:m_max]  # (m_max, d)
    valid = pos_sorted < n
    vals = jnp.take_along_axis(Xs, jnp.minimum(pos_sorted, n - 1), axis=0)
    return jnp.where(valid, vals, jnp.inf).T  # (d, m_max)


def _nb_count_chunk_impl(Xc, yc, cats, labels):
    """(L, d, m) co-occurrence counts of one row chunk: both one-hots are
    lane-broadcast compares, the contraction over rows is an MXU einsum —
    no gathers, no host loops."""
    import jax.numpy as jnp

    eq = (Xc[:, :, None] == cats[None, :, :]).astype(jnp.float32)
    Y1 = (yc[:, None] == labels[None, :]).astype(jnp.float32)
    return jnp.einsum("cdm,cl->ldm", eq, Y1), Y1.sum(axis=0)


def _nb_predict_chunk_impl(Xc, cats, logp, pi, labels):
    """Per-row label scores + argmax prediction, gather-free: probs =
    pi + einsum over the (c, d, m) category one-hot and the (d, m, L)
    log-prob tensor (NaiveBayesModel.calculateProb as one MXU contraction);
    the label decode is a one-hot matvec. Returns (pred, all_seen, seen,
    top-2 score gap)."""
    import jax
    import jax.numpy as jnp

    eq = Xc[:, :, None] == cats[None, :, :]
    seen = jnp.any(eq, axis=2)  # (c, d)
    # precision=highest: the TPU default feeds bf16 into the MXU, and
    # truncating logp to 8 mantissa bits flips argmax on ~0.1-gap rows
    probs = pi[None, :] + jnp.einsum(
        "cdm,dml->cl", eq.astype(jnp.float32), logp, precision="highest"
    )
    arg = jnp.argmax(probs, axis=1)
    L = labels.shape[0]
    onehot = (arg[:, None] == jnp.arange(L)[None, :]).astype(labels.dtype)
    pred = jnp.einsum("cl,l->c", onehot, labels, precision="highest")
    if L >= 2:  # top-2 score gap: rows inside f32 error get host-refined
        top2 = jax.lax.top_k(probs, 2)[0]
        # normalize the gap by the f32 accumulation error scale
        # (~d * eps * |score|) so the host-rescore trigger holds for any
        # feature count / score magnitude, not just the measured d=10 case
        d = Xc.shape[1]
        eps = jnp.float32(1.2e-7)
        scale = d * eps * (jnp.abs(top2).sum(axis=1) + 1.0)
        gap = (top2[:, 0] - top2[:, 1]) / scale
    else:
        gap = jnp.full(probs.shape[0], jnp.inf, probs.dtype)
    return pred, jnp.all(seen), seen, gap


def _nb_unpack_model_impl(flat, d, m, L):
    """Device-side views of the single packed model upload: (cats (d, m),
    logp (d, m, L), pi (L,), labels (L,)). One H2D transfer replaces four
    separate device_puts — each upload is its own transfer, and this runs
    on the benchmark's first transform."""
    import jax.numpy as jnp

    cm = d * m
    cats = jnp.reshape(flat[:cm], (d, m))
    logp = jnp.reshape(flat[cm : cm + cm * L], (d, m, L))
    pi = flat[cm + cm * L : cm + cm * L + L]
    labels = flat[cm + cm * L + L :]
    return cats, logp, pi, labels


from ...utils.lazyjit import lazy_jit

_nb_sorted_cat_counts = lazy_jit(_nb_sorted_cat_counts_impl)
_nb_extract_cats = lazy_jit(_nb_extract_cats_impl, static_argnames=("m_max",))
_nb_count_chunk = lazy_jit(_nb_count_chunk_impl)
_nb_predict_chunk = lazy_jit(_nb_predict_chunk_impl)
_nb_unpack_model = lazy_jit(_nb_unpack_model_impl, static_argnames=("d", "m", "L"))


class NaiveBayesModelParams(HasFeaturesCol, HasPredictionCol):
    MODEL_TYPE = StringParam(
        "modelType",
        "The model type.",
        "multinomial",
        ParamValidators.in_array(["multinomial"]),
    )

    def get_model_type(self) -> str:
        return self.get(self.MODEL_TYPE)

    def set_model_type(self, value: str):
        return self.set(self.MODEL_TYPE, value)


class NaiveBayesParams(NaiveBayesModelParams, HasLabelCol):
    SMOOTHING = DoubleParam(
        "smoothing", "The smoothing parameter.", 1.0, ParamValidators.gt_eq(0.0)
    )

    def get_smoothing(self) -> float:
        return self.get(self.SMOOTHING)

    def set_smoothing(self, value: float):
        return self.set(self.SMOOTHING, value)


class NaiveBayesModel(Model, NaiveBayesModelParams):
    fusable = False
    fusable_reason = "exactness contract needs host f64 rescoring of near-tie rows and a data-dependent unseen-category error, both mid-transform readbacks"

    def __init__(self):
        self.theta: List[List[Dict[float, float]]] = None  # [label][feature] -> {value: logp}
        self.pi: np.ndarray = None  # (numLabels,) log priors
        self.labels: np.ndarray = None  # (numLabels,) label values
        self._device_tensors = None  # cached (cats, logp, pi, labels) on device

    def set_model_data(self, *inputs: Table) -> "NaiveBayesModel":
        (model_data,) = inputs
        row = model_data.collect()[0]
        self.theta = row["theta"]
        self.pi = np.asarray(row["piArray"].to_array(), dtype=np.float64)
        self.labels = np.asarray(row["labels"].to_array(), dtype=np.float64)
        self._device_tensors = None
        return self

    def get_model_data(self) -> List[Table]:
        from ...linalg import DenseVector

        return [
            Table(
                {
                    "theta": [self.theta],
                    "piArray": [DenseVector(self.pi)],
                    "labels": [DenseVector(self.labels)],
                }
            )
        ]

    def _theta_tensors(self):
        """(cats (d, m_max) +inf-padded, logp (d, m_max, L)) views of the
        per-feature log-prob dictionaries for the device kernel."""
        num_labels = len(self.labels)
        d = len(self.theta[0])
        per_col = [np.asarray(sorted(self.theta[0][j]), np.float64) for j in range(d)]
        m_max = max(v.size for v in per_col)
        cats = np.full((d, m_max), np.inf, np.float32)
        logp = np.zeros((d, m_max, num_labels), np.float32)
        labels_cast = self.labels.astype(np.float32)
        if not np.array_equal(labels_cast.astype(np.float64), self.labels):
            return None, None  # labels not f32-exact: decode would round
        for j, values in enumerate(per_col):
            if not np.isfinite(values).all():
                # +inf IS the padding sentinel: a trained +inf category
                # would also match every padding slot of its column (logp 0
                # each), corrupting the score sums — and NaN/-inf are not
                # worth a separate device story. Host path scores exactly.
                return None, None
            cast = values.astype(np.float32)
            if not np.array_equal(cast.astype(np.float64), values):
                # categories not exactly f32-representable: the device
                # compare would accept/merge values the host path rejects
                return None, None
            if np.unique(cast).size != cast.size:
                return None, None  # f32 merges distinct categories: host path
            cats[j, : values.size] = cast
            for r, v in enumerate(values):
                for i in range(num_labels):
                    logp[j, r, i] = self.theta[i][j][float(v)]
        return cats, logp

    def transform(self, *inputs: Table) -> List[Table]:
        import jax

        (table,) = inputs
        X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
        n, d = X.shape
        dev = None
        if isinstance(X, jax.Array) and n > 0 and X.dtype == np.float32:
            # f32-only: an f64 device column (x64 on) would lose category
            # identity through the f32 kernels — host path keeps exactness.
            # The tensors upload once per model and are cached (repeated
            # transforms pay nothing; set_model_data/_load_extra invalidate)
            dev = self._device_tensors
            if dev is None:
                cats_h, logp_h = self._theta_tensors()
                if cats_h is None:
                    dev = self._device_tensors = False  # host-only model
                else:
                    dm, m_max = cats_h.shape
                    L = self.labels.size
                    flat = np.concatenate(
                        [
                            cats_h.ravel(),
                            logp_h.ravel(),
                            self.pi.astype(np.float32),
                            self.labels.astype(np.float32),
                        ]
                    )
                    from ...parallel.prefetch import stage_to_device

                    dev = self._device_tensors = (
                        *_nb_unpack_model(stage_to_device(flat), dm, m_max, L),
                        m_max,
                    )
        if dev:
            # device path: probability sums as one MXU contraction per row
            # chunk — predictions stay on device, nothing crosses the host
            # except the unseen-value flag
            import jax.numpy as jnp

            cats, logp, pi, labels, m_max = dev
            from ...utils.packing import packed_device_get

            chunk = _nb_chunk_rows(d, m_max)
            starts = list(range(0, n, chunk))
            preds, flags, gaps = [], [], []
            for s in starts:
                p, ok, seen, gap = _nb_predict_chunk(
                    jnp.asarray(X[s : s + chunk], jnp.float32), cats, logp, pi, labels
                )
                # `seen` is NOT retained: keeping every (chunk, d) mask on
                # device would cost n*d bools of HBM just for the error
                # message; the failing chunk is recomputed below instead
                preds.append(p)
                flags.append(ok)
                gaps.append(gap)
            # ONE packed readback for the unseen flag + tie gaps (each
            # extra sync is another blocking readback)
            all_ok = jnp.all(jnp.stack(flags))
            gap_dev = gaps[0] if len(gaps) == 1 else jnp.concatenate(gaps)
            ok_h, gap_h = packed_device_get(all_ok.astype(jnp.float32), gap_dev)
            if not bool(ok_h):
                for s, ok_c in zip(starts, flags):
                    if bool(ok_c):
                        continue
                    _, _, seen, _ = _nb_predict_chunk(
                        jnp.asarray(X[s : s + chunk], jnp.float32),
                        cats, logp, pi, labels,
                    )
                    # tpulint: disable=host-sync-leak -- error path: fit already failed validation; pulls locate the offending value for the message
                    rows, cols = np.nonzero(~np.asarray(seen))
                    bad = float(np.asarray(X[s + rows[0], cols[0]]))
                    raise ValueError(
                        f"Feature value {bad} in column {int(cols[0])} "
                        "was not seen during training"
                    )
            pred = preds[0] if len(preds) == 1 else jnp.concatenate(preds)
            # exactness: rows whose top-2 score gap is inside the f32 error
            # bound rescore on host in f64, so device predictions match the
            # reference's double-precision argmax bit-for-bit. The kernel
            # returns the gap NORMALIZED by the worst-case error scale
            # d*eps*|score| (the measured error is ~20x below that bound at
            # d=10, so a factor-2 threshold keeps >20x margin over the flip
            # radius at ANY width while touching a vanishing fraction of
            # rows; at d=10, |score|~30 it reproduces the previously
            # validated 1e-4 absolute cut)
            ties = np.nonzero(gap_h < 2.0)[0]
            if ties.size:
                Xt = np.asarray(X[jnp.asarray(ties)], np.float64)
                pred = pred.at[jnp.asarray(ties)].set(
                    jnp.asarray(self._predict_host(Xt), pred.dtype)
                )
            return [table.with_column(self.get_prediction_col(), pred)]
        X = np.asarray(X)  # host fallback (incl. f32-colliding categories)
        pred = self._predict_host(X)
        return [table.with_column(self.get_prediction_col(), pred)]

    def _predict_host(self, X: np.ndarray) -> np.ndarray:
        """Reference-precision (float64) scoring, columnwise on host."""
        n, d = X.shape
        num_labels = len(self.labels)
        probs = np.tile(self.pi, (n, 1))  # (n, numLabels)
        for j in range(d):
            # columnwise: sorted category values + (num_values, num_labels)
            # log-prob matrix, then one searchsorted gather per feature
            values = np.asarray(sorted(self.theta[0][j]), dtype=np.float64)
            logp = np.stack(
                [[self.theta[i][j][v] for i in range(num_labels)] for v in values]
            )  # (num_values, num_labels)
            col = X[:, j]
            pos = np.searchsorted(values, col)
            pos_clipped = np.clip(pos, 0, values.size - 1)
            unseen = (pos >= values.size) | (values[pos_clipped] != col)
            if unseen.any():
                bad = float(col[np.nonzero(unseen)[0][0]])
                raise ValueError(
                    f"Feature value {bad} in column {j} was not seen during training"
                )
            probs += logp[pos_clipped]
        return self.labels[np.argmax(probs, axis=1)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(
            path,
            theta=np.asarray(self.theta, dtype=object),
            piArray=self.pi,
            labels=self.labels,
        )

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        arrays = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_naivebayes
        )
        self.theta = [list(row) for row in arrays["theta"]]
        self.pi = arrays["piArray"]
        self.labels = arrays["labels"]
        self._device_tensors = None


class NaiveBayes(Estimator, NaiveBayesParams):
    checkpointable = False
    checkpoint_reason = "single-pass label/feature count aggregation; a restart recomputes the fit"
    def _fit_stats_device(self, X, y):
        """(labels, per-label counts, per-column category values, per-pair
        co-occurrence counts) aggregated on device: column sorts for the
        category sets, lane-broadcast one-hot compares + an MXU einsum for
        the counts. Only the small (L, d, m) statistics cross to the host
        (at the benchmark's 1M x 10 that is 100 floats vs an 80 MB matrix
        pull + per-label np.unique loops). Exact: every count is an
        integer < 2^24 accumulated in f32 per chunk, summed in f64 across
        chunks. Returns None when a column's category count exceeds the
        device bound. Matches NaiveBayes.java GenerateModelFunction's
        aggregation exactly."""
        import jax
        import jax.numpy as jnp

        from ...ops.stats import _nunique_device, _unique_device
        from ...utils.packing import packed_device_get

        n, d = X.shape
        if n == 0:
            return None
        if X.dtype != jnp.float32:
            return None  # f64 device input (x64 on): f32 cast could merge
        X32 = X
        if isinstance(y, jax.Array):
            if y.dtype != jnp.float32:
                return None
            y_dev = y
        else:
            y_np = np.asarray(y)
            y32 = y_np.astype(np.float32)
            if not np.array_equal(
                y32.astype(y_np.dtype), y_np, equal_nan=True
            ):
                return None  # labels not f32-exact: counts would merge
            y_dev = jnp.asarray(y32)
        Xs, m_per_col = _nb_sorted_cat_counts(X32)
        # round trip 1: the scalars the later programs are shaped by. The
        # feature-NaN probe rides the same transfer: NaN features would
        # silently inflate the category sets (NaN != NaN makes every NaN a
        # distinct "category" through the sorted-compare counting), so they
        # are rejected here exactly like NaN labels.
        nan_flag, x_nan_flag, x_inf_flag, m_max_arr, nunique = packed_device_get(
            jnp.isnan(y_dev).any().astype(jnp.float32),
            jnp.isnan(X32).any().astype(jnp.float32),
            jnp.isposinf(X32).any().astype(jnp.float32),
            jnp.max(m_per_col).astype(jnp.float32),
            _nunique_device(y_dev).astype(jnp.float32),
        )
        if bool(nan_flag):
            raise ValueError("Label column contains null/NaN values")
        if bool(x_nan_flag):
            raise ValueError("Feature column contains null/NaN values")
        if bool(x_inf_flag):
            # +inf doubles as the category-padding sentinel in the count
            # kernel — a real +inf feature would co-count with every padding
            # slot. The host path trains inf categories exactly (and the
            # predict-side _theta_tensors guard keeps serving them on host).
            return None
        m_max = int(m_max_arr)
        if m_max > DEVICE_MAX_CATEGORIES:
            return None
        cats = _nb_extract_cats(Xs, m_max)  # (d, m_max), +inf padded
        num_labels = int(nunique)
        labels_dev = _unique_device(y_dev, num_labels)
        chunk = _nb_chunk_rows(d, m_max)
        counts = np.zeros((num_labels, d, m_max), np.float64)
        label_counts_arr = np.zeros(num_labels, np.float64)
        cats_h = m_h = labels_h = None
        for s in range(0, n, chunk):
            c, lc = _nb_count_chunk(
                X32[s : s + chunk], y_dev[s : s + chunk], cats, labels_dev
            )
            if cats_h is None:
                # round trip 2 (once): chunk stats + model-shaping arrays
                c_h, lc_h, cats_h, m_h, labels_h = packed_device_get(
                    c, lc, cats, m_per_col.astype(jnp.float32), labels_dev
                )
            else:
                c_h, lc_h = packed_device_get(c, lc)
            counts += np.asarray(c_h, np.float64)
            label_counts_arr += np.asarray(lc_h, np.float64)
        return (
            np.asarray(labels_h, np.float64),
            label_counts_arr,
            np.asarray(cats_h, np.float64),
            np.asarray(m_h, np.int64),
            counts,
        )

    def fit(self, *inputs: Table) -> NaiveBayesModel:
        import jax

        (table,) = inputs
        smoothing = self.get_smoothing()
        X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
        n, d = X.shape
        stats = None
        if isinstance(X, jax.Array):
            stats = self._fit_stats_device(X, table.column(self.get_label_col()))
        if stats is not None:
            labels_h, label_counts_arr, cats_h, m_h, counts = stats
            num_labels = len(labels_h)
            theta: List[List[Dict[float, float]]] = []
            for i in range(num_labels):
                label_theta = []
                for j in range(d):
                    m_j = int(m_h[j])
                    theta_log = math.log(label_counts_arr[i] + smoothing * m_j)
                    label_theta.append(
                        {
                            float(cats_h[j, r]): math.log(counts[i, j, r] + smoothing)
                            - theta_log
                            for r in range(m_j)
                        }
                    )
                theta.append(label_theta)
            pi_log = math.log(n * d + num_labels * smoothing)
            pi = np.asarray(
                [
                    math.log(label_counts_arr[i] * d + smoothing) - pi_log
                    for i in range(num_labels)
                ]
            )
            model = NaiveBayesModel()
            model.theta = theta
            model.pi = pi
            model.labels = labels_h
            update_existing_params(model, self)
            return model
        X = np.asarray(X)
        y = np.asarray(table.column(self.get_label_col()), dtype=np.float64)
        if np.isnan(y).any():
            raise ValueError("Label column contains null/NaN values")
        if np.isnan(X).any():
            # matching the device probe: a NaN "category" can never be
            # matched at predict time (NaN != NaN), so training would bake
            # in unreachable probability mass — reject like NaN labels
            raise ValueError("Feature column contains null/NaN values")
        labels = np.unique(y)
        num_labels = len(labels)
        label_counts = {float(l): int(np.sum(y == l)) for l in labels}
        # per-feature category sets across ALL labels
        categories = [np.unique(X[:, j]) for j in range(d)]
        theta: List[List[Dict[float, float]]] = []
        for l in labels:
            rows = X[y == l]
            label_theta = []
            for j in range(d):
                values, counts = np.unique(rows[:, j], return_counts=True)
                count_map = dict(zip(values, counts))
                theta_log = math.log(label_counts[float(l)] + smoothing * len(categories[j]))
                label_theta.append(
                    {
                        float(v): math.log(count_map.get(v, 0.0) + smoothing) - theta_log
                        for v in categories[j]
                    }
                )
            theta.append(label_theta)
        pi_log = math.log(n * d + num_labels * smoothing)
        pi = np.asarray(
            [
                math.log(label_counts[float(l)] * d + smoothing) - pi_log
                for l in labels
            ]
        )
        model = NaiveBayesModel()
        model.theta = theta
        model.pi = pi
        model.labels = labels.astype(np.float64)
        update_existing_params(model, self)
        return model
