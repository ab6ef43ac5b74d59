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

The model is held as arrays: `theta_array` [labels, categories], each
feature's seen values in ascending order in `categories`, feature j's at
`offsets[j]:offsets[j + 1]`, beside `pi` and `labels`. The reference's
[label][feature] -> {value: log-probability} maps (`theta`, the model data
table, the Java codec) are built from them only when asked for.

A fit over a float32 device table counts on the device, each feature by one
of two routes, chosen by a probe of the table in one round trip:

- a feature whose values are all whole numbers in [0, 2**24) (float32 holds
  each exactly: dictionary indices) is counted into bins 0..max; with at
  most MATCH_MAX_CATEGORIES bins by compares (`ops.bincount.
  label_match_counts`), with more by a scatter-add (`ops.bincount.
  label_bincount`), the INDEXED route. No column is sorted (XLA's TPU
  scatter sorts its own keys, block by block); its categories are the bins
  that counted a row;
- any other feature with at most MATCH_MAX_CATEGORIES distinct values finds
  them by a column sort and is counted by compares.

Counts are exact int32, and come back whole in one read. The host takes each
feature's seen bins and their theta in float64 from the exact counts, rounded
once to float32, on every route, so that every route gives the same bits:
`jnp.log` in float32 on a TPU v5e reads up to 1.1e-4 off float64 (up to
~500 ulps, read alone over every count below 2**24), more than one row added
to or lost from a category of fewer than 65,536 rows moves a theta. A table
the device routes cannot take (another dtype, labels float32 does not hold,
+inf, a feature of more distinct values that are not indices) is counted on
the host, into the same arrays.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from ...api import Estimator, Model
from ...common.param import HasFeaturesCol, HasLabelCol, HasPredictionCol
from ...param import DoubleParam, ParamValidators, StringParam
from ...table import Table, as_dense_matrix
from ...utils import metrics, read_write
from ...utils.param_utils import update_existing_params


# Largest number of categories a feature is counted by compares (fit), and
# of categories a feature may have for the device scoring kernel
# (transform): the (rows, categories) compare volume grows with it.
MATCH_MAX_CATEGORIES = 512
# Float32 holds every whole number below this exactly: the indexed route's
# bound on a feature's values.
INDEX_LIMIT = 1 << 24
# Labels that are whole numbers below this are found by the probe's
# compares; any others by two sorts of the labels.
LABEL_RANGE = 64
# Rows a count step reads; the last step ends at the table's last row.
COUNT_BLOCK_ROWS = 1 << 18
# Bins the host takes at a time when it builds a model from counts.
HOST_BLOCK = 1 << 20
# Bound on chunk * d * m elements per device program (~2 GB of f32 temps).
_CHUNK_BUDGET = 5 * 10**8


def _nb_chunk_rows(d: int, m: int) -> int:
    # cap at 2^24 rows so per-chunk f32 count accumulation stays integer-
    # exact regardless of d * m
    return max(1, min(_CHUNK_BUDGET // max(1, d * m), 1 << 24))


def bucket(n: int) -> int:
    """`n` rounded up to one of eight sizes an octave (a multiple of 2**(k-3)
    for n in (2**(k-1), 2**k]; every n up to 16 is its own size): a
    feature's bins take these sizes, so that a log whose largest index grows
    a little compiles no new program."""
    step = 1 << max(0, (max(n, 1) - 1).bit_length() - 4)
    return -(-n // step) * step


class _Field(NamedTuple):
    """How one feature is counted: `form` "scatter" (ids into bins by the
    scatter-add), "range" (ids against 0..width-1 by compares) or "sorted"
    (values against its sorted distinct values, row `row` of the staged
    candidates); its bins are offset..offset+width-1 of the count."""

    form: str
    offset: int
    width: int
    row: int = -1


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


def _nb_probe_impl(X, y):
    """One float32 vector the fit's routes are chosen by: NaN in the labels,
    NaN in the features, +inf in the features, whether every label is a
    whole number below LABEL_RANGE, then for each such number whether a
    label is it, for each feature whether every value is an index (a whole
    number in [0, 2**24)), and each feature's largest value. Nothing is
    sorted: a sort of the table's rows costs the chip's compiler minutes."""
    import jax.numpy as jnp

    index = jnp.all((X >= 0) & (X < INDEX_LIMIT) & (X == jnp.floor(X)), axis=0)
    small = jnp.all((y >= 0) & (y < LABEL_RANGE) & (y == jnp.floor(y)))
    present = jnp.any(y[:, None] == jnp.arange(LABEL_RANGE, dtype=y.dtype)[None, :], axis=0)
    flags = jnp.stack(
        [jnp.isnan(y).any(), jnp.isnan(X).any(), jnp.isposinf(X).any(), small]
    ).astype(jnp.float32)
    return jnp.concatenate(
        [flags, present.astype(jnp.float32), index.astype(jnp.float32), jnp.max(X, axis=0).astype(jnp.float32)]
    )


def _candidates(field: _Field, sorted_cats):
    import jax.numpy as jnp

    if field.form == "sorted":
        return sorted_cats[field.row, : field.width]
    return jnp.arange(field.width, dtype=jnp.float32)


def _nb_count_impl(X, y, labels, sorted_cats, plan: Tuple[_Field, ...], block_rows: int):
    """Exact int32 counts [labels, bins] of every (label, feature value)
    pair of the table, in one pass of row blocks (one program for every
    block, the last one too: each copy of the scatter costs the chip's
    compiler tens of seconds), as one int32 vector with what the host needs
    beside them: [the labels' float32 bits | the sorted features' candidates'
    float32 bits | the counts, features in order]. The features counted by
    compares add into a carry of their own, a few hundred bins wide."""
    import jax.numpy as jnp
    from jax import lax

    from ...ops.bincount import label_bincount, label_match_counts

    n = X.shape[0]
    num_labels = labels.shape[0]
    scattered = [j for j, f in enumerate(plan) if f.form == "scatter"]
    matched = [j for j, f in enumerate(plan) if f.form != "scatter"]
    # each form's carry holds its features' bins side by side
    within = {}
    for group in (scattered, matched):
        start = 0
        for j in group:
            within[j] = start
            start += plan[j].width
    scatter_offsets = jnp.asarray([within[j] for j in scattered], jnp.int32)
    candidates = {j: _candidates(plan[j], sorted_cats) for j in matched}
    block = min(block_rows, n)

    def step(k, carry):
        wide, narrow = carry
        # the last block ends at the last row; the rows it shares with the
        # block before are counted there and get the label past the end,
        # which no bin holds
        start = jnp.minimum(k * block, n - block)
        Xb = lax.dynamic_slice_in_dim(X, start, block, axis=0)
        yb = lax.dynamic_slice_in_dim(y, start, block, axis=0)
        fresh = start + jnp.arange(block) >= k * block
        label_idx = jnp.where(fresh, jnp.searchsorted(labels, yb), num_labels).astype(jnp.int32)
        if scattered:
            ids = jnp.stack([Xb[:, j] for j in scattered], axis=1).astype(jnp.int32) + scatter_offsets
            wide = label_bincount(wide, ids, label_idx)
        if matched:
            narrow = narrow + jnp.concatenate(
                [label_match_counts(Xb[:, j], candidates[j], label_idx, num_labels) for j in matched], axis=1
            )
        return wide, narrow

    carry = tuple(
        jnp.zeros((num_labels, sum(plan[j].width for j in group)), jnp.int32) for group in (scattered, matched)
    )
    wide, narrow = lax.fori_loop(0, -(-n // block), step, carry)
    bins = jnp.concatenate(
        [
            (wide if f.form == "scatter" else narrow)[:, within[j] : within[j] + f.width]
            for j, f in enumerate(plan)
        ],
        axis=1,
    )
    return jnp.concatenate(
        [
            lax.bitcast_convert_type(labels, jnp.int32),
            lax.bitcast_convert_type(sorted_cats, jnp.int32).reshape(-1),
            bins.reshape(-1),
        ]
    )


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
_nb_probe = lazy_jit(_nb_probe_impl)
_nb_count = lazy_jit(_nb_count_impl, static_argnames=("plan", "block_rows"))
_nb_predict_chunk = lazy_jit(_nb_predict_chunk_impl)
_nb_unpack_model = lazy_jit(_nb_unpack_model_impl, static_argnames=("d", "m", "L"))


class _Theta:
    """float32 theta of one feature's counts, taken in float64 as
    NaiveBayes.java takes it and rounded once: log(count + smoothing) -
    log(label rows + smoothing * categories), `categories` the feature's
    number of seen categories. Each label's value is computed once for every
    count from 0 to the feature's largest, `top`, and looked up (a log's
    counts are mostly small, and the same few values repeat), unless the
    table would outgrow the counts. `fill` works in blocks of HOST_BLOCK
    counts, so that its temporaries are reused and not fresh memory (a TPU
    v5e host writes fresh memory at about 1 GB/s)."""

    def __init__(self, label_rows, categories: int, smoothing: float, top: int, size: int):
        self.smoothing = smoothing
        self.below = np.log(np.asarray(label_rows, np.float64) + smoothing * categories)
        self.table = None
        if top < max(size, 1 << 16):
            with np.errstate(divide="ignore"):  # smoothing 0: log(0) = -inf, as Java's Math.log
                self.table = (np.log(np.arange(top + 1) + smoothing)[None, :] - self.below[:, None]).astype(np.float32)

    def fill(self, label: int, counts, out) -> None:
        """theta of one label's counts [n] into out [n]."""
        for start in range(0, counts.size, HOST_BLOCK):
            part, into = counts[start : start + HOST_BLOCK], out[start : start + HOST_BLOCK]
            if self.table is None:
                with np.errstate(divide="ignore"):
                    into[...] = np.log(part + self.smoothing) - self.below[label]
            else:
                np.take(self.table[label], part, out=into, mode="clip")  # every count is in the table


def _log_priors(label_rows, rows: int, features: int, smoothing: float) -> np.ndarray:
    label_rows = np.asarray(label_rows, np.float64)
    with np.errstate(divide="ignore"):
        return np.log(label_rows * features + smoothing) - np.log(
            rows * features + label_rows.size * smoothing
        )


def _arrays_from_maps(theta) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(theta_array float64, categories, offsets) of the nested
    [label][feature] -> {value: log-probability} maps."""
    num_labels = len(theta)
    num_features = len(theta[0]) if num_labels else 0
    categories, columns = [], []
    for j in range(num_features):
        keys = sorted(theta[0][j])
        try:
            columns.append(np.asarray([[theta[i][j][k] for k in keys] for i in range(num_labels)], np.float64))
        except KeyError as e:
            raise ValueError(f"NaiveBayes theta: feature {j} lacks category {e.args[0]} under some label") from None
        if any(len(theta[i][j]) != len(keys) for i in range(num_labels)):
            raise ValueError(f"NaiveBayes theta: feature {j} has other categories under some label than under label 0")
        categories.append(np.asarray(keys, np.float64))
    offsets = np.concatenate([[0], np.cumsum([c.size for c in categories], dtype=np.int64)]).astype(np.int64)
    if not categories:
        return np.zeros((num_labels, 0)), np.zeros(0), offsets
    return np.concatenate(columns, axis=1).reshape(num_labels, -1), np.concatenate(categories), offsets


def _model_from_counts(host, num_labels: int, sorted_shape, plan: Tuple[_Field, ...], rows: int, smoothing: float):
    """Model arrays from the count program's vector (`_nb_count_impl`): each
    feature's seen bins, ascending, and their theta."""
    labels = host[:num_labels].view(np.float32)
    taken = num_labels + int(np.prod(sorted_shape))
    sorted_cats = host[num_labels:taken].view(np.float32).reshape(sorted_shape)
    bins = host[taken:].reshape(num_labels, -1)
    seen = bins.any(axis=0)
    m = [int(np.count_nonzero(seen[f.offset : f.offset + f.width])) for f in plan]
    offsets = np.concatenate([[0], np.cumsum(m)]).astype(np.int64)
    first = plan[0]
    label_rows = bins[:, first.offset : first.offset + first.width].sum(axis=1, dtype=np.int64)
    categories = np.empty(offsets[-1], np.float32)
    theta_array = np.empty((num_labels, offsets[-1]), np.float32)
    for j, f in enumerate(plan):
        field = bins[:, f.offset : f.offset + f.width]
        theta = _Theta(label_rows, m[j], smoothing, int(field.max()), m[j])
        at = offsets[j]
        # in blocks, so that the host's temporaries are reused (`_Theta`)
        for start in range(0, f.width, HOST_BLOCK):
            local = np.flatnonzero(seen[f.offset + start : f.offset + min(start + HOST_BLOCK, f.width)]) + start
            k = local.size
            categories[at : at + k] = sorted_cats[f.row, local] if f.form == "sorted" else local
            for i in range(num_labels):
                theta.fill(i, np.take(field[i], local), theta_array[i, at : at + k])
            at += k
    return theta_array, categories, offsets, _log_priors(label_rows, rows, len(plan), smoothing), labels


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
    """`theta_array` [labels, categories] (float32 from a fit, float64 as a
    saved reference model or model data table gives it), `categories` (each
    feature's seen values ascending, in the dtype of the features they were
    fitted on), `offsets` (feature j's are offsets[j]:offsets[j + 1]),
    `pi` [labels] float64, `labels` [labels] float64."""

    fusable = False
    fusable_reason = "exactness contract needs host f64 rescoring of near-tie rows and a data-dependent unseen-category error, both mid-transform readbacks"

    def __init__(self):
        self.theta_array: np.ndarray = None
        self.categories: np.ndarray = None
        self.offsets: np.ndarray = None
        self.pi: np.ndarray = None  # (numLabels,) log priors
        self.labels: np.ndarray = None  # (numLabels,) label values
        self._maps = None  # the [label][feature] maps, once asked for
        self._device_tensors = None  # cached (cats, logp, pi, labels) on device

    def _set_arrays(self, theta_array, categories, offsets, pi, labels) -> "NaiveBayesModel":
        self.theta_array = theta_array
        self.categories = categories
        self.offsets = np.asarray(offsets, np.int64)
        self.pi = np.asarray(pi, dtype=np.float64)
        self.labels = np.asarray(labels, dtype=np.float64)
        self._maps = None
        self._device_tensors = None
        return self

    @property
    def theta(self) -> List[List[Dict[float, float]]]:
        """The reference's form, [label][feature] -> {value: log-probability},
        built from the arrays on first use."""
        if self._maps is None and self.theta_array is not None:
            bounds = list(zip(self.offsets[:-1].tolist(), self.offsets[1:].tolist()))
            values = [self.categories[a:b].tolist() for a, b in bounds]
            self._maps = [
                [dict(zip(values[j], row[a:b].tolist())) for j, (a, b) in enumerate(bounds)]
                for row in self.theta_array
            ]
        return self._maps

    def _feature(self, j: int):
        """(values, theta [labels, values]) of feature j."""
        a, b = int(self.offsets[j]), int(self.offsets[j + 1])
        return self.categories[a:b], self.theta_array[:, a:b]

    def set_model_data(self, *inputs: Table) -> "NaiveBayesModel":
        (model_data,) = inputs
        row = model_data.collect()[0]
        theta_array, categories, offsets = _arrays_from_maps(row["theta"])
        return self._set_arrays(
            theta_array, categories, offsets, row["piArray"].to_array(), row["labels"].to_array()
        )

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
        """(cats (d, m_max) +inf-padded, logp (d, m_max, L)) for the device
        kernel, or (None, None) where the model is scored on the host: a
        feature of more than MATCH_MAX_CATEGORIES categories, or values or
        labels float32 does not hold exactly."""
        num_labels = len(self.labels)
        widths = np.diff(self.offsets)
        d = widths.size
        m_max = int(widths.max()) if d else 0
        if not 0 < m_max <= MATCH_MAX_CATEGORIES:
            return None, None
        labels_cast = self.labels.astype(np.float32)
        if not np.array_equal(labels_cast.astype(np.float64), self.labels):
            return None, None  # labels not f32-exact: decode would round
        cats = np.full((d, m_max), np.inf, np.float32)
        logp = np.zeros((d, m_max, num_labels), np.float32)
        for j in range(d):
            values, theta = self._feature(j)
            values = np.asarray(values, np.float64)
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
            if np.any(np.diff(cast) <= 0):
                return None, None  # f32 merges distinct categories: host path
            cats[j, : values.size] = cast
            logp[j, : values.size, :] = theta.T
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
        probs = np.tile(self.pi, (n, 1))  # (n, numLabels)
        for j in range(d):
            # columnwise: the feature's ascending values and its
            # (labels, values) log-probabilities, one searchsorted gather
            values, theta = self._feature(j)
            col = X[:, j]
            pos = np.searchsorted(values, col)
            pos_clipped = np.clip(pos, 0, values.size - 1)
            unseen = (pos >= values.size) | (values[pos_clipped] != col)
            if unseen.any():
                bad = float(col[np.nonzero(unseen)[0][0]])
                raise ValueError(
                    f"Feature value {bad} in column {j} was not seen during training"
                )
            probs += theta[:, pos_clipped].T
        return self.labels[np.argmax(probs, axis=1)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(
            path,
            theta_array=self.theta_array,
            categories=self.categories,
            offsets=self.offsets,
            piArray=self.pi,
            labels=self.labels,
        )

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        arrays = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_naivebayes
        )
        if "theta_array" in arrays:
            model = (arrays["theta_array"], arrays["categories"], arrays["offsets"])
        else:  # saved as the [label][feature] maps
            model = _arrays_from_maps([list(row) for row in arrays["theta"]])
        self._set_arrays(*model, arrays["piArray"], arrays["labels"])


class _Staged(NamedTuple):
    """A device table the device routes take, as the probe and the sort
    left it."""

    X: object
    y: object
    labels: object
    sorted_cats: object
    plan: Tuple[_Field, ...]


class NaiveBayes(Estimator, NaiveBayesParams):
    checkpointable = False
    checkpoint_reason = "single-pass label/feature count aggregation; a restart recomputes the fit"

    def fit(self, *inputs: Table) -> NaiveBayesModel:
        import jax

        from ...obs import tracing

        (table,) = inputs
        with tracing.phase("fit.extract"):
            smoothing = self.get_smoothing()
            X = as_dense_matrix(table.column(self.get_features_col()), allow_device=True)
            y = table.column(self.get_label_col())
            n, d = X.shape
        metrics.inc_counter("naivebayes.fits")
        metrics.inc_counter("naivebayes.rows", n)
        with tracing.phase("fit.stage"):
            staged = self._stage(X, y) if isinstance(X, jax.Array) else None
        if staged is None:
            metrics.inc_counter("naivebayes.fields.host", d)
            arrays = self._fit_host(X, y, smoothing)
        else:
            arrays = self._fit_device(staged, smoothing)
        model = NaiveBayesModel()._set_arrays(*arrays)
        metrics.inc_counter("naivebayes.categories", int(model.offsets[-1]))
        update_existing_params(model, self)
        return model

    @staticmethod
    def _stage(X, y):
        """The plan of a device fit, or None where the table takes the host
        route. One round trip reads the probe; a second one, only where a
        feature is not an index, the distinct counts of those features.
        NaN labels or features raise here, as on the host route: a NaN
        "category" can never be matched at predict time (NaN != NaN)."""
        import jax
        import jax.numpy as jnp

        from ...ops.stats import _nunique_device, _unique_device
        from ...utils.packing import packed_device_get

        n, d = X.shape
        if n == 0 or d == 0 or X.dtype != jnp.float32:
            return None  # f64 device input (x64 on): f32 cast could merge
        if isinstance(y, jax.Array):
            if y.dtype != jnp.float32:
                return None
            y_dev = y
        else:
            y_np = np.asarray(y)
            y32 = y_np.astype(np.float32)
            if not np.array_equal(y32.astype(y_np.dtype), y_np, equal_nan=True):
                return None  # labels not f32-exact: counts would merge
            y_dev = jnp.asarray(y32)
        (probe,) = packed_device_get(_nb_probe(X, y_dev), sync_kind="probe")
        if probe[0]:
            raise ValueError("Label column contains null/NaN values")
        if probe[1]:
            raise ValueError("Feature column contains null/NaN values")
        if probe[2]:
            # +inf doubles as the category-padding sentinel of the compare
            # kernels. The host path trains inf categories exactly (and the
            # predict-side _theta_tensors guard keeps serving them on host).
            return None
        labels = np.flatnonzero(probe[4 : 4 + LABEL_RANGE]).astype(np.float32) if probe[3] else None
        index = probe[4 + LABEL_RANGE : 4 + LABEL_RANGE + d].astype(bool)
        top = probe[4 + LABEL_RANGE + d :].astype(np.int64)
        others = np.flatnonzero(~index)
        sorted_cats, widths = None, {}
        if others.size:
            Xs, distinct = _nb_sorted_cat_counts(X[:, jnp.asarray(others)])
            (distinct,) = packed_device_get(distinct, sync_kind="sort")
            if int(distinct.max()) > MATCH_MAX_CATEGORIES:
                return None
            width = bucket(int(distinct.max()))
            sorted_cats = _nb_extract_cats(Xs, width)
            widths = {int(j): width for j in others}
        plan, offset = [], 0
        for j in range(d):
            if index[j]:
                cap = int(top[j]) + 1
                form = "range" if cap <= MATCH_MAX_CATEGORIES else "scatter"
                field = _Field(form, offset, bucket(cap))
            else:
                field = _Field("sorted", offset, widths[j], int(np.searchsorted(others, j)))
            plan.append(field)
            offset += field.width
        if labels is None:  # labels the probe's compares cannot find: sorted
            (num_labels,) = packed_device_get(_nunique_device(y_dev), sync_kind="sort")
            labels = _unique_device(y_dev, int(num_labels))
        if (labels.shape[0] + 1) * offset >= 2**31:
            return None  # int32 keys of the scatter
        if sorted_cats is None:
            sorted_cats = jnp.zeros((0, 0), jnp.float32)
        return _Staged(X, y_dev, labels, sorted_cats, tuple(plan))

    @staticmethod
    def _fit_device(staged: _Staged, smoothing: float):
        """Model arrays from the device routes: the count program, the one
        read of its counts, and on the host each feature's seen bins and
        their theta."""
        from ...obs import tracing
        from ...parallel import dispatch

        plan = staged.plan
        metrics.inc_counter("naivebayes.fields.indexed", sum(f.form == "scatter" for f in plan))
        metrics.inc_counter("naivebayes.fields.compare", sum(f.form != "scatter" for f in plan))
        metrics.inc_counter("naivebayes.bins", sum(f.width for f in plan))
        out = dispatch.timed_dispatch(
            _nb_count, staged.X, staged.y, staged.labels, staged.sorted_cats, plan, COUNT_BLOCK_ROWS
        )
        with tracing.phase("fit.readback", marks=False):
            host = tracing.sync("fit", out)
        rows = staged.X.shape[0]
        return _model_from_counts(host, int(staged.labels.shape[0]), staged.sorted_cats.shape, plan, rows, smoothing)

    @staticmethod
    def _fit_host(X, y, smoothing: float):
        """Model arrays from the host: np.unique of the labels and of each
        feature, one bincount a feature."""
        X = np.asarray(X)
        y = np.asarray(y, dtype=np.float64)
        if np.isnan(y).any():
            raise ValueError("Label column contains null/NaN values")
        if np.isnan(X).any():
            # matching the device probe: a NaN "category" can never be
            # matched at predict time (NaN != NaN), so training would bake
            # in unreachable probability mass — reject like NaN labels
            raise ValueError("Feature column contains null/NaN values")
        n, d = X.shape
        labels, label_idx = np.unique(y, return_inverse=True)
        num_labels = labels.size
        label_rows = np.bincount(label_idx, minlength=num_labels)
        categories, thetas = [], []
        for j in range(d):
            values, inverse = np.unique(X[:, j], return_inverse=True)
            m = values.size
            counts = np.bincount(label_idx * m + inverse, minlength=num_labels * m).reshape(num_labels, m)
            theta = _Theta(label_rows, m, smoothing, int(counts.max(initial=0)), m)
            thetas.append(np.empty((num_labels, m), np.float32))
            for i in range(num_labels):
                theta.fill(i, counts[i], thetas[-1][i])
            categories.append(values)
        offsets = np.concatenate([[0], np.cumsum([c.size for c in categories], dtype=np.int64)])
        theta_array = np.concatenate(thetas, axis=1) if thetas else np.zeros((num_labels, 0), np.float32)
        return (
            theta_array,
            np.concatenate(categories) if categories else np.zeros(0, X.dtype),
            offsets,
            _log_priors(label_rows, n, d, smoothing),
            labels,
        )
