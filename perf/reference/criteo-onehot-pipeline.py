"""Plain reference: StandardScaler + OneHotEncoder + VectorAssembler +
LogisticRegression over indexed click-log columns, one partition.

What the reference library's four stages give, written out in `jax.numpy`:

- StandardScaler (StandardScaler.java; withMean false, withStd true): the
  mean and the SAMPLE standard deviation (n - 1) of every numeric column over
  all rows, two passes; a value is divided by its column's deviation (by 1
  where that is 0).
- OneHotEncoder (OneHotEncoder.java; dropLast true, handleInvalid error): a
  field's size is its largest index + 1 over all rows; index i becomes the
  unit vector e_i of length size - 1, the last category the empty vector.
- VectorAssembler (VectorAssembler.java): the inputs side by side in
  `inputCols` order: ids 0 .. integer_fields - 1 the scaled numeric values,
  field j's index at integer_fields + sum_{i<j}(size_i - 1) + index with the
  value 1, the dropped last category an empty slot (id -1, value 0).
- LogisticRegression by mini-batch SGD (SGD.java, BinaryLogisticLoss.java) on
  those rows: epoch e reads rows [k*B, (k+1)*B), k = e mod (rows / B); a row's
  score is the sum of its values times the coefficients at its ids; the
  gradient adds each value times the row's multiplier into its id; then
  `coeff -= learningRate * grad / B`. It stops after maxIter epochs or after
  the epoch whose mean loss is <= tol. reg and elasticNet are 0 here.

Imports nothing of the program or of another reference. float32 throughout;
`"bfloat16"` is the control: the operands of every product and quotient
rounded to bfloat16 first, sums kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _operand(a, precision):
    if precision == "bfloat16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return a


def scaler_stats(numeric, precision):
    """(mean, sample std) of every column, two passes over all rows."""
    x = _operand(numeric, precision)
    n = x.shape[0]
    mean = jnp.sum(x, axis=0) / n
    centred = _operand(x - mean, precision)
    return mean, jnp.sqrt(jnp.sum(centred * centred, axis=0) / max(n - 1, 1))


def assembled(numeric, columns, std, sizes, precision):
    """(ids i32[rows, nnz], values f32[rows, nnz], dim) of the assembled rows;
    `sizes` are the encoder's, host integers."""
    scale = jnp.where(std > 0, std, 1.0)
    counts = numeric.shape[1]
    ids = [jnp.broadcast_to(jnp.arange(counts, dtype=jnp.int32), numeric.shape)]
    values = [_operand(numeric, precision) / _operand(scale, precision)]
    offset = counts
    for column, size in zip(columns, sizes):
        kept = column < size - 1  # the last category is dropped
        ids.append(jnp.where(kept, column + offset, -1)[:, None])
        values.append(kept.astype(jnp.float32)[:, None])
        offset += size - 1
    return jnp.concatenate(ids, axis=1), jnp.concatenate(values, axis=1), offset


def epoch(idx, val, yb, coeff, lr, precision):
    """One epoch on one batch: (new coefficient, mean loss at the old one)."""
    rows = idx.shape[0]
    stored = idx >= 0
    slot = jnp.where(stored, idx, 0)
    val = jnp.where(stored, _operand(val, precision), 0.0)
    sign = 2.0 * yb - 1.0
    margin = jnp.sum(val * _operand(coeff, precision)[slot], axis=1) * sign
    loss = jnp.mean(jnp.logaddexp(0.0, -margin))
    mult = -sign / (1.0 + jnp.exp(margin))
    grad = jnp.zeros_like(coeff).at[slot].add(val * _operand(mult, precision)[:, None])
    return coeff - (lr / rows) * grad, loss


@functools.partial(jax.jit, static_argnames=("sizes", "batch", "max_iter", "precision"))
def _fit(numeric, columns, label, mean_std, lr, tol, *, sizes, batch, max_iter, precision):
    indices, values, dim = assembled(numeric, columns, mean_std[1], sizes, precision)
    num_batches = label.shape[0] // batch

    def cond(state):
        _, e, loss = state
        return jnp.logical_and(e < max_iter, loss > tol)

    def body(state):
        coeff, e, _ = state
        start = (e % num_batches) * batch
        idx = lax.dynamic_slice_in_dim(indices, start, batch, 0)
        val = lax.dynamic_slice_in_dim(values, start, batch, 0)
        yb = lax.dynamic_slice_in_dim(label, start, batch, 0)
        coeff, loss = epoch(idx, val, yb, coeff, lr, precision)
        return coeff, e + 1, loss

    init = (jnp.zeros((dim,), jnp.float32), jnp.int32(0), jnp.float32(jnp.inf))
    return lax.while_loop(cond, body, init)


_stats = jax.jit(scaler_stats, static_argnames="precision")
_largest = jax.jit(lambda columns: jnp.stack([jnp.max(column) for column in columns]))


def fit(arrays: dict, data: dict, params: dict, precision: str = "float32"):
    """(coefficient f32[dim], epochs run, last epoch's mean loss, and what
    the feature stages fitted: {"mean", "std", "sizes"})."""
    if params.get("reg", 0.0) or params.get("elasticNet", 0.0):
        raise ValueError("this reference is written for reg = elasticNet = 0")
    rows = arrays["label"].shape[0]
    batch = int(params["globalBatchSize"])
    if rows % batch:
        raise ValueError(f"{rows} rows are not a whole number of batches of {batch}")
    columns = tuple(arrays[f"C{j + 1}"] for j in range(len(data["cardinalities"])))
    with jax.default_matmul_precision("highest"):
        sizes = tuple(int(m) + 1 for m in jax.device_get(_largest(columns)))
        mean_std = _stats(arrays["numeric"], precision=precision)
        coeff, epochs, loss = _fit(
            arrays["numeric"], columns, arrays["label"], mean_std,
            jnp.float32(params["learningRate"]), jnp.float32(params["tol"]),
            sizes=sizes, batch=batch, max_iter=int(params["maxIter"]), precision=precision,
        )
    fitted = {"mean": mean_std[0], "std": mean_std[1], "sizes": sizes}
    return coeff, int(epochs), float(loss), fitted
