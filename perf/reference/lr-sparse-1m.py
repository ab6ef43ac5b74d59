"""Plain reference: binary logistic regression by mini-batch SGD, sparse rows.

The same schedule as the dense reference (SGD.java, BinaryLogisticLoss.java;
BLAS.java's dot and axpy over a SparseVector's indices): epoch e reads rows
[k*B, (k+1)*B), k = e mod (rows / B); a row's score is the sum of its values
times the coefficients at its feature ids (a gather); the gradient adds each
value times the row's multiplier into its feature id (a scatter-add); then
`coeff -= learningRate * grad / B`. It stops after maxIter epochs or after
the epoch whose mean loss is <= tol. reg and elasticNet are 0 here.

Imports nothing of the program. float32 products and sums; `"bfloat16"` is
the control: the operands of every product rounded to bfloat16 first, sums
kept in float32.
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


def epoch(idx, val, yb, coeff, lr, precision):
    """One epoch on one batch: (new coefficient, mean loss at the old one)."""
    rows = idx.shape[0]
    val = _operand(val, precision)
    sign = 2.0 * yb - 1.0
    margin = jnp.sum(val * _operand(coeff, precision)[idx], axis=1) * sign
    loss = jnp.mean(jnp.logaddexp(0.0, -margin))
    mult = -sign / (1.0 + jnp.exp(margin))
    grad = jnp.zeros_like(coeff).at[idx].add(val * _operand(mult, precision)[:, None])
    return coeff - (lr / rows) * grad, loss


@functools.partial(jax.jit, static_argnames=("dim", "batch", "max_iter", "precision"))
def _fit(indices, values, label, lr, tol, *, dim, batch, max_iter, precision):
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


def fit(arrays: dict, data: dict, params: dict, precision: str = "float32"):
    """(coefficient f32[dim], epochs run, last epoch's mean loss)."""
    if params.get("reg", 0.0) or params.get("elasticNet", 0.0):
        raise ValueError("this reference is written for reg = elasticNet = 0")
    rows = arrays["label"].shape[0]
    batch = int(params["globalBatchSize"])
    if rows % batch:
        raise ValueError(f"{rows} rows are not a whole number of batches of {batch}")
    coeff, epochs, loss = _fit(
        arrays["indices"], arrays["values"], arrays["label"],
        jnp.float32(params["learningRate"]), jnp.float32(params["tol"]),
        dim=int(data["dim"]), batch=batch, max_iter=int(params["maxIter"]), precision=precision,
    )
    return coeff, int(epochs), float(loss)
