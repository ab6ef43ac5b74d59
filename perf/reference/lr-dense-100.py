"""Plain reference: binary logistic regression by mini-batch SGD, dense rows.

The reference library's schedule (SGD.java, BinaryLogisticLoss.java), written
down directly: epoch e reads rows [k*B, (k+1)*B) with k = e mod (rows / B),
takes the mean gradient of the logistic loss at the current coefficient, and
steps `coeff -= learningRate * grad / B`. It stops after maxIter epochs, or
after the epoch whose mean loss is <= tol (that epoch's step still lands).
reg and elasticNet are 0 in this configuration and refused otherwise.

Imports nothing of the program. Contractions run at `highest` precision for
float32; `precision="bfloat16"` is the control: the same schedule with both
operands of each contraction rounded to bfloat16 (float32 accumulation), the
step a later PR would be tempted to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _contract(a, b, precision):
    if precision == "bfloat16":
        return jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def epoch(xb, yb, coeff, lr, precision):
    """One epoch on one batch: (new coefficient, mean loss at the old one)."""
    rows = xb.shape[0]
    sign = 2.0 * yb - 1.0
    margin = _contract(xb, coeff, precision) * sign
    loss = jnp.mean(jnp.logaddexp(0.0, -margin))
    mult = -sign / (1.0 + jnp.exp(margin))
    grad = _contract(mult, xb, precision)
    return coeff - (lr / rows) * grad, loss


@functools.partial(jax.jit, static_argnames=("batch", "num_batches", "precision"))
def _epochs(features, label, first_row, coeff, e, stop, lr, tol, *, batch, num_batches, precision="float32"):
    """Epochs e, e+1, ... below `stop` on the rows held here (`features` starts
    at row `first_row` of the table), until one ends with loss <= tol."""

    def cond(state):
        _, e, loss = state
        return jnp.logical_and(e < stop, loss > tol)

    def body(state):
        coeff, e, _ = state
        start = (e % num_batches) * batch - first_row
        xb = lax.dynamic_slice_in_dim(features, start, batch, 0)
        yb = lax.dynamic_slice_in_dim(label, start, batch, 0)
        coeff, loss = epoch(xb, yb, coeff, lr, precision)
        return coeff, e + 1, loss

    return lax.while_loop(cond, body, (coeff, e, jnp.float32(jnp.inf)))


def _shards(features, label):
    """(first row, rows, features, label) of each device's share, in row
    order. A batch never straddles two shares (checked by the caller)."""
    by_start = lambda shard: shard.index[0].start or 0
    parts = zip(
        sorted(features.addressable_shards, key=by_start),
        sorted(label.addressable_shards, key=by_start),
    )
    return [(by_start(f), f.data.shape[0], f.data, l.data) for f, l in parts]


def fit(arrays: dict, data: dict, params: dict, precision: str = "float32"):
    """(coefficient f32[dim], epochs run, last epoch's mean loss).

    A table sharded by rows over several chips is walked share by share: the
    epochs whose batch lies on one chip run there, and the coefficient (dim
    floats) is carried to the next chip. One chip holds one share."""
    if params.get("reg", 0.0) or params.get("elasticNet", 0.0):
        raise ValueError("this reference is written for reg = elasticNet = 0")
    rows = arrays["label"].shape[0]
    batch, max_iter = int(params["globalBatchSize"]), int(params["maxIter"])
    if rows % batch:
        raise ValueError(f"{rows} rows are not a whole number of batches of {batch}")
    num_batches = rows // batch
    shards = _shards(arrays["features"], arrays["label"])
    if any(first % batch or held % batch for first, held, _, _ in shards):
        raise ValueError("a batch straddles two chips' shares of the table")
    lr, tol = jnp.float32(params["learningRate"]), jnp.float32(params["tol"])
    coeff = jnp.zeros((int(data["dim"]),), jnp.float32)
    e, loss = 0, float("inf")
    while e < max_iter and loss > float(tol):
        row = (e % num_batches) * batch
        first, held, features, label = next(s for s in shards if s[0] <= row < s[0] + s[1])
        # run on while the batches stay on this chip, without wrapping round
        stop = min(max_iter, e + (first + held - row) // batch)
        device = next(iter(features.devices()))
        coeff, e_dev, loss_dev = _epochs(
            features, label, jnp.int32(first), jax.device_put(coeff, device),
            jnp.int32(e), jnp.int32(stop), lr, tol,
            batch=batch, num_batches=num_batches, precision=precision,
        )
        e, loss = int(e_dev), float(loss_dev)
    return coeff, e, loss
