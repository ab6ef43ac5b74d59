"""Plain reference: N binary logistic regressions by mini-batch SGD over the
same dense rows, one value of `reg` a member.

The reference library's schedule (SGD.java, BinaryLogisticLoss.java,
RegularizationUtils.java), written down directly for N members at once, the
coefficients stacked as one [N, dim] matrix. Every epoch first applies the
step the last epoch's gradient asks for, `coeff -= learningRate * grad / B`
and then the L2 shrink `coeff -= learningRate * reg * coeff` (a member of reg
0 has none), and then takes the mean gradient of the logistic loss at that
coefficient over rows [k*B, (k+1)*B), k = epoch mod (rows / B). The first
epoch has no gradient to apply. A member stops after maxIter epochs, or after
the epoch whose mean loss is <= tol; one final step lands after it stopped.
Members do not see each other: each is the solo fit of its `reg`, from zero.
elasticNet is 0 in this configuration and refused otherwise.

`params["reg"]` is the grid, a list (the coefficients come back [N, dim], in
the list's order), or one number (one member, [dim]).

Imports nothing of the program. A batch at a time lies beside the table
(a [B, N] matrix of margins, 40 MB). Contractions are plain matrix products at
`highest` precision for float32; `precision="bfloat16"` is the control: the
same schedule with both operands of each product rounded to bfloat16
(float32 accumulation), the step a later PR would be tempted to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _contract(a, b, precision):
    if precision == "bfloat16":
        return jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def step(coeff, grad, lr, reg, rows):
    """The update a gradient asks for, then the L2 shrink: [N, dim] each,
    `reg` [N, 1]."""
    coeff = coeff - (lr / rows) * grad
    return jnp.where(reg > 0.0, coeff - lr * reg * coeff, coeff)


def gradient(xb, yb, coeff, precision):
    """(sum over the batch's rows of each member's gradient [N, dim], each
    member's mean loss [N]) at `coeff`."""
    sign = (2.0 * yb - 1.0)[:, None]
    margin = _contract(xb, coeff.T, precision) * sign  # [B, N]
    loss = jnp.mean(jnp.logaddexp(0.0, -margin), axis=0)
    mult = -sign / (1.0 + jnp.exp(margin))
    return _contract(mult.T, xb, precision), loss


@functools.partial(jax.jit, static_argnames=("batch", "precision", "final_update"))
def _fit(features, label, reg, lr, tol, max_iter, *, batch, precision, final_update):
    members, dim = reg.shape[0], features.shape[1]
    num_batches = features.shape[0] // batch

    def cond(state):
        _, _, epochs, loss = state
        return jnp.any(jnp.logical_and(epochs < max_iter, loss > tol))

    def body(state):
        coeff, grad, epochs, loss = state
        running = jnp.logical_and(epochs < max_iter, loss > tol)
        e = jnp.max(epochs)  # the running members step together
        start = (e % num_batches) * batch
        xb = lax.dynamic_slice_in_dim(features, start, batch, 0)
        yb = lax.dynamic_slice_in_dim(label, start, batch, 0)
        stepped = jnp.where(e > 0, step(coeff, grad, lr, reg, batch), coeff)
        new_grad, new_loss = gradient(xb, yb, stepped, precision)
        keep = running[:, None]
        return (
            jnp.where(keep, stepped, coeff),
            jnp.where(keep, new_grad, grad),
            jnp.where(running, epochs + 1, epochs),
            jnp.where(running, new_loss, loss),
        )

    start = (
        jnp.zeros((members, dim), jnp.float32),
        jnp.zeros((members, dim), jnp.float32),
        jnp.zeros((members,), jnp.int32),
        jnp.full((members,), jnp.inf, jnp.float32),
    )
    coeff, grad, epochs, loss = lax.while_loop(cond, body, start)
    if final_update:
        coeff = jnp.where((epochs > 0)[:, None], step(coeff, grad, lr, reg, batch), coeff)
    return coeff, epochs, loss


def fit(arrays: dict, data: dict, params: dict, precision: str = "float32", final_update: bool = True):
    """(coefficients f32[N, dim] in the grid's order, or f32[dim] for one
    `reg`; the epochs the longest member ran; the members' last mean losses).
    `final_update=False` leaves the step after the last epoch out: a fault
    for `perf/faults_fleet.py`, never the schedule."""
    if params.get("elasticNet", 0.0):
        raise ValueError("this reference is written for elasticNet = 0")
    rows, batch = arrays["label"].shape[0], int(params["globalBatchSize"])
    if rows % batch:
        raise ValueError(f"{rows} rows are not a whole number of batches of {batch}")
    grid = params.get("reg", 0.0)
    reg = jnp.asarray(np.atleast_1d(np.asarray(grid, np.float32)))[:, None]
    coeff, epochs, loss = _fit(
        arrays["features"], arrays["label"], reg,
        jnp.float32(params["learningRate"]), jnp.float32(params["tol"]), jnp.int32(params["maxIter"]),
        batch=batch, precision=precision, final_update=final_update,
    )
    if np.ndim(grid) == 0:
        coeff = coeff[0]
    return coeff, int(jnp.max(epochs)), loss
