"""Plain reference: N binary logistic regressions by mini-batch SGD over the
same hashed sparse rows, one value of `reg` a member.

The schedule of `perf/reference/lr-regpath-100.py` (SGD.java,
BinaryLogisticLoss.java, RegularizationUtils.java) over the padded-CSR rows
of `perf/reference/lr-sparse-1m.py` (BLAS.java's dot and axpy over a
SparseVector's indices). Every epoch first applies the step the last epoch's
gradient asks for, `coeff -= learningRate * grad / B`, then the L2 shrink
`coeff -= learningRate * reg * coeff` (a member of reg 0 has none), then takes
the gradient of the logistic loss at that coefficient over rows [k*B,
(k+1)*B), k = epoch mod (rows / B): a row's score is the sum of its values
times the member's coefficients at its feature ids (a gather from the
member's own [dim] coefficient), and the gradient adds each value times the
row's multiplier into its feature id (a scatter-add), so two fields of a row
that hash to one id both count. A member stops after maxIter epochs, or after
the epoch whose mean loss is <= tol; one final step lands after it stopped.
Members do not see each other: each is the solo fit of its `reg`, from zero.
elasticNet is 0 in this configuration and refused otherwise.

`params["reg"]` is the grid, a list (the coefficients come back [N, dim], in
the list's order), or one number (one member, [dim]). Members are fitted
`BLOCK` at a time, so that their state and one batch's gathered entries lie
beside the table.

Imports nothing of the program and makes no column plan. float32 products and
sums; `precision="bfloat16"` is the control: both operands of every product of
the data (the row-dot's and the gradient's) rounded to bfloat16 first, by
`lax.reduce_precision`, which the chip's compiler keeps (a pair of casts it
carries out in float32), sums kept in float32. `final_update=False` and
`shifted_column` are faults for `perf/faults_fleet_sparse.py`, never the
schedule: the step after the last epoch left out, and one column's ids read
one id further on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 25  # members fitted together: 25 x 8 MB of state, 25 x 31 MB of a batch's entries


def _operand(a, precision):
    if precision == "bfloat16":
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return a


def member_gradient(coeff, idx, val, yb, precision):
    """(the sum over the batch of one member's gradient [dim], its mean loss)
    at its own coefficient [dim]."""
    sign = 2.0 * yb - 1.0
    margin = jnp.sum(_operand(val, precision) * _operand(coeff[idx], precision), axis=1) * sign
    loss = jnp.mean(jnp.logaddexp(0.0, -margin))
    mult = -sign / (1.0 + jnp.exp(margin))
    grad = jnp.zeros_like(coeff).at[idx].add(_operand(val, precision) * _operand(mult, precision)[:, None])
    return grad, loss


def step(coeff, grad, lr, reg, rows):
    """The update a gradient asks for, then the L2 shrink: [N, dim] each,
    `reg` [N, 1]."""
    coeff = coeff - (lr / rows) * grad
    return jnp.where(reg > 0.0, coeff - lr * reg * coeff, coeff)


@functools.partial(jax.jit, static_argnames=("dim", "batch", "precision"))
def _fit(indices, values, label, reg, lr, tol, max_iter, final_update, column, shift, *, dim, batch, precision):
    members = reg.shape[0]
    num_batches = label.shape[0] // batch
    gradients = jax.vmap(member_gradient, in_axes=(0, None, None, None, None))

    def cond(state):
        _, _, epochs, loss = state
        return jnp.any(jnp.logical_and(epochs < max_iter, loss > tol))

    def body(state):
        coeff, grad, epochs, loss = state
        running = jnp.logical_and(epochs < max_iter, loss > tol)
        e = jnp.max(epochs)  # the running members step together
        start = (e % num_batches) * batch
        idx = lax.dynamic_slice_in_dim(indices, start, batch, 0)
        val = lax.dynamic_slice_in_dim(values, start, batch, 0)
        yb = lax.dynamic_slice_in_dim(label, start, batch, 0)
        # the faults' hook, runtime numbers so that every fault is the sound program (0 shifts
        # nothing); by a mask of the columns, since an index into the rows-minor table's columns
        # makes the chip's compiler copy the whole table the other way round
        hooked = (jnp.arange(idx.shape[1]) == column)[None, :] & (idx >= 0)
        idx = jnp.where(hooked, (idx + shift) % dim, idx)
        valid = idx >= 0
        idx, val = jnp.where(valid, idx, 0), jnp.where(valid, val, 0.0)
        stepped = jnp.where(e > 0, step(coeff, grad, lr, reg, batch), coeff)
        new_grad, new_loss = gradients(stepped, idx, val, yb, precision)
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
    coeff = jnp.where(final_update & (epochs > 0)[:, None], step(coeff, grad, lr, reg, batch), coeff)
    return coeff, epochs, loss


def fit(
    arrays: dict, data: dict, params: dict, precision: str = "float32",
    final_update: bool = True, shifted_column=None,
):
    """(coefficients f32[N, dim] on the host in the grid's order, or f32[dim]
    for one `reg`; the epochs the longest member ran; the members' last mean
    losses)."""
    if params.get("elasticNet", 0.0):
        raise ValueError("this reference is written for elasticNet = 0")
    rows, batch = arrays["label"].shape[0], int(params["globalBatchSize"])
    if rows % batch:
        raise ValueError(f"{rows} rows are not a whole number of batches of {batch}")
    grid = params.get("reg", 0.0)
    regs = np.atleast_1d(np.asarray(grid, np.float32))
    coeffs, ran, losses = [], 0, []
    for first in range(0, len(regs), BLOCK):
        block = regs[first : first + BLOCK]
        # the last block is filled up to BLOCK members, so that one program serves every block
        filled = np.concatenate([block, np.full(BLOCK - len(block), block[-1], np.float32)])
        coeff, epochs, loss = _fit(
            arrays["indices"], arrays["values"], arrays["label"], jnp.asarray(filled)[:, None],
            jnp.float32(params["learningRate"]), jnp.float32(params["tol"]), jnp.int32(params["maxIter"]),
            jnp.bool_(final_update), jnp.int32(shifted_column or 0), jnp.int32(shifted_column is not None),
            dim=int(data["dim"]), batch=batch, precision=precision,
        )
        coeffs.append(np.asarray(coeff)[: len(block)])
        losses.append(np.asarray(loss)[: len(block)])
        ran = max(ran, int(jnp.max(epochs)))
    coeffs, losses = np.concatenate(coeffs), np.concatenate(losses)
    if np.ndim(grid) == 0:
        return coeffs[0], ran, losses[0]
    return coeffs, ran, losses
