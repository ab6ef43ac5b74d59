"""Plain reference: online logistic regression by FTRL-Proximal, a global
batch at a time, dense over all `dim` coordinates.

McMahan et al., "Ad Click Prediction: a View from the Trenches" (KDD 2013),
Algorithm 1, in the form apache/flink-ml ships it (OnlineLogisticRegression:
CalculateLocalGradient, UpdateModel). Batch k is rows [k*B, (k+1)*B) of the
stream, in order. A row's score is the sum of its values times the
coefficients at its ids; p = sigmoid(score). Per coordinate i, over the whole
model: grad_sum[i] = sum over the rows that hold i of (p - y) * x_i, count[i]
= the rows that hold i, both `zeros(dim).at[ids].add`; then, for every i with
count[i] > 0,

    g = grad_sum / count
    sigma = (sqrt(n + g*g) - sqrt(n)) / alpha
    z += g - sigma * w ;  n += g*g
    w = 0 if |z| <= l1 else (sign(z)*l1 - z) / ((beta + sqrt(n)) / alpha + l2)

with l1 = elasticNet * reg, l2 = (1 - elasticNet) * reg, and every other
coordinate keeps its w, z, n. The sweep is over all `dim` coordinates, as the
upstream UpdateModel's loop is: independent of a program that updates only
the coordinates a batch holds. Version v is the state after v batches.

Departures from the upstream, written from memory of it: float32 where it has
double (the configuration states float32); the whole batch at once where it
adds row by row over parallel subtasks and reduces; -1 ids are padding and
count nothing.

Imports nothing of the program. float32 products and sums; `"bfloat16"` is
the control: the operands of the row-dot's and the gradient's products
rounded to bfloat16 first, sums kept in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _operand(a, precision):
    """bfloat16's 8 exponent and 7 mantissa bits, said to the compiler as a
    rounding it has to make: a cast to bfloat16 and back is one the TPU's may
    skip (`xla_allow_excess_precision`), and on the chip it skipped most."""
    if precision == "bfloat16":
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return a


def hyperparameters(params: dict):
    """(alpha, beta, l1, l2) as the upstream's FtrlIterationBody has them."""
    reg, en = float(params.get("reg", 0.0)), float(params.get("elasticNet", 0.0))
    return float(params["alpha"]), float(params["beta"]), en * reg, (1.0 - en) * reg


def batch_step(state, batch, hyper, precision="float32", dot_with=None, mean=True):
    """One global batch: (w, z, n) -> (w, z, n). `dot_with` is the coefficient
    the row-dot reads where that is not the state's own, and `mean=False`
    leaves the count denominator out: `perf/faults_stream.py`'s faults."""
    w, z, n = state
    idx, val, y = batch
    alpha, beta, l1, l2 = hyper
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    val = _operand(jnp.where(valid, val, 0.0).astype(jnp.float32), precision)
    read = w if dot_with is None else dot_with
    score = jnp.sum(val * _operand(read, precision)[safe], axis=1)
    mult = 1.0 / (1.0 + jnp.exp(-score)) - y
    grad_sum = jnp.zeros_like(w).at[safe].add(val * _operand(mult, precision)[:, None])
    count = jnp.zeros_like(w).at[safe].add(valid.astype(jnp.float32))
    held = count > 0
    g = grad_sum / jnp.maximum(count, 1.0) if mean else grad_sum
    sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / alpha
    z2 = z + g - sigma * w
    n2 = n + g * g
    w2 = jnp.where(jnp.abs(z2) <= l1, 0.0, (jnp.sign(z2) * l1 - z2) / ((beta + jnp.sqrt(n2)) / alpha + l2))
    return jnp.where(held, w2, w), jnp.where(held, z2, z), jnp.where(held, n2, n)


@functools.partial(jax.jit, static_argnames=("batch", "precision"), donate_argnames=("state",))
def _run(state, indices, values, label, hyper, first, batches, *, batch, precision):
    def body(k, state):
        start = (first + k) * batch
        rows = tuple(lax.dynamic_slice_in_dim(a, start, batch, 0) for a in (indices, values, label))
        return batch_step(state, rows, hyper, precision)

    with jax.default_matmul_precision("highest"):
        return lax.fori_loop(0, batches, body, state)


def zeros(dim: int):
    """Version 0 of the configuration: w, z, n all zeros."""
    return tuple(jnp.zeros((dim,), jnp.float32) for _ in range(3))


def run(arrays: dict, params: dict, state, first: int, batches: int, precision: str = "float32"):
    """`batches` global batches of `arrays`' rows from batch `first` on, from
    `state` (which is given up): the state after them."""
    batch = int(params["globalBatchSize"])
    if (first + batches) * batch > arrays["label"].shape[0]:
        raise ValueError(f"{arrays['label'].shape[0]} rows do not hold batches {first}..{first + batches} of {batch}")
    hyper = tuple(jnp.float32(h) for h in hyperparameters(params))
    return _run(
        state, arrays["indices"], arrays["values"], arrays["label"], hyper,
        jnp.int32(first), jnp.int32(batches), batch=batch, precision=precision,
    )


def pack(state):
    """A state as one vector on the host, [w | z | n]: what stands for a
    model's coefficient where a stand-in is put in the program's place. (On
    the device the joined vector would be 2.45 GB more beside its parts.)"""
    return np.concatenate([np.asarray(a) for a in state])


def unpack(packed, dim: int):
    return packed[:dim], packed[dim : 2 * dim], packed[2 * dim :]


def fit(arrays: dict, data: dict, params: dict, precision: str = "float32"):
    """Every whole batch of `arrays`' rows, in order, from zeros:
    (packed state f32[3*dim] on the host, batches folded, None)."""
    batches = arrays["label"].shape[0] // int(params["globalBatchSize"])
    state = run(arrays, params, zeros(int(data["dim"])), 0, batches, precision)
    return pack(state), batches, None
