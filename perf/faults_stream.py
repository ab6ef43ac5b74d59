"""The plain FTRL reference put in the online learner's place, sound or broken.

`perf/faults.py`'s stand-in and its faults (a state left unchanged, half of
each batch, one coefficient altered) work on this cell as they are: the
reference's `fit` takes the bounded table of a stream's first batches and
returns the packed state [w | z | n]. The faults here are the ones an online
learner can have and a whole fit cannot: the count denominator left out of the
per-coordinate mean, a batch of the stream skipped, and a gradient taken at
the coefficient of the batch before (a delayed update). Used to read the
control and the faults against the limits (perf/probe_stream.py, on the chip
at the cell's own size) and by perf/tests. The benchmark's own runs never load
this file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def batches_of(arrays, rows: int):
    for k in range(arrays["label"].shape[0] // rows):
        yield tuple(arrays[name][k * rows : (k + 1) * rows] for name in ("indices", "values", "label"))


def folded(reference, arrays, data, params, precision, skip=None, stale=False, mean=True):
    """The reference a batch at a time from zeros, with a fault planted:
    batch `skip` left out, the row-dot read from the coefficient of the
    batch before (`stale`), or the sum where the mean belongs."""
    hyper = tuple(jnp.float32(h) for h in reference.hyperparameters(params))
    step = jax.jit(
        functools.partial(reference.batch_step, precision=precision, mean=mean), donate_argnums=0
    )
    state = reference.zeros(int(data["dim"]))
    read = None
    before = jnp.copy(state[0])
    for k, batch in enumerate(batches_of(arrays, int(params["globalBatchSize"]))):
        if k == skip:
            continue
        if stale:  # this step reads what the step before it started from
            read, before = before, jnp.copy(state[0])
        state = step(state, batch, hyper, dot_with=read)
    return reference.pack(state)


def sound(reference, arrays, data, params, precision):
    return reference.fit(arrays, data, params, precision=precision)[0]


def count_left_out(reference, arrays, data, params, precision):
    """g = grad_sum where the mean over the holding rows belongs."""
    return folded(reference, arrays, data, params, precision, mean=False)


def batch_skipped(reference, arrays, data, params, precision):
    """The stream's second batch never folded: one version too few."""
    return folded(reference, arrays, data, params, precision, skip=1)


def stale_coefficient(reference, arrays, data, params, precision):
    """Step k+1 reads w_{k-1}: an update that lags its stream by a batch."""
    return folded(reference, arrays, data, params, precision, stale=True)


FAULTS = {
    "count_left_out": count_left_out,
    "batch_skipped": batch_skipped,
    "stale_coefficient": stale_coefficient,
}


class Model:
    def __init__(self, coefficient):
        self.coefficient = coefficient


class ReferenceStage:
    """Stands where the program's OnlineLogisticRegression stands, by the
    stand-in's protocol of `generators/stream_loop.py`: `fit(table)` of a
    stream's first batches gives a model whose `coefficient` is the packed
    state after them, by the reference with `fault` planted (None: sound) and
    in `precision`."""

    def __init__(self, reference, maker, data, params, fault=None, precision="float32"):
        self.reference, self.maker, self.data, self.params = reference, maker, data, params
        self.run, self.precision = FAULTS[fault] if fault else sound, precision

    def fit(self, table):
        arrays = self.maker.from_table(table)
        return Model(np.asarray(self.run(self.reference, arrays, self.data, self.params, self.precision)))
