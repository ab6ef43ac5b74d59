"""The plain reference put in the program's place, sound or broken.

Used to read the control and the faults against the limits (perf/probe.py, on
the chip at the cell's own size) and by perf/tests to see `correct` come out
false when the timed path is broken underneath. The benchmark's own runs
never load this file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _part_of_each_batch(a, batch: int, parts: int):
    """Copied batch by batch into place on the chip that holds the rows: a
    reshape of a table that fills half the chip would cost a second table
    first, and a slice across chips would gather it."""
    keep = batch // parts

    def local(a):
        batches = a.shape[0] // batch

        def copy(k, out):
            rows = lax.dynamic_slice_in_dim(a, k * batch, keep, 0)
            return lax.dynamic_update_slice_in_dim(out, rows, k * keep, 0)

        return lax.fori_loop(0, batches, copy, jnp.zeros((batches * keep,) + a.shape[1:], a.dtype))

    spec = P("data", *([None] * (a.ndim - 1)))
    mesh = a.sharding.mesh
    fn = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    return jax.jit(fn, out_shardings=NamedSharding(mesh, spec))(a)


def part_of_each_batch(arrays: dict, batch: int, parts: int) -> dict:
    """The first 1/`parts` of every batch's rows, in order: what is trained
    on when the rest of each batch is left out."""
    return {name: _part_of_each_batch(a, batch, parts) for name, a in arrays.items()}


def sound(fit, arrays, params, chips):
    return fit(arrays, params)


def state_unchanged(fit, arrays, params, chips):
    """A fit that hands back the coefficient it started from."""
    return np.zeros_like(np.asarray(fit(arrays, params)))


def _on_part_of_each_batch(fit, arrays, params, parts):
    batch = int(params["globalBatchSize"])
    return fit(part_of_each_batch(arrays, batch, parts), dict(params, globalBatchSize=batch // parts))


def half_batch(fit, arrays, params, chips):
    """Half of each batch left out, the mean taken over the rest."""
    return _on_part_of_each_batch(fit, arrays, params, 2)


def no_exchange(fit, arrays, params, chips):
    """The exchange between chips left out: the first chip steps on the mean
    of its own share of each batch."""
    return _on_part_of_each_batch(fit, arrays, params, chips)


def answer_altered(fit, arrays, params, chips):
    """One coefficient altered where the answer is produced: the largest one
    comes back with its sign turned."""
    coeff = np.array(fit(arrays, params))
    coeff[np.argmax(np.abs(coeff))] *= -1.0
    return coeff


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "no_exchange": no_exchange,
    "answer_altered": answer_altered,
}


class Model:
    def __init__(self, coefficient):
        self.coefficient = coefficient


class ReferenceStage:
    """Stands where the program's estimator stands: `fit(table)` gives a
    model with a `coefficient`, computed by the reference with `fault`
    planted (None: sound) and in `precision`."""

    def __init__(self, reference, maker, data, params, chips=1, fault=None, precision="float32"):
        self.maker, self.params, self.chips = maker, params, chips
        self.run = FAULTS[fault] if fault else sound
        self.reference_fit = lambda arrays, params: reference.fit(
            arrays, data, params, precision=precision
        )[0]

    def fit(self, table):
        arrays = self.maker.from_table(table)
        return Model(np.asarray(self.run(self.reference_fit, arrays, self.params, self.chips)))
