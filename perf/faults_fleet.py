"""The plain reference of a path put in the place of the program's `FitFleet`,
sound or broken.

`perf/faults.py`'s stand-in is one estimator's and carries one coefficient; a
fleet's faults are the fleet's: members handed back in another order, a step
every member lacks. `planted(...)` gives a class that is constructed as
`FitFleet` is, from the program's own estimators, reads what the job asks for
off them through their getters, and fits with the reference. Used to read the
control and the faults against the limits (perf/probe_fleet.py, on the chip
at the cell's own size) and by perf/tests to see `correct` come out false
when the timed path is broken underneath. The benchmark's own runs never load
this file.
"""

from __future__ import annotations

import numpy as np


def sound(reference, arrays, data, params, precision):
    return reference.fit(arrays, data, params, precision=precision)[0]


def members_reversed(reference, arrays, data, params, precision):
    """The members in the wrong order: each trained soundly, the list handed
    back from the other end."""
    return np.asarray(sound(reference, arrays, data, params, precision))[::-1]


def final_update_left_out(reference, arrays, data, params, precision):
    """The one step after the last epoch left out, for every member."""
    return reference.fit(arrays, data, params, precision=precision, final_update=False)[0]


def reg_left_out(reference, arrays, data, params, precision):
    """The regularisation never applied: N copies of the unpenalised model."""
    return sound(reference, arrays, data, dict(params, reg=[0.0] * len(params["reg"])), precision)


FAULTS = {
    "members_reversed": members_reversed,
    "final_update_left_out": final_update_left_out,
    "reg_left_out": reg_left_out,
}


class Model:
    def __init__(self, coefficient):
        self.coefficient = coefficient


def planted(reference, maker, data, fault=None, precision="float32"):
    """A class to stand where `flink_ml_tpu.fleet.FitFleet` stands."""
    run = FAULTS[fault] if fault else sound

    class ReferenceFleet:
        def __init__(self, estimators, **_):
            self.estimators = list(estimators)

        def fit(self, table):
            first = self.estimators[0]
            params = {
                "learningRate": first.get_learning_rate(),
                "elasticNet": first.get_elastic_net(),
                "globalBatchSize": first.get_global_batch_size(),
                "tol": first.get_tol(),
                "maxIter": first.get_max_iter(),
                "reg": [estimator.get_reg() for estimator in self.estimators],
            }
            coefficients = np.asarray(run(reference, maker.from_table(table), data, params, precision))
            return [Model(member) for member in coefficients]

    return ReferenceFleet
