"""The plain k-means reference put in the program's place, sound or broken.

`perf/faults.py`'s stand-in carries a linear model's one coefficient and its
faults cut batches; a Lloyd fit has centroids and counts and one batch, the
table. Used to read the control and the faults against the limits
(perf/probe_lloyd.py, on the chip at the cell's own size) and by perf/tests to
see `correct` come out false when the timed path is broken underneath. The
benchmark's own runs never load this file.
"""

from __future__ import annotations

import numpy as np


def sound(reference, arrays, data, params, precision):
    return reference.fit(arrays, data, params, precision=precision)[0]


def update_left_out(reference, arrays, data, params, precision):
    """The update left out: the fit hands back the rows it started from."""
    return reference.fit(arrays, data, dict(params, maxIter=0), precision=precision)[0]


def half_rows(reference, arrays, data, params, precision):
    """Half the rows left out of every iteration's sums and counts (the
    initial centroids are the sound fit's)."""
    rows = arrays["features"].shape[0] // 2
    return reference.fit(arrays, data, params, precision=precision, rows=rows)[0]


def centroid_altered(reference, arrays, data, params, precision):
    """One centroid altered where the answer is produced: its largest
    coordinate comes back halved."""
    model = np.array(sound(reference, arrays, data, params, precision))
    centroids, _ = reference.unpack(model, int(params["k"]))  # a view of `model`
    centroids[np.unravel_index(np.argmax(centroids), centroids.shape)] *= 0.5
    return model


FAULTS = {
    "update_left_out": update_left_out,
    "half_rows": half_rows,
    "centroid_altered": centroid_altered,
}


class Model:
    def __init__(self, centroids, weights):
        self.centroids, self.weights = centroids, weights


class ReferenceStage:
    """Stands where the program's KMeans stands: `fit(table)` gives a model
    with `centroids` and `weights`, computed by the reference with `fault`
    planted (None: sound) and in `precision`."""

    def __init__(self, reference, maker, data, params, fault=None, precision="float32"):
        self.reference, self.maker, self.data, self.params = reference, maker, data, params
        self.run, self.precision = FAULTS[fault] if fault else sound, precision

    def fit(self, table):
        arrays = self.maker.from_table(table)
        packed = self.run(self.reference, arrays, self.data, self.params, self.precision)
        return Model(*self.reference.unpack(packed, int(self.params["k"])))
