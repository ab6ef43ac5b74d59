"""Traffic generator `lloyd_loop`: one client, one k-means fit after another
of one resident table.

A fit job owns its chip, so the loop is closed: the next fit starts when the
centroids and counts of the last one are on the host. The traffic file gives
`rows` and `max_iter`; the configuration gives the estimator, its
hyperparameters and the table's shape. Every fit has the same seed, so every
fit of a run has to give the same model. Everything is made from `--seed`.

Two things here stand in for what `perf/run.py` reads by the linear family's
names, and go when a `benchmark` PR makes them plain (ROADMAP S6):
`globalBatchSize`, the rows one call of the work counter covers, is no
parameter of KMeans and is left out of the stage; and the configuration's work
counter lives in `perf/counters/`, from where `setup` hands it to the harness.
"""

from __future__ import annotations

import sys
import time
import traceback

import jax
import numpy as np

SPAN = "perf.fit"
UNIT_ROWS = "globalBatchSize"


class StageParams(dict):
    """The hyperparameters as `make_stage` sets them on the estimator, one
    setter an item. The harness's unit, `globalBatchSize`, is not among the
    items; asked for by name (as `perf/faults.py` asks, of whatever stands in
    the estimator's place) it reads the table's rows, Lloyd's one batch."""

    def __init__(self, params: dict, rows: int):
        super().__init__({key: value for key, value in params.items() if key != UNIT_ROWS})
        self.rows = rows

    def __missing__(self, key):
        if key == UNIT_ROWS:
            return self.rows
        raise KeyError(key)

    def with_max_iter(self, iterations: int) -> "StageParams":
        return StageParams(dict(self, maxIter=iterations), self.rows)


def packed(model) -> np.ndarray:
    """A model as one float64 vector, [centroids.ravel | counts]: the
    program's KMeansModel, or a stand-in that carries the vector itself
    (`perf/faults.py`'s Model, under `coefficient`)."""
    if hasattr(model, "centroids"):
        return np.concatenate([np.asarray(model.centroids, np.float64).ravel(), np.asarray(model.weights, np.float64)])
    return np.asarray(model.coefficient, np.float64)


def setup(ctx):
    """The table on the device, the estimator's parameters, the work counter,
    one warm-up fit of the one shape the window uses."""
    traffic, config = ctx.traffic, ctx.config
    counter = ctx.load("counters", config["work"])
    setattr(ctx.work, config["work"], getattr(counter, config["work"]))
    maker = ctx.load("tables", config["data"]["table"])
    rows = int(traffic["rows"])
    arrays = maker.make(ctx.seed_key(), rows, config["data"], ctx.mesh)
    jax.block_until_ready(arrays)
    params = dict(config["stage"]["params"], maxIter=int(traffic["max_iter"]))
    if "k" in traffic:  # a rehearsal's few centroids, so that the CPU is done in a second
        params["k"] = int(traffic["k"])
    state = {
        "arrays": arrays,
        "table": maker.to_table(arrays, config["data"]),
        "params": StageParams(params, rows),
    }
    model = packed(ctx.make_stage(state["params"]).fit(state["table"]))
    if not np.all(np.isfinite(model)):
        raise RuntimeError("the warm-up fit returned a model that is not finite")
    return state


def window(ctx, state, seconds: float):
    """Fits of the resident table until `seconds` have passed; the fit that
    is running at the deadline is finished and counted, with its time. Each
    fit is timed from the call to the centroids and counts on the host."""
    table, params = state["table"], state["params"]
    ops, answers, failed = [], [], 0
    clock = time.perf_counter
    begin = clock()
    deadline = begin + seconds
    attempted = 0
    while True:
        start = clock()
        if start >= deadline:
            break
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation(SPAN):
                model = packed(ctx.make_stage(params).fit(table))
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        end = clock()
        if not np.all(np.isfinite(model)):
            failed += 1
            continue
        ops.append((start, end, 0))
        answers.append((0, model))
    return {
        "begin": begin,
        "end": clock(),
        "ops": ops,
        "answers": answers,
        "attempted": attempted,
        "failed": failed,
        "span": SPAN,
    }


def check(ctx, state, win):
    """Every model the window's fits returned against the plain reference.
    Fills in each fit's `units`, the rows it assigned and accumulated: the
    iterations run times the table's rows. Returns the numbers compared."""
    reference = ctx.load("reference", ctx.cell["config"])
    params = state["params"]
    want, iterations, _ = reference.fit(state["arrays"], ctx.config["data"], params)
    win["units"] = [iterations * params[UNIT_ROWS] for _ in win["ops"]]
    return compared(ctx, state, reference, want, [model for _, model in win["answers"]], ctx.make_stage)


def compared(ctx, state, reference, want, models, make_stage) -> dict:
    """The widest gap of any of `models` (packed) from two references.

    `centroid_gap` and `count_gap` hold a model to `want`, the reference's
    whole fit of the table from the same initial rows: the norm of the
    centroids' difference over the norm, and the rows counted to another
    cluster than the reference's, sum |counts - reference's| / n. Lloyd's
    iterations amplify: one row that goes the other way moves two centroids,
    and a few more rows follow in the next iteration, so after maxIter
    iterations float32 arithmetic in another order is as far from the
    reference as bfloat16 is (PERF.md gives the readings). These two say that
    the fit ran the table's rows, the seed's initial rows and maxIter updates.

    The `step_` numbers say that the arithmetic is float32's. `make_stage`
    fits the table once more with maxIter one less, outside the window; the
    reference makes ONE iteration from that state, and the model has to be
    what comes out: `step_centroid_gap`, `step_count_gap` as above, and
    `step_centroid_max_gap`, the largest difference of one coordinate over
    the largest coordinate, which sees one altered centroid that the norm of
    three million coordinates would hide."""
    params = state["params"]
    k, rows = int(params["k"]), params[UNIT_ROWS]
    if not models or any(np.shape(m) != np.shape(want) for m in models):
        return dict.fromkeys(
            ("centroid_gap", "count_gap", "step_centroid_gap", "step_centroid_max_gap", "step_count_gap"), np.inf
        )
    before = packed(make_stage(params.with_max_iter(int(params["maxIter"]) - 1)).fit(state["table"]))
    stepped = reference.step(state["arrays"], reference.unpack(before, k)[0])
    compare = ctx.compare
    pairs = [reference.unpack(m, k) for m in models]
    answers = [(0, centroids) for centroids, _ in pairs]

    def count_gap(against):
        counts = reference.unpack(against, k)[1]
        return max(float(np.sum(np.abs(c - counts)) / rows) for _, c in pairs)

    whole, last = {0: reference.unpack(want, k)[0]}, {0: reference.unpack(stepped, k)[0]}
    return {
        "centroid_gap": compare.worst(compare.coefficient_gap, answers, whole),
        "count_gap": count_gap(want),
        "step_centroid_gap": compare.worst(compare.coefficient_gap, answers, last),
        "step_centroid_max_gap": compare.worst(compare.largest_miss, answers, last),
        "step_count_gap": count_gap(stepped),
    }
