"""Traffic generator `fleet_fit_loop`: one client, one fleet fit after another
of one resident table.

The job is a path: N estimators of one class that differ in ONE
hyperparameter, trained over the same rows as one `FitFleet`. It owns its
chip, so the loop is closed: the next fleet fit starts when all N coefficients
of the last are on the host. The traffic file gives `rows`, `members` and
`max_iter`; the configuration gives the estimator, the hyperparameters every
member shares, and the grid: the ONE list-valued hyperparameter, a value a
member (fewer `members` than the grid has, a rehearsal's, take values evenly
spaced along it, both ends among them). Everything is made from `--seed`.

A trained row is counted as the other cells count it, a row an epoch read,
once, whatever N: `units` = the epochs the longest member ran times
`globalBatchSize`. The configuration's work counter lives in `perf/counters/`,
from where `setup` hands it to the harness (`perf/work.py` is no PR's to edit
but a `benchmark` PR's).
"""

from __future__ import annotations

import sys
import time
import traceback

import jax
import numpy as np

SPAN = "perf.fit"


def grid_of(params: dict, members: int):
    """(the swept hyperparameter's name, its `members` values)."""
    ((name, values),) = [(key, value) for key, value in params.items() if isinstance(value, list)]
    if members > len(values) or members < 2:
        raise ValueError(f"{members} members of a grid of {len(values)} values")
    last = len(values) - 1
    return name, [values[round(i * last / (members - 1))] for i in range(members)]


def fit_path(ctx, state) -> np.ndarray:
    """One job: every member's estimator built by the harness, the fleet's
    fit, the coefficients on the host as one [N, dim] float64 matrix in the
    grid's order. The program's estimators train as ONE `FitFleet`; what a
    test or a probe stands in an estimator's place (`perf/faults.py`'s
    reference stage) has no fleet, and is fitted member by member."""
    from flink_ml_tpu.api import Estimator
    from flink_ml_tpu.fleet import FitFleet

    params, sweep = state["params"], state["sweep"]
    stages = [ctx.make_stage(dict(params, **{sweep: value})) for value in params[sweep]]
    if all(isinstance(stage, Estimator) for stage in stages):
        models = FitFleet(stages).fit(state["table"])
    else:
        models = [stage.fit(state["table"]) for stage in stages]
    return np.stack([np.asarray(model.coefficient, np.float64) for model in models])


def setup(ctx):
    """The table on the device, the members' parameters, the work counter,
    one warm-up fit of the one shape the window uses."""
    traffic, config = ctx.traffic, ctx.config
    counter = ctx.load("counters", config["work"])
    setattr(ctx.work, config["work"], getattr(counter, config["work"]))
    maker = ctx.load("tables", config["data"]["table"])
    arrays = maker.make(ctx.seed_key(), int(traffic["rows"]), config["data"], ctx.mesh)
    jax.block_until_ready(arrays)
    params = dict(config["stage"]["params"], maxIter=int(traffic["max_iter"]))
    sweep, values = grid_of(params, int(traffic["members"]))
    params[sweep] = values
    state = {
        "arrays": arrays,
        "table": maker.to_table(arrays, config["data"]),
        "params": params,
        "sweep": sweep,
    }
    if not np.all(np.isfinite(fit_path(ctx, state))):
        raise RuntimeError("the warm-up fit returned a coefficient that is not finite")
    return state


def window(ctx, state, seconds: float):
    """Fleet fits of the resident table until `seconds` have passed; the one
    that is running at the deadline is finished and counted, with its time.
    Each is timed from the members' construction to the N coefficients on
    the host."""
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
                coefficients = fit_path(ctx, state)
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        end = clock()
        if not np.all(np.isfinite(coefficients)):
            failed += 1
            continue
        ops.append((start, end, 0))
        answers.append((0, coefficients))
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
    """Every member of every fleet fit the window returned against the plain
    reference's fit of the same table and grid. Fills in each fit's `units`
    from the epochs the reference ran (tol may stop a fit early). Returns the
    numbers compared."""
    reference = ctx.load("reference", ctx.cell["config"])
    want, ran, _ = reference.fit(state["arrays"], ctx.config["data"], state["params"])
    win["units"] = [ran * int(state["params"]["globalBatchSize"]) for _ in win["ops"]]
    return compared(ctx, np.asarray(want), [coefficients for _, coefficients in win["answers"]])


def compared(ctx, want, fits) -> dict:
    """The widest gap of any member of any of `fits` ([N, dim] each) from the
    reference's member of the same place in the grid: `coef_gap`, the norm of
    a member's difference over the norm of the reference's member, and
    `coef_max_gap`, a member's largest difference of one coefficient over
    the reference member's largest. Member by member, because the members'
    norms differ by the grid's four decades of shrinkage and a norm over the
    whole matrix would hear the weakly regularised ones alone."""
    compare = ctx.compare
    if not fits or any(np.shape(fit) != np.shape(want) for fit in fits):
        return {"coef_gap": np.inf, "coef_max_gap": np.inf}
    members = dict(enumerate(want))
    answers = [(i, member) for fit in fits for i, member in enumerate(fit)]
    return {
        "coef_gap": compare.worst(compare.coefficient_gap, answers, members),
        "coef_max_gap": compare.worst(compare.largest_miss, answers, members),
    }
