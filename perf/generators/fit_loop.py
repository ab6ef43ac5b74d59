"""Traffic generator `fit_loop`: one client, one fit after another.

A fit job owns its chips, so the loop is closed: the next fit starts when the
model of the last one is on the host. The traffic file gives `rows` (of one
table), `partitions` (how many resident tables the loop fits in turn, one
model a table) and `max_iter`; the configuration gives the estimator, its
hyperparameters and the table's shape. Everything is made from the seed.
"""

from __future__ import annotations

import sys
import time
import traceback

import jax
import numpy as np

SPAN = "perf.fit"


def setup(ctx):
    """Tables on the device, the estimator's parameters, one warm-up fit for
    the one shape the window uses."""
    traffic, config = ctx.traffic, ctx.config
    maker = ctx.load("tables", config["data"]["table"])
    key = ctx.seed_key()
    partitions = int(traffic["partitions"])
    arrays = [
        maker.make(jax.random.fold_in(key, p), int(traffic["rows"]), config["data"], ctx.mesh)
        for p in range(partitions)
    ]
    jax.block_until_ready(arrays)
    params = dict(config["stage"]["params"], maxIter=int(traffic["max_iter"]))
    state = {
        "arrays": arrays,
        "tables": [maker.to_table(a, config["data"]) for a in arrays],
        "params": params,
    }
    coeff = np.asarray(ctx.make_stage(params).fit(state["tables"][0]).coefficient)
    if not np.all(np.isfinite(coeff)):
        raise RuntimeError("the warm-up fit returned a non-finite coefficient")
    return state


def window(ctx, state, seconds: float):
    """Fits in turn over the resident tables until `seconds` have passed; the
    fit that is running at the deadline is finished and counted, with its
    time. Each fit is timed from the call to the coefficient on the host."""
    tables, params = state["tables"], state["params"]
    ops, answers, failed = [], [], 0
    clock = time.perf_counter
    begin = clock()
    deadline = begin + seconds
    i = 0
    while True:
        start = clock()
        if start >= deadline:
            break
        index = i % len(tables)
        i += 1
        try:
            with jax.profiler.TraceAnnotation(SPAN):
                model = ctx.make_stage(params).fit(tables[index])
                coeff = np.asarray(model.coefficient)
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        end = clock()
        if not np.all(np.isfinite(coeff)):
            failed += 1
            continue
        ops.append((start, end, index))
        answers.append((index, coeff))
    return {
        "begin": begin,
        "end": clock(),
        "ops": ops,
        "answers": answers,
        "attempted": i,
        "failed": failed,
        "span": SPAN,
    }


def check(ctx, state, win):
    """Every coefficient the window's fits returned against the plain
    reference's fit of the same table. Fills in each fit's `units`, the rows
    it trained, from the epochs the reference ran (tol may stop a fit early).
    Returns the numbers compared."""
    reference = ctx.load("reference", ctx.cell["config"])
    params = state["params"]
    refs, epochs = {}, {}
    for index in sorted({index for index, _ in win["answers"]}):
        coeff, ran, _ = reference.fit(state["arrays"][index], ctx.config["data"], params)
        refs[index] = np.asarray(coeff)
        epochs[index] = ran
    batch = int(params["globalBatchSize"])
    win["units"] = [epochs[index] * batch for _, _, index in win["ops"]]
    compare = ctx.compare
    return {
        "coef_gap": compare.worst(compare.coefficient_gap, win["answers"], refs),
        "coef_max_gap": compare.worst(compare.largest_miss, win["answers"], refs),
    }
