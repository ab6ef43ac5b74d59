"""Traffic generator `naivebayes_fit_loop`: one client, one NaiveBayes fit
after another over one resident table of indexed categorical fields.

A fit job owns its chip, so the loop is closed: the next fit starts when the
last one's model is whole on the host, as arrays. The traffic file gives
`rows` (and, for a rehearsal, smaller `data`); the configuration gives the
estimator, its hyperparameters and the table's shape. Every fit reads the
same table, so every fit of a run has to give the same model. Everything is
made from `--seed`.

What is compared is the last timed fit's model against the plain reference's
fit of the same table: `category_sets_gap`, how many of the labels and the
fields' category sets differ (exact); `theta_gap`, the largest difference of
one log probability; `pi_gap`, the largest difference of one log prior.

Two things here stand in for what `perf/run.py` reads by the linear family's
names (ROADMAP S6): `globalBatchSize`, the rows one call of the work counter
covers (the table's rows: a fit reads the table once), is no parameter of
NaiveBayes and is not among the stage's items, though it can be read by name
(`lloyd_loop.StageParams`); and the configuration's work counter lives in
`perf/counters/`, from where `setup` hands it to the harness.
"""

from __future__ import annotations

import importlib.util
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

SPAN = "perf.fit"
NUMBERS = ("category_sets_gap", "theta_gap", "pi_gap")


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("perf_generators_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hyperparameters as `make_stage` sets them, with the harness's unit readable by name but not an item
StageParams = _sibling("lloyd_loop").StageParams


def refuse_a_program_without_the_indexed_route() -> None:
    """One device fit of a field of 1,000 categories. Where the program's
    counter `naivebayes.fields.indexed` does not move, its fit takes another
    route, which at this configuration's size pulls the table to the host and
    keeps a dict entry a category (minutes and tens of GB): the run ends
    here, with exit status 1, before a table is made."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.classification.naivebayes import NaiveBayes
    from flink_ml_tpu.utils import metrics

    rows = 4000
    X = jnp.asarray((np.arange(rows) % 1000).astype(np.float32)[:, None])
    y = jnp.asarray((np.arange(rows) % 2).astype(np.float32))
    before = metrics.get_counter("naivebayes.fields.indexed")
    NaiveBayes().fit(Table({"features": X, "label": y}))
    if metrics.get_counter("naivebayes.fields.indexed") == before:
        print(
            "naivebayes_fit_loop: this program's NaiveBayes counts no field of 1,000 categories on the "
            "indexed route; it cannot fit the configuration's table on the device",
            file=sys.stderr,
        )
        sys.exit(1)


def answer(model, reference) -> dict | None:
    """A model as the reference's dict: labels, each field's categories and
    theta, pi. The program's NaiveBayesModel gives views of its arrays; a
    stand-in (`perf/faults.py`'s Model) carries the reference's packed vector
    under `coefficient`, which the reference unpacks (None where it is no
    model's)."""
    if not hasattr(model, "theta_array"):
        return reference.unpack(model.coefficient)
    bounds = list(zip(model.offsets[:-1], model.offsets[1:]))
    return {
        "labels": np.asarray(model.labels),
        "categories": [model.categories[a:b] for a, b in bounds],
        "theta": [model.theta_array[:, a:b] for a, b in bounds],
        "pi": np.asarray(model.pi),
    }


def compared(want: dict, got) -> dict:
    """The numbers `correct` holds `got` (an `answer`, or None) to against
    the reference's `want`."""
    if got is None or len(got["categories"]) != len(want["categories"]):
        return dict.fromkeys(NUMBERS, math.inf)
    sets = int(not np.array_equal(got["labels"], want["labels"]))
    sets += sum(not np.array_equal(g, w) for g, w in zip(got["categories"], want["categories"]))
    if sets:
        return {"category_sets_gap": sets, "theta_gap": math.inf, "pi_gap": math.inf}
    theta = max(
        float(np.max(np.abs(np.asarray(g, np.float64) - w), initial=0.0))
        for g, w in zip(got["theta"], want["theta"])
    )
    pi = float(np.max(np.abs(np.asarray(got["pi"], np.float64) - want["pi"])))
    return {"category_sets_gap": 0, "theta_gap": theta, "pi_gap": pi}


def setup(ctx):
    """The refusal of a program without the indexed route, the table on the
    device, the work counter, one warm-up fit of the one shape the window
    uses."""
    refuse_a_program_without_the_indexed_route()
    traffic, config = ctx.traffic, ctx.config
    counter = ctx.load("counters", config["work"])
    setattr(ctx.work, config["work"], getattr(counter, config["work"]))
    data = dict(config["data"], **traffic.get("data", {}))  # a rehearsal's small cardinalities
    maker = ctx.load("tables", data["table"])
    rows = int(traffic["rows"])
    arrays = maker.make(ctx.seed_key(), rows, data, ctx.mesh)
    jax.block_until_ready(arrays)
    params = StageParams(config["stage"]["params"], rows)
    state = {"arrays": arrays, "table": maker.to_table(arrays, data), "params": params, "data": data, "rows": rows}
    model = ctx.make_stage(params).fit(state["table"])
    values = model.theta_array if hasattr(model, "theta_array") else model.coefficient  # the program's, or a stand-in's
    if not np.all(np.isfinite(values)):
        raise RuntimeError("the warm-up fit returned a model that is not finite")
    return state


def window(ctx, state, seconds: float):
    """Fits of the resident table until `seconds` have passed; the fit that
    is running at the deadline is finished and counted, with its time. Each
    fit is timed from the call to its model's arrays on the host. Only the
    last model is kept: each holds a readback of a quarter of a GB."""
    table, params = state["table"], state["params"]
    ops, failed, last = [], 0, None
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
                model = ctx.make_stage(params).fit(table)
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        end = clock()
        ops.append((start, end, 0))
        last = model
    return {
        "begin": begin,
        "end": clock(),
        "ops": ops,
        "answers": [(0, last)] if last is not None else [],
        "attempted": attempted,
        "failed": failed,
        "span": SPAN,
    }


def check(ctx, state, win):
    """The last model of the window against the plain reference. Fills in
    each fit's `units`, the table's rows. Returns the numbers compared."""
    reference = ctx.load("reference", ctx.cell["config"])
    want = reference.unpack(reference.fit(state["arrays"], state["data"], state["params"])[0])
    win["units"] = [state["rows"] for _ in win["ops"]]
    got = answer(win["answers"][-1][1], reference) if win["answers"] else None
    return compared(want, got)
