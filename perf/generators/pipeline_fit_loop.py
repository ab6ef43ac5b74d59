"""Traffic generator `pipeline_fit_loop`: one client, one `Pipeline.fit` after
another over resident partitions of raw columns.

A fit job owns its chip, so the loop is closed: the next fit starts when the
last one's `PipelineModel` is whole on the host. The traffic file gives `rows`
(of one partition), `partitions` (how many resident tables the loop fits in
turn, one model a table) and `max_iter`; the configuration gives the feature
stages in front (`pipeline`: class and params of each, in order), the trainer
(`stage`, the last stage) and the raw table's shape. A fit is
`Pipeline([...]).fit(table)`, the library's own entry, timed from the call to
the scaler's mean and deviation, the encoder's sizes and the coefficient on
the host. Everything is made from the seed.

What is compared, for every fit of the window, is what the timed fit itself
returned, against the plain reference's fit of the same partition: the
encoder's sizes (`sizes_gap`, how many differ: exact), the scaler's mean and
deviation (`scaler_gap`, the wider of the two relative distances) and the
coefficient (`coef_gap`, `coef_max_gap`, as `perf/compare.py` defines them,
taken on the device: three float64 copies of 33.76M coefficients an answer
are the host's to avoid).
"""

from __future__ import annotations

import importlib
import math
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

SPAN = "perf.fit"


def make_stage(spec: dict):
    """A stage of the configuration's `pipeline`, its params set through the
    program's own setters (inputCols -> set_input_cols(*names))."""
    module, _, cls = spec["class"].rpartition(".")
    stage = getattr(importlib.import_module(module), cls)()
    for key, value in spec["params"].items():
        setter = getattr(stage, "set_" + "".join("_" + c.lower() if c.isupper() else c for c in key))
        setter(*value) if isinstance(value, list) else setter(value)
    return stage


def fit_once(ctx, params: dict, table) -> dict:
    """One `Pipeline.fit` of the configuration's stages over `table`, and what
    it leaves on the host. Whatever `make_stage` gives in the trainer's place
    that is no estimator of the program's (`perf/faults.py`: the reference
    where the program stands) stands for the WHOLE pipeline: it is fitted on
    the raw table and answers with a coefficient alone."""
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.api import Estimator

    trainer = ctx.make_stage(params)
    if not isinstance(trainer, Estimator):
        return {"coefficient": np.asarray(trainer.fit(table).coefficient)}
    return fitted(Pipeline([*(make_stage(spec) for spec in ctx.config["pipeline"]), trainer]).fit(table))


def fitted(model) -> dict:
    """What a fit leaves on the host, by stage: the first scaler's mean and
    deviation, the first encoder's sizes, the last stage's coefficient."""
    stages = model.stages
    scaler = next(s for s in stages if hasattr(s, "std"))
    encoder = next(s for s in stages if hasattr(s, "category_sizes"))
    return {
        "mean": np.asarray(scaler.mean, np.float64),
        "std": np.asarray(scaler.std, np.float64),
        "sizes": tuple(int(size) for size in encoder.category_sizes),
        "coefficient": np.asarray(stages[-1].coefficient),
    }


def refuse_a_program_that_densifies() -> None:
    """Four rows through the program's assembler: where a one-hot column comes
    out dense, a partition of this configuration would be 2.7e14 B on the
    host, and the run ends here, before a table is made."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.feature.vectorassembler import VectorAssembler
    from flink_ml_tpu.table import SparseBatch

    wide = SparseBatch(1000, np.asarray([[1], [7], [-1], [999]], np.int32), np.ones((4, 1), np.float32))
    table = Table({"a": np.zeros((4, 2), np.float32), "b": wide})
    out = VectorAssembler().set_input_cols("a", "b").set_output_col("o").transform(table)[0].column("o")
    if not isinstance(out, SparseBatch):
        raise RuntimeError(
            "this program's VectorAssembler densifies a sparse input: it cannot run a one-hot "
            "pipeline at the configuration's cardinalities"
        )


def setup(ctx):
    """Partitions on the device, the trainer's parameters, the least time the
    feature stages need a fit, one warm-up fit for the one shape the window uses."""
    refuse_a_program_that_densifies()
    traffic, config = ctx.traffic, ctx.config
    data = dict(config["data"], **traffic.get("data", {}))  # a rehearsal's small cardinalities
    maker = ctx.load("tables", data["table"])
    key = ctx.seed_key()
    rows = int(traffic["rows"])
    arrays = [
        maker.make(jax.random.fold_in(key, p), rows, data, ctx.mesh)
        for p in range(int(traffic["partitions"]))
    ]
    jax.block_until_ready(arrays)
    params = dict(config["stage"]["params"], maxIter=int(traffic["max_iter"]))
    prep_least = None
    if ctx.peak is not None:
        counter = ctx.load("counters", "pipeline_prep").pipeline_prep
        prep_least = ctx.work.least_seconds(counter(data, rows), ctx.peak, ctx.chips)["seconds"]
    state = {
        "arrays": arrays,
        "tables": [maker.to_table(a, data) for a in arrays],
        "params": params,
        "data": data,
        "prep_least_s_a_fit": prep_least,
    }
    coeff = fit_once(ctx, params, state["tables"][0])["coefficient"]
    if not np.all(np.isfinite(coeff)):
        raise RuntimeError("the warm-up fit returned a non-finite coefficient")
    return state


def window(ctx, state, seconds: float):
    """Pipeline fits in turn over the resident partitions until `seconds` have
    passed; the fit that is running at the deadline is finished and counted,
    with its time."""
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
                answer = fit_once(ctx, params, tables[index])
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        ops.append((start, clock(), index))
        answers.append((index, answer))
    end = clock()
    # a pass over 33.76M coefficients an answer is the benchmark's own work,
    # not the client's: the window's clock has stopped
    finite = [bool(np.all(np.isfinite(answer["coefficient"]))) for _, answer in answers]
    return {
        "begin": begin,
        "end": end,
        "ops": [op for op, ok in zip(ops, finite) if ok],
        "answers": [answer for answer, ok in zip(answers, finite) if ok],
        "attempted": i,
        "failed": failed + finite.count(False),
        "span": SPAN,
        "prep_least_s_a_fit": state["prep_least_s_a_fit"],
    }


def relative(answer, reference, measure) -> float:
    """`measure` of the difference over `measure` of the reference, on the
    device; an answer of another shape or not finite reads infinite."""
    answer, reference = jnp.asarray(answer, jnp.float32), jnp.asarray(reference, jnp.float32)
    if answer.shape != reference.shape:
        return math.inf
    value = float(measure(answer - reference) / jnp.maximum(measure(reference), 1e-30))
    return value if math.isfinite(value) else math.inf


def gaps(answer: dict, coeff, stats: dict) -> dict:
    """One fit's answer against the reference's (coefficient, fitted stages).
    An answer without feature stages (a stand-in's) has none to hold: 0."""
    largest = lambda a: jnp.max(jnp.abs(a))
    numbers = {
        "sizes_gap": 0.0,
        "scaler_gap": 0.0,
        "coef_gap": relative(answer["coefficient"], coeff, jnp.linalg.norm),
        "coef_max_gap": relative(answer["coefficient"], coeff, largest),
    }
    if "sizes" in answer:
        sizes = answer["sizes"]
        numbers["sizes_gap"] = float(
            abs(len(sizes) - len(stats["sizes"])) + sum(a != b for a, b in zip(sizes, stats["sizes"]))
        )
        numbers["scaler_gap"] = max(
            relative(answer[name], stats[name], jnp.linalg.norm) for name in ("mean", "std")
        )
    return numbers


def check(ctx, state, win):
    """Every answer of the window against the plain reference's fit of the
    same partition, the widest of each number. Fills in each fit's `units`,
    the rows it trained, from the epochs the reference ran."""
    reference = ctx.load("reference", ctx.cell["config"])
    params = state["params"]
    batch = int(params["globalBatchSize"])
    epochs, widest = {}, {}
    for index in sorted({index for index, _ in win["answers"]}):
        coeff, ran, _, stats = reference.fit(state["arrays"][index], state["data"], params)
        epochs[index] = ran
        for at, answer in win["answers"]:
            if at == index:
                for name, value in gaps(answer, coeff, stats).items():
                    widest[name] = max(widest.get(name, 0.0), value)
        del coeff
    win["units"] = [epochs[index] * batch for _, _, index in win["ops"]]
    names = ("sizes_gap", "scaler_gap", "coef_gap", "coef_max_gap")
    return {name: widest.get(name, math.inf) for name in names}
