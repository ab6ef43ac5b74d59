"""Traffic generator `fleet_wide_loop`: `fleet_fit_loop` for members whose
coefficients are wide. One client, one fleet fit after another of one
resident table; the job, its grid, its work counter and its comparison are
`fleet_fit_loop`'s (the traffic file gives `rows`, `members`, `max_iter`).

What differs is what a fit's answer costs the host. A fit of 100 members of a
million coefficients hands back 400 MB of float32 coefficients, and
`fleet_fit_loop` stacks every fit's as one float64 matrix, 800 MB a fit: a
window would hold several GB. Here each fit's answer is its models'
coefficients as they are, float32 (nothing copied; a program that hands back
float64 is narrowed, the same numbers, since every coefficient the program
trains is a float32), and the check stacks them. The fit is timed from the
members' construction to the N coefficients on the host, as there.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time
import traceback

import jax
import numpy as np

SPAN = "perf.fit"


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("perf_generators_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_PATH = _sibling("fleet_fit_loop")
grid_of, compared = _PATH.grid_of, _PATH.compared


def fitted(ctx, state) -> list:
    """One job: the members' estimators built by the harness, the fleet's
    fit (or, for a stand-in that has no fleet, each member's), the models."""
    from flink_ml_tpu.api import Estimator
    from flink_ml_tpu.fleet import FitFleet

    params, sweep = state["params"], state["sweep"]
    stages = [ctx.make_stage(dict(params, **{sweep: value})) for value in params[sweep]]
    if all(isinstance(stage, Estimator) for stage in stages):
        return FitFleet(stages).fit(state["table"])
    return [stage.fit(state["table"]) for stage in stages]


def answer(models) -> list:
    """The models' coefficients in order, float32: the models' own arrays."""
    return [np.asarray(model.coefficient, np.float32) for model in models]


def fit_path(ctx, state) -> np.ndarray:
    return np.stack(answer(fitted(ctx, state)))


def setup(ctx):
    """`fleet_fit_loop.setup`: the table on the device, the members'
    parameters, the work counter, one warm-up fit."""
    return _PATH.setup(ctx)


def window(ctx, state, seconds: float):
    """Fleet fits of the resident table until `seconds` have passed; the one
    that is running at the deadline is finished and counted, with its time."""
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
                models = fitted(ctx, state)
        except Exception:  # a failed fit is counted, and the loop goes on
            traceback.print_exc(file=sys.stderr)
            failed += 1
            continue
        end = clock()
        ops.append((start, end, 0))
        answers.append((0, answer(models)))
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
    """`fleet_fit_loop.check` over each fit's answer stacked: a fit of which
    a coefficient is not finite reads infinite there, and `correct` is false."""
    win["answers"] = [(table, np.stack(members)) for table, members in win["answers"]]
    return _PATH.check(ctx, state, win)
