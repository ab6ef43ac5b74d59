"""Traffic generator `stream_loop`: one online learner folding an unbounded
stream, one global batch after another.

The stream is a log of `batches` global batches kept on the device, replayed in
row order and wrapped: the learner's `fit` takes a `StreamTable` whose batches
are slices of the log, and `process_updates` is drained as fast as the learner
folds. The loop is closed (a learner that is behind its stream has no other
rate). An operation is a global batch, from the moment the loop asks for it to
its version being published; its unit is the batch's rows. The window ends
when the last published version's coefficient is complete on the device and
one checksum scalar of it is on the host. The traffic file gives `batches`,
`warmup` (batches folded before the window, from version 0) and
`check_version`; the configuration gives the estimator, its hyperparameters
(`globalBatchSize` is the batch's rows) and the log's shape. Everything is
made from `--seed`.

What is compared (`compared`): (a) the coefficient the timed stream itself
published at `check_version`, held as the record's own array, against the plain
reference's after the same batches from the same start; (b) one step from the
learner's own state: its w, z, n at the last version are copied, one more batch
goes through the same path, and the result is held against one reference batch
from the copy; (c) the last version is the count of batches folded, and no
batch was shed.

As `lloyd_loop` does, `setup` hands the configuration's work counter
(perf/counters/) to the harness, which looks counters up on perf/work.py alone.
A stand-in for the estimator (`perf/faults.py`, `perf/faults_stream.py`: the
reference in the program's place) has no stream to fold: its `fit` takes the
bounded table of the first v batches and returns the packed state after them.
"""

from __future__ import annotations

import math
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

SPAN = "perf.batch"
NUMBERS = (
    "coef_gap", "coef_max_gap", "step_coef_gap", "step_coef_max_gap",
    "step_state_gap", "step_state_max_gap", "version_gap", "shed",
)


def gap(answer, reference) -> float:
    """Norm of the difference over the norm of the reference, on the device
    (`compare.coefficient_gap` makes three float64 copies on the host, 5 GB at
    this size). An answer that is not finite or of another shape reads infinite."""
    if answer.shape != reference.shape:
        return math.inf
    value = float(jnp.linalg.norm(answer - reference) / jnp.maximum(jnp.linalg.norm(reference), 1e-30))
    return value if math.isfinite(value) else math.inf


def max_gap(answer, reference) -> float:
    """The largest difference of one element over the reference's largest:
    it sees one altered coefficient that the norm of 204M would hide."""
    if answer.shape != reference.shape:
        return math.inf
    value = float(jnp.max(jnp.abs(answer - reference)) / jnp.maximum(jnp.max(jnp.abs(reference)), 1e-30))
    return value if math.isfinite(value) else math.inf


def checksum(coefficient) -> float:
    return float(jnp.sum(coefficient))


class Log:
    """The resident log and its slices."""

    def __init__(self, ctx, arrays, data, params, batches):
        self.arrays, self.data, self.params = arrays, data, params
        self.batches, self.rows = batches, int(params["globalBatchSize"])
        self.maker = ctx.load("tables", data["table"])
        self.mesh = ctx.mesh
        rows = self.rows
        self._take = jax.jit(
            lambda arrays, k: {
                name: jax.lax.dynamic_slice_in_dim(a, k * rows, rows, 0) for name, a in arrays.items()
            }
        )

    def stream(self):
        """The unbounded stream: batch k of the log, k = 0, 1, ... wrapped."""
        from flink_ml_tpu.table import StreamTable

        def tables():
            k = 0
            while True:
                yield self.maker.to_table(self._take(self.arrays, np.int32(k % self.batches)), self.data)
                k += 1

        return StreamTable(tables())

    def first(self, batches: int):
        """The bounded table of the first `batches` batches (a stand-in's input)."""
        rows = batches * self.rows
        head = {
            name: jax.device_put(a[:rows], NamedSharding(self.mesh, P("data", *[None] * (a.ndim - 1))))
            for name, a in self.arrays.items()
        }
        return self.maker.to_table(head, self.data)


class Program:
    """The program's estimator over the stream."""

    def __init__(self, stage, log: Log):
        from flink_ml_tpu.linalg import DenseVector
        from flink_ml_tpu.table import Table

        initial = Table({"coefficient": [DenseVector(np.zeros(int(log.data["dim"])))]})
        self.model = stage.set_initial_model_data(initial).fit(log.stream())

    def fold(self, batches: int = 1) -> None:
        self.model.process_updates(max_batches=batches)

    @property
    def version(self) -> int:
        return self.model.model_version

    def coefficient(self):
        """The published record's own array: no copy, no readback."""
        return self.model.model_arrays()[0]

    def state(self):
        return self.model.training_state()[1]


class StandIn:
    """Whatever `make_stage` gave in the estimator's place: it folds nothing,
    and its state at a version is its `fit` of the log's first batches."""

    def __init__(self, make_stage, log: Log, reference):
        self.make_stage, self.log, self.reference = make_stage, log, reference
        self.version = 0

    def fold(self, batches: int = 1) -> None:
        self.version += batches

    def state_at(self, version: int):
        model = self.make_stage(self.log.params).fit(self.log.first(version))
        packed, dim = np.asarray(model.coefficient, np.float32), int(self.log.data["dim"])
        if packed.shape != (3 * dim,):
            raise ValueError(f"a stand-in's model is the packed state [w|z|n], not {packed.shape}")
        return tuple(jnp.asarray(part) for part in self.reference.unpack(packed, dim))


def learner_of(stage, make_stage, log, reference):
    if hasattr(stage, "set_initial_model_data"):
        return Program(stage, log)
    return StandIn(make_stage, log, reference)


def refuse_a_program_that_densifies(make_stage, params) -> None:
    """A program from before the sparse online path takes the cell's first
    batch through `to_dense()`: 4,096 x 204,184,601 float64 on the host, which
    the machine grants page by page until it kills the process. Such a program
    is told from this one by the property itself, at a size where densifying
    costs nothing: one batch of 8 sparse rows over 64 coordinates is folded, and
    the step has to count fewer state slots updated (`ftrl.slots_updated`) than
    the model has coordinates. A program that sweeps them all, or counts
    nothing, fails here, in the first seconds of set-up, with an error and
    exit code 1."""
    stage = make_stage(dict(params, globalBatchSize=8))
    if not hasattr(stage, "set_initial_model_data"):
        return
    from flink_ml_tpu.linalg import DenseVector
    from flink_ml_tpu.table import SparseBatch, StreamTable, Table
    from flink_ml_tpu.utils import metrics as program_counters

    dim, rows = 64, 8
    ids = np.arange(2 * rows, dtype=np.int32).reshape(rows, 2)
    batch = Table({
        "features": SparseBatch(dim, ids, np.ones(ids.shape, np.float32)),
        "label": np.arange(rows, dtype=np.float32) % 2,
    })
    before = program_counters.snapshot()["counters"].get("ftrl.slots_updated", 0)
    model = stage.set_initial_model_data(Table({"coefficient": [DenseVector(np.zeros(dim))]})).fit(
        StreamTable.from_batches([batch])
    )
    model.process_updates()
    slots = program_counters.snapshot()["counters"].get("ftrl.slots_updated", 0) - before
    if not 0 < slots < dim:
        raise RuntimeError(
            f"{type(stage).__name__} of this checkout cannot run the cell: a batch of {ids.size} sparse "
            f"entries over {dim} coordinates updated {slots} state slots by its own count, so it has no "
            "update over the coordinates a batch holds and would densify every batch"
        )


def sized(ctx):
    """The configuration as this run folds it: a rehearsal's traffic brings a
    small `data` (field sizes, dim) and `batch` (rows a global batch), which
    take the configuration's place for whatever reads it in this process."""
    traffic, config = ctx.traffic, ctx.config
    if "data" in traffic:
        config["data"].update(traffic["data"])
    if "batch" in traffic:
        config["stage"]["params"]["globalBatchSize"] = int(traffic["batch"])
    return config["data"], dict(config["stage"]["params"])


def setup(ctx):
    """The log on the device, the work counter, the learner at version
    `warmup`: every shape the window uses has run, and nothing is in flight."""
    from flink_ml_tpu.utils import metrics as program_counters

    traffic, config = ctx.traffic, ctx.config
    counter = ctx.load("counters", config["work"])
    setattr(ctx.work, config["work"], getattr(counter, config["work"]))
    data, params = sized(ctx)
    refuse_a_program_that_densifies(ctx.make_stage, params)
    batches, warmup, check_version = (int(traffic[k]) for k in ("batches", "warmup", "check_version"))
    if not 0 < warmup < check_version:
        raise ValueError("the checked version has to be published inside the window")
    maker = ctx.load("tables", data["table"])
    arrays = maker.make(ctx.seed_key(), batches * int(params["globalBatchSize"]), data, ctx.mesh)
    jax.block_until_ready(arrays)
    log = Log(ctx, arrays, data, params, batches)
    reference = ctx.load("reference", ctx.cell["config"])
    shed_before = program_counters.snapshot()["counters"].get("flow.shed", 0)
    learner = learner_of(ctx.make_stage(params), ctx.make_stage, log, reference)
    learner.fold(warmup)
    if isinstance(learner, Program) and not math.isfinite(checksum(learner.coefficient())):
        raise RuntimeError("the warm-up left a coefficient that is not finite")
    return {
        "log": log, "learner": learner, "reference": reference, "params": params,
        "warmup": warmup, "check_version": check_version, "held": None, "shed_before": shed_before,
    }


def hold_if_checked(state) -> None:
    """Keep the record's coefficient when the checked version is published."""
    learner = state["learner"]
    if state["held"] is None and learner.version == state["check_version"]:
        state["held"] = learner.coefficient() if isinstance(learner, Program) else True


def window(ctx, state, seconds: float):
    """Global batches folded until `seconds` have passed, then the wait for
    the last version to be whole on the device. A batch's time is the host's
    (ask to published): the device runs behind it, by what the runtime lets
    be in flight, and the window's end waits for it."""
    learner = state["learner"]
    standing_in = not isinstance(learner, Program)
    ops, failed, attempted = [], 0, 0
    clock = time.perf_counter
    begin = clock()
    deadline = begin + seconds
    while True:
        start = clock()
        if start >= deadline or (standing_in and state["held"] is not None):
            break
        attempted += 1
        try:
            with jax.profiler.TraceAnnotation(SPAN):
                learner.fold(1)
        except Exception:  # a stream that failed folds nothing more
            traceback.print_exc(file=sys.stderr)
            failed += 1
            break
        ops.append((start, clock(), 0))
        hold_if_checked(state)
    last = None
    if not standing_in and not failed:
        with jax.profiler.TraceAnnotation(SPAN):
            last = (learner.version, checksum(learner.coefficient()))
        if not math.isfinite(last[1]):
            failed += 1
    return {
        "begin": begin,
        "end": clock(),
        "ops": ops,
        "answers": [last],
        "attempted": attempted,
        "failed": failed,
        "span": SPAN,
    }


def check(ctx, state, win):
    """Fills in each batch's `units`, its rows, and returns the numbers
    compared. A window too short to publish the checked version is folded on
    to it here, outside the timed part."""
    from flink_ml_tpu.utils import metrics as program_counters

    learner, rows = state["learner"], int(state["params"]["globalBatchSize"])
    win["units"] = [rows for _ in win["ops"]]
    folded = len(win["ops"])
    if win["failed"]:
        return dict.fromkeys(NUMBERS, math.inf)
    while state["held"] is None:
        learner.fold(1)
        folded += 1
        hold_if_checked(state)
    shed = program_counters.snapshot()["counters"].get("flow.shed", 0) - state["shed_before"]
    version_gap = abs(learner.version - (state["warmup"] + folded))
    return dict(compared(ctx, state), version_gap=float(version_gap), shed=float(shed))


def compared(ctx, state) -> dict:
    """(a) and (b) of the module's docstring, for the program or a stand-in.
    The order keeps what is alive on the device at once under the chip's
    memory: the reference's own state is 2.45 GB at the cell's size, and its
    dense gradient and count 1.6 GB more."""
    learner, reference, log = state["learner"], state["reference"], state["log"]
    params, dim, version = state["params"], int(log.data["dim"]), state["check_version"]
    want = reference.run(log.arrays, params, reference.zeros(dim), 0, version)
    if isinstance(learner, Program):
        held, state["held"] = state["held"], True
        numbers = {"coef_gap": gap(held, want[0]), "coef_max_gap": max_gap(held, want[0])}
        del held, want
        # the learner's state is handed out as copies (2.45 GB): the copy before the step
        # leaves the chip, with the reference's temporaries, before the copy after it arrives
        at, before = learner.model.training_state()
        stepped = reference.run(log.arrays, params, before, at % log.batches, 1)
        del before
        learner.fold(1)
        after = learner.state()
    else:
        # a stand-in's answer is the whole packed state: all of it is held to the reference's
        after = learner.state_at(version)
        numbers = {
            "coef_gap": max(gap(a, r) for a, r in zip(after, want)),
            "coef_max_gap": max(max_gap(a, r) for a, r in zip(after, want)),
        }
        del want
        stepped = reference.run(log.arrays, params, learner.state_at(version - 1), (version - 1) % log.batches, 1)
    numbers["step_coef_gap"] = gap(after[0], stepped[0])
    numbers["step_coef_max_gap"] = max_gap(after[0], stepped[0])
    numbers["step_state_gap"] = max(gap(a, s) for a, s in zip(after[1:], stepped[1:]))
    numbers["step_state_max_gap"] = max(max_gap(a, s) for a, s in zip(after[1:], stepped[1:]))
    return numbers
