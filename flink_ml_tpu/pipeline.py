"""Pipeline / PipelineModel — sequential stage composition + transform fusion.

Mirrors flink-ml-core/.../builder/Pipeline.java:79-107 and
PipelineModel.java:63-68: `Pipeline.fit` trains each Estimator on the data
as transformed by all earlier stages, producing a `PipelineModel` of the
trained models; `PipelineModel.transform` folds inputs through every stage.

Execution of `fit` is eager (each stage consumes materialized columnar
tables). `transform` is where the serving hot path lives, and dispatching
each stage as its own XLA program pays a fixed
dispatch+readback latency once per stage — the per-stage overhead that
dominates distributed ML runtime in the Spark study (arXiv:1612.01437).
So `PipelineModel.transform` runs a **fusion planner**: consecutive stages
that expose the transform-kernel protocol (api.AlgoOperator) are
partitioned into maximal segments, each segment's composed kernel is
jitted ONCE, and the column pytree threads through the whole segment in
HBM — one device program per segment instead of one per stage, outputs
bit-identical to the eager path. Host-only stages break segments; guard
predicates (deferred validation) come back in one packed readback at the
pipeline exit or host-segment boundary.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .api import AlgoOperator, Estimator, KernelContext, Model, Stage
from .obs import tracing
from .table import SparseBatch, Table
from .utils import metrics, read_write
from .utils.lazyjit import keyed_jit


def _transform_one(stage: Stage, table: Table) -> Table:
    outputs = stage.transform(table)  # type: ignore[attr-defined]
    if len(outputs) != 1:
        raise ValueError(f"Stage {type(stage).__name__} must produce exactly 1 output table")
    return outputs[0]


# ---------------------------------------------------------------------------
# fusion planner
# ---------------------------------------------------------------------------

class _DensePlaceholder:
    """Stand-in for a dense column produced earlier in a segment (no array
    exists until the program runs); kernels' readiness hooks may only rely
    on `dtype`, which is the jit default float."""

    dtype = np.dtype("float32")


_DENSE = _DensePlaceholder()
_SPARSE = object()  # sparse placeholder: kind-only


def _column_kind(col) -> str:
    """'dense' (device array), 'sparse' (device SparseBatch) or 'host'."""
    import jax

    if isinstance(col, SparseBatch):
        return "sparse" if isinstance(col.indices, jax.Array) else "host"
    if isinstance(col, jax.Array):
        return "dense"
    return "host"


def _stage_is_fusable(stage: Stage) -> bool:
    return (
        isinstance(stage, AlgoOperator)
        and stage.supports_fusion()
        and type(stage).transform_kernel is not AlgoOperator.transform_kernel
    )


class FusedSegment:
    """A maximal run of fusable stages compiled as one device program."""

    def __init__(self, indexed_stages: Sequence[Tuple[int, Stage]]):
        self.indices = [i for i, _ in indexed_stages]
        self.stages: List[AlgoOperator] = [s for _, s in indexed_stages]
        self._jit = None
        self._traced = None  # jit.traces-counting wrapper around _run
        # guard messages in program-output order; captured at trace time
        # (fixed for a given stage list — every compiled signature of this
        # segment registers the same guards). A program-bank hit skips the
        # trace, so the messages are restored from the bank entry's extras
        # instead (compilebank.py — same list, persisted at backfill time).
        self._guard_messages: List[str] = []

    @property
    def start(self) -> int:
        return self.indices[0]

    def ready_feed(self, table: Table) -> Optional[Dict[str, Any]]:
        """The columns to feed the segment program, or None when the segment
        cannot run fused on this table (host-resident inputs, a column kind
        a stage's kernel doesn't handle, or a stage-specific veto)."""
        produced: Dict[str, Any] = {}
        feed: Dict[str, Any] = {}
        for stage in self.stages:
            view: Dict[str, Any] = {}
            sparse_inputs = False
            for name in stage.kernel_input_cols():
                if name in produced:
                    col = produced[name]
                    kind = "sparse" if col is _SPARSE else "dense"
                elif name in table:
                    col = table.column(name)
                    kind = _column_kind(col)
                    if kind == "host":
                        return None
                    feed[name] = col
                else:
                    return None
                if kind == "sparse" and not stage.kernel_supports_sparse:
                    return None
                sparse_inputs = sparse_inputs or kind == "sparse"
                view[name] = col
            if not stage.kernel_ready(view):
                return None
            out_marker = _SPARSE if stage.kernel_output_sparse(sparse_inputs) else _DENSE
            for name in stage.kernel_output_cols():
                produced[name] = out_marker
        return feed

    def _run(self, consts_list, cols, keep: Optional[frozenset] = None):
        """The composed kernels over `cols`. With `keep`, only the columns it
        names are returned: what the program does not return it need not
        write, and an input it would only hand back is in the table already."""
        import jax
        import jax.numpy as jnp

        ctx = KernelContext()
        for stage, consts in zip(self.stages, consts_list):
            cols = stage.transform_kernel(consts, dict(cols), ctx)
            # pin the stage boundary: XLA must not contract/reassociate ops
            # ACROSS stages (e.g. FMA-fusing one stage's affine into the
            # next stage's reduction), or fused outputs drift a last-ulp
            # from the per-stage eager path — the bit-parity guarantee is
            # per-stage compilation regions inside ONE device program.
            # Behind a stage that rounds nothing (`kernel_exact`) there is
            # nothing to pin, and its columns fuse into the next stage
            if not stage.kernel_exact:
                cols = jax.lax.optimization_barrier(cols)
        if keep is not None:
            cols = {name: col for name, col in cols.items() if name in keep}
        # guards pack into ONE program output vector: the eventual drain is
        # a single device_get with no host-side packing dispatches
        self._guard_messages = list(ctx.guards)
        guard_vec = (
            jnp.stack([jnp.asarray(v, jnp.bool_) for v in ctx.guards.values()])
            if ctx.guards
            else jnp.zeros((0,), jnp.bool_)
        )
        return cols, guard_vec

    def bank_kernel_id(self) -> Optional[str]:
        """Process-restart-stable program-bank identity for this segment:
        stage classes + their param values (model arrays are runtime
        operands whose shapes live in the call signature, not here). None
        when a param value has no stable token — that segment skips the
        bank and keeps the classic jit path."""
        from . import compilebank

        parts = []
        for stage in self.stages:
            tokens = []
            for param, value in sorted(
                stage.get_param_map().items(), key=lambda kv: kv[0].name
            ):
                token = compilebank.static_token(value)
                if token is None:
                    return None
                tokens.append(f"{param.name}={token}")
            cls = type(stage)
            parts.append(f"{cls.__module__}.{cls.__qualname__}({','.join(tokens)})")
        return "pipeline.FusedSegment[" + ";".join(parts) + "]"

    def _traced_run(self):
        if self._traced is None:
            from .utils.lazyjit import _traced

            self._traced = _traced(self._run)
        return self._traced

    def shared_program(self, keep: frozenset):
        """(the program, the segment whose trace it holds) for this segment's
        kernels returning `keep`, one for all segments of equal stages: every
        stage vouches (`kernel_static`) that its class, params and static
        model data are all its kernel reads while traced. (None, None) where
        one does not. `Pipeline.fit` builds a segment a fit; without this
        every fit would trace and compile its own."""
        from . import compilebank

        identity = self.bank_kernel_id()
        statics = [stage.kernel_static() for stage in self.stages]
        if identity is None or any(static is None for static in statics):
            return None, None
        key = _ProgramKey((identity, compilebank.static_token(statics), tuple(sorted(keep))), self)
        program = _shared_programs(key)
        return program, program.__dict__.setdefault("owner", self)

    def execute(
        self,
        table: Table,
        feed: Dict[str, Any],
        pending: List[Tuple[Tuple[str, ...], Any]],
        keep: Optional[frozenset] = None,
    ) -> Table:
        # model constants are RUNTIME OPERANDS of the jitted program, not
        # baked trace constants: fetched per dispatch (memoized uploads —
        # `device_constants` re-uploads only after a publication bump), so
        # a swap-capable stage's live `set_model_data` reaches the next
        # batch with zero recompiles. Each stage's consts are read ONCE
        # here — the batch in flight keeps exactly the version it was
        # dispatched with, however many swaps land during its compute.
        consts_list = [stage.device_constants() for stage in self.stages]
        program = None
        if keep is not None:
            program, owner = self.shared_program(keep)
        if program is not None:
            out = program(consts_list, feed)
            self._guard_messages = owner._guard_messages
        else:
            out = self._execute_banked(consts_list, feed)
        if out is None:
            if self._jit is None:
                import jax

                # tpulint: disable=retrace-hazard,serve-path-trace -- bank-off fallback: one compile per fused segment (plan cached on stage ids + params); with a bank active execute() routes through _execute_banked and never reaches this line
                self._jit = jax.jit(self._traced_run())
            out = self._jit(consts_list, feed)
        out_cols, guard_vec = out
        if keep is not None:  # a program of the segment's own returns every column
            out_cols = {name: col for name, col in out_cols.items() if name in keep}
        if self._guard_messages:
            pending.append((tuple(self._guard_messages), guard_vec))
        for stage in self.stages:
            stage.kernel_ran(out_cols)
        return table.with_columns(out_cols)

    def _execute_banked(self, consts_list, feed):
        """Run through the AOT program bank when one is active: a hit
        calls a warm-loaded executable (zero traces, zero compiles — the
        serving no-compile SLA) and restores the trace-time guard
        messages from the entry's extras; a miss AOT-compiles and
        back-fills. None = bank off / segment unbankable."""
        from . import compilebank

        bank = compilebank.active_bank()
        if bank is None:
            return None
        kernel_id = self.bank_kernel_id()
        if kernel_id is None:
            return None

        def on_extras(extras):
            if extras and extras.get("guards") is not None:
                self._guard_messages = list(extras["guards"])

        handled, result = compilebank.banked_call(
            bank,
            kernel_id,
            self._traced_run(),
            (consts_list, feed),
            {},
            {},
            extras_fn=lambda: {"guards": list(self._guard_messages)},
            on_extras=on_extras,
        )
        return result if handled else None


class _ProgramKey:
    """Key of a segment's program among `_shared_programs`: equal by what the
    trace reads (classes, params, static model data, the columns returned),
    and holding the segment that is traced when the key is new."""

    __slots__ = ("token", "segment")

    def __init__(self, token, segment: FusedSegment):
        self.token, self.segment = token, segment

    def __hash__(self):
        return hash(self.token)

    def __eq__(self, other):
        return isinstance(other, _ProgramKey) and self.token == other.token


def _pipeline_prep_of(key: _ProgramKey):
    segment, keep = key.segment, frozenset(key.token[2])

    def _pipeline_prep(consts_list, cols):
        return segment._run(consts_list, cols, keep)

    return _pipeline_prep


_shared_programs = keyed_jit(_pipeline_prep_of)


class _FusionPlan:
    """Partition of a stage list into fused segments and eager runs."""

    def __init__(self, stages: Sequence[Stage], first: int = 0):
        self.runs: List[Tuple[str, Any]] = []  # ("fused", seg) | ("eager", i, stage)
        buf: List[Tuple[int, Stage]] = []
        for i, stage in enumerate(stages, first):
            if _stage_is_fusable(stage):
                buf.append((i, stage))
            else:
                if buf:
                    self.runs.append(("fused", FusedSegment(buf)))
                    buf = []
                self.runs.append(("eager", i, stage))
        if buf:
            self.runs.append(("fused", FusedSegment(buf)))
        self.has_fusable = any(kind == "fused" for kind, *_ in self.runs)


def _drain_guards(pending: List[Tuple[Tuple[str, ...], Any]]) -> None:
    """ONE packed readback of every accumulated guard vector (one vector
    per executed segment); raises the first registered message whose
    predicate fired. Accounted as a transform-path host sync — the only
    blocking point a fused pipeline transform has."""
    if not pending:
        return
    from .utils.packing import packed_device_get

    vectors = packed_device_get(*[v for _, v in pending], sync_kind="transform")
    entries = list(pending)
    pending.clear()
    for (messages, _), values in zip(entries, vectors):
        for message, value in zip(messages, np.asarray(values)):
            if bool(value):
                raise ValueError(message)


class PipelineModel(Model):
    """Model produced by Pipeline.fit (builder/PipelineModel.java)."""

    # the composite itself never fuses as a unit; fusion happens INSIDE its
    # own transform across the member stages' kernels
    fusable = False
    fusable_reason = "composite stage: fusion runs across its member stages"

    def __init__(self, stages: Sequence[Stage] = ()):
        self._stages: List[Stage] = list(stages)

    @property
    def stages(self) -> List[Stage]:
        return self._stages

    def _fusion_plan(self) -> _FusionPlan:
        """The cached segment plan; invalidated when the stage list, any
        stage's params, or a STATIC stage's model arrays change (a jitted
        segment bakes params at trace time; model arrays are runtime
        operands re-fed per dispatch). Swap-capable stages deliberately
        drop their array identities AND publication counter from the
        token: a live model swap must reuse the compiled plan — the swap
        is a new operand value of the same shape, not a new program."""
        token = tuple(
            (
                id(stage),
                stage.__dict__.get("_params_version", 0),
                (stage.model_data_version,) + tuple(id(a) for a in stage._constant_sources())
                if isinstance(stage, AlgoOperator) and not getattr(stage, "swap_capable", False)
                else (),
            )
            for stage in self._stages
        )
        cached = self.__dict__.get("_plan_cache")
        if cached is not None and cached[0] == token:
            return cached[1]
        plan = _FusionPlan(self._stages)
        self.__dict__["_plan_cache"] = (token, plan)
        return plan

    def _run_eager(self, index: int, stage: Stage, table: Table) -> Table:
        with tracing.span(
            "pipeline.stage",
            index=index,
            stage=type(stage).__name__,
            op="transform",
        ):
            return _transform_one(stage, table)

    def _transform_fused(
        self, table: Table, pending: List[Tuple[str, Any]]
    ) -> Table:
        """Run the fusion plan: fused segments dispatch as single programs;
        segments that aren't device-ready for this table, and non-fusable
        stages, run eagerly. Guards accumulate in `pending` and are drained
        before any eager (host-visible) work and by the caller at exit."""
        from .table import register_device_pytrees

        register_device_pytrees()
        plan = self._fusion_plan()
        fused_segments = 0
        fused_stages = 0
        for run in plan.runs:
            if run[0] == "fused":
                seg: FusedSegment = run[1]
                feed = seg.ready_feed(table)
                if feed is not None:
                    with tracing.span(
                        "pipeline.segment",
                        index=seg.start,
                        stages=",".join(type(s).__name__ for s in seg.stages),
                        numStages=len(seg.stages),
                        op="transform",
                        fused=True,
                    ):
                        table = seg.execute(table, feed, pending)
                    fused_segments += 1
                    fused_stages += len(seg.stages)
                    continue
                # not device-ready: the whole segment falls back to eager
                _drain_guards(pending)
                for i, stage in zip(seg.indices, seg.stages):
                    table = self._run_eager(i, stage, table)
            else:
                _, i, stage = run
                _drain_guards(pending)
                table = self._run_eager(i, stage, table)
        metrics.set_gauge("pipeline.fused_segments", fused_segments)
        metrics.set_gauge("pipeline.fused_stages", fused_stages)
        return table

    def transform(self, *inputs: Table) -> List[Table]:
        if len(inputs) != 1:
            raise ValueError("PipelineModel.transform expects exactly 1 input table")
        table = inputs[0]
        from . import config

        with metrics.timed("pipeline.transform"):
            if config.pipeline_fusion == "off":
                for i, stage in enumerate(self._stages):
                    table = self._run_eager(i, stage, table)
            else:
                pending: List[Tuple[str, Any]] = []
                table = self._transform_fused(table, pending)
                _drain_guards(pending)
        return [table]

    def transform_deferred(self, table: Table) -> Tuple[Table, List[Tuple[str, Any]]]:
        """Fused transform WITHOUT the exit guard drain: returns the output
        table (device-resident columns still in flight) plus the pending
        (message, device-scalar) guards. The serving runner uses this to
        overlap the next batch's upload/compute with this batch's pending
        validation, draining guards only when the batch leaves its bounded
        in-flight window (parallel/dispatch.py DrainQueue pattern)."""
        from . import config

        pending: List[Tuple[str, Any]] = []
        with metrics.timed("pipeline.transform"):
            if config.pipeline_fusion == "off":
                for i, stage in enumerate(self._stages):
                    table = self._run_eager(i, stage, table)
            else:
                table = self._transform_fused(table, pending)
        return table, pending

    def save(self, path: str) -> None:
        read_write.save_metadata(self, path, {"numStages": len(self._stages)})
        for i, stage in enumerate(self._stages):
            stage.save(read_write.get_path_for_pipeline_stage(i, len(self._stages), path))

    @classmethod
    def load(cls, path: str) -> "PipelineModel":
        metadata = read_write.load_metadata(path)
        num_stages = int(metadata.get("numStages", metadata.get("num_stages", 0)))
        stages = [
            read_write.load_stage(
                read_write.resolve_pipeline_stage_path(i, num_stages, path)
            )
            for i in range(num_stages)
        ]
        return cls(stages)


# the params that name a column a stage WRITES; every other param whose name
# ends in Col or Cols names a column it reads
_WRITTEN_COLUMN_PARAMS = ("outputCol", "outputCols", "predictionCol", "rawPredictionCol", "modelVersionCol")


def _columns_read(stage: Stage) -> Optional[set]:
    """The columns a stage's params say it reads (inputCol(s), featuresCol,
    labelCol, weightCol, categoricalCols), or None for a stage that names
    none: what such a stage reads (a SQL statement's columns) is not known."""
    names: set = set()
    for param, value in stage.get_param_map().items():
        if param.name.endswith(("Col", "Cols")) and param.name not in _WRITTEN_COLUMN_PARAMS and value:
            names.update([value] if isinstance(value, str) else value)
    return names or None


def _through(
    waiting: Sequence[Stage], first: int, table: Table, given: Table, readers: Sequence[Stage]
) -> Table:
    """The training table of a `Pipeline.fit` through the fitted stages that
    wait for it (`first` is the first one's place in the pipeline), for the
    stages that will still read it, `readers`. A fused segment returns the
    columns it writes that a later stage reads; what this fit made and no
    reader reads is dropped from the table; every guard is read back at the
    end."""
    if not waiting:
        return table
    from . import config
    from .table import register_device_pytrees

    register_device_pytrees()
    reads = [_columns_read(stage) for stage in (*waiting, *readers)]

    def read_from(place: int) -> Optional[set]:
        """What the stages from `place` of the pipeline on read; None: unknown."""
        later = reads[place - first :]
        return None if any(names is None for names in later) else set().union(*later)

    pending: List[Tuple[Tuple[str, ...], Any]] = []

    def eager(stage: Stage, table: Table) -> Table:
        _drain_guards(pending)
        return _transform_one(stage, table)  # a `stage.transform` span of its own

    for run in _FusionPlan(waiting, first).runs:
        segment = run[1] if run[0] == "fused" and config.pipeline_fusion != "off" else None
        feed = None if segment is None else segment.ready_feed(table)
        if feed is not None:
            # what a later stage of the segment reads of an earlier one's the
            # program keeps to itself
            written = {name for stage in segment.stages for name in stage.kernel_output_cols()}
            live = read_from(segment.indices[-1] + 1)
            keep = frozenset(written if live is None else written & live)
            with tracing.span(
                "pipeline.segment",
                index=segment.start,
                stages=",".join(type(s).__name__ for s in segment.stages),
                numStages=len(segment.stages),
                op="fit",
                fused=True,
            ):
                table = segment.execute(table, feed, pending, keep)
        else:
            for stage in run[1].stages if run[0] == "fused" else run[2:]:
                table = eager(stage, table)
    _drain_guards(pending)
    live = read_from(first + len(waiting))
    if live is not None:
        made = [
            name for name in table.column_names
            if name not in live and (name not in given or table.column(name) is not given.column(name))
        ]
        table = table.drop(*made)
    return table


class Pipeline(Estimator):
    """Sequential Estimator (builder/Pipeline.java:79-107)."""
    checkpointable = False
    checkpoint_reason = "composite stage: each contained estimator snapshots its own fit through config.iteration_checkpoint_dir; the pipeline itself holds no training state"

    def __init__(self, stages: Sequence[Stage] = ()):
        self._stages: List[Stage] = list(stages)

    @property
    def stages(self) -> List[Stage]:
        return self._stages

    def fit(self, *inputs: Table) -> PipelineModel:
        """Every estimator fitted on the training table as the stages before
        it leave it. The table goes through fitted stages only when the next
        estimator asks for it, through all that wait at once: device columns
        as fused programs (`FusedSegment`, shared from fit to fit) that write
        only the columns a later stage names, their guards read back once
        before the last estimator's fit. Columns this fit made and no later
        stage names are let go as it proceeds."""
        if len(inputs) != 1:
            raise ValueError("Pipeline.fit expects exactly 1 input table")
        given = table = inputs[0]
        stages = self._stages
        last = max((i for i, stage in enumerate(stages) if isinstance(stage, Estimator)), default=-1)
        model_stages: List[Stage] = []
        waiting: List[Stage] = []  # fitted, and the training table not yet through them

        def fit_stage(i: int, stage: Stage, table: Table) -> None:
            # one span per stage slot; the training table's way through the
            # fitted stages lies between them (`pipeline.segment` spans)
            with tracing.span("pipeline.stage", index=i, stage=type(stage).__name__, op="fit"):
                model_stages.append(stage.fit(table) if isinstance(stage, Estimator) else stage)

        with metrics.timed("pipeline.fit"), tracing.phase("pipeline.fit"):
            with tracing.phase("pipeline.prep"):
                read_before = metrics.get_counter("readback.bytes")
                for i, stage in enumerate(stages[: max(last, 0)]):
                    if isinstance(stage, Estimator):
                        table = _through(waiting, i - len(waiting), table, given, stages[i : last + 1])
                        waiting = []
                    fit_stage(i, stage, table)
                    if not isinstance(model_stages[-1], AlgoOperator):
                        raise TypeError(
                            f"Intermediate stage {type(stage).__name__} cannot transform data"
                        )
                    waiting.append(model_stages[-1])
                if last >= 0:
                    table = _through(waiting, last - len(waiting), table, given, stages[last : last + 1])
                metrics.inc_counter(
                    "pipeline.prep.readback_bytes", metrics.get_counter("readback.bytes") - read_before
                )
            for i in range(max(last, 0), len(stages)):
                fit_stage(i, stages[i], table)
        return PipelineModel(model_stages)

    def save(self, path: str) -> None:
        read_write.save_metadata(self, path, {"numStages": len(self._stages)})
        for i, stage in enumerate(self._stages):
            stage.save(read_write.get_path_for_pipeline_stage(i, len(self._stages), path))

    @classmethod
    def load(cls, path: str) -> "Pipeline":
        metadata = read_write.load_metadata(path)
        num_stages = int(metadata.get("numStages", metadata.get("num_stages", 0)))
        stages = [
            read_write.load_stage(
                read_write.resolve_pipeline_stage_path(i, num_stages, path)
            )
            for i in range(num_stages)
        ]
        return cls(stages)
