"""The five-interface Stage contract.

Mirrors the reference API layer (flink-ml-core/.../api/Stage.java:43,
AlgoOperator.java:31, Transformer.java:31, Model.java:31-50,
Estimator.java:30) with Tables replaced by the columnar Table of
`flink_ml_tpu.table`. Save/load keeps the reference's directory protocol:
`{path}/metadata` JSON + model data under `{path}/data` (ReadWriteUtils.java:98-140,440-460).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional

from .param import WithParams
from .table import Table


class KernelContext:
    """Trace-time collector of deferred validation guards.

    A fused transform kernel cannot raise on data-dependent conditions (a
    Python `if` on a traced value would force a host sync mid-program), so
    kernels register a scalar predicate + message here instead. The fusion
    runner returns the guards as extra program outputs and reads them back
    in ONE packed transfer at the pipeline exit / host-segment boundary,
    raising the registered message when a predicate fired.
    """

    def __init__(self):
        self.guards: Dict[str, Any] = {}

    def guard(self, pred, message: str) -> None:
        """Register `pred` (scalar bool array, True == invalid) to raise
        ValueError(message) at the next guard drain."""
        prev = self.guards.get(message)
        self.guards[message] = pred if prev is None else prev | pred


def as_kernel_matrix(col):
    """`as_dense_matrix`'s device-passthrough shape rule for kernel code:
    a 1-D column becomes an (n, 1) matrix, everything else passes through.
    Works on tracers — kernels must not touch numpy conversion paths."""
    return col if col.ndim > 1 else col[:, None]


class Stage(WithParams, abc.ABC):
    """Base class for all pipeline nodes; persistable with params (Stage.java:43)."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        # every concrete fit/transform automatically runs under a
        # `stage.fit`/`stage.transform` span (obs/tracing.py) — per-class
        # instrumentation code would rot; a subclass hook cannot
        from .obs.tracing import instrument_stage_methods

        instrument_stage_methods(cls)
        _instrument_model_publication(cls)

    # Data-placement hint for loaders/generators: True when the stage's hot
    # path is inherently host-resident (e.g. categorical string rendering),
    # so inputs should be born host-side rather than in device HBM — the
    # analogue of scheduling a source next to its consumer.
    prefers_host_input: bool = False

    def save(self, path: str) -> None:
        from .utils import read_write

        read_write.save_metadata(self, path)
        self._save_extra(path)

    def _save_extra(self, path: str) -> None:
        """Hook for subclasses to persist model data under `{path}/data`."""

    @classmethod
    def load(cls, path: str) -> "Stage":
        from .utils import read_write

        stage = read_write.instantiate_with_params(read_write.load_metadata(path))
        if not isinstance(stage, cls):
            raise TypeError(f"Loaded stage {type(stage).__name__} is not a {cls.__name__}")
        stage._load_extra(path)
        return stage

    def _load_extra(self, path: str) -> None:
        """Hook for subclasses to restore model data from `{path}/data`."""


def _instrument_model_publication(cls) -> None:
    """Route every concrete `set_model_data` through an explicit
    constants-cache invalidation. The device-constant memo and the fusion
    plan cache key on array OBJECT IDENTITY, which is sound for the
    re-assign-never-mutate idiom — but `id()` values are reused after GC,
    and `set_model_data` replaces model arrays outside the params path, so
    a swapped model could in principle serve a stale cached upload. The
    wrapper bumps the monotone `model_data_version` (consumed by
    `device_constants` and the plan token) after every publication, making
    invalidation explicit instead of identity-coincidental."""
    fn = cls.__dict__.get("set_model_data")
    if fn is None or not callable(fn) or getattr(fn, "_publish_instrumented", False):
        return

    import functools

    @functools.wraps(fn)
    def wrapped(self, *inputs):
        result = fn(self, *inputs)
        bump = getattr(self, "bump_model_data_version", None)
        if bump is not None:
            bump()
        return result

    wrapped._publish_instrumented = True
    cls.set_model_data = wrapped


class AlgoOperator(Stage):
    """A stage that transforms N input tables into M output tables (AlgoOperator.java:31).

    Transform-kernel protocol (pipeline fusion): a stage whose transform is
    a pure per-batch device computation may set `fusable = True` and expose

    - `transform_kernel(consts, cols, ctx)` — a jit-traceable function from
      a column dict to a column dict. `consts` is the pytree returned by
      `device_constants()`; `cols` maps column names to device arrays (or
      SparseBatch); data-dependent validation goes through `ctx.guard`.
      Parameters may be read from `self` — they are trace-time constants
      (param changes invalidate the compiled plan via the params version).
    - `_kernel_constants()` — host-side model constants (arrays/scalars)
      uploaded once per model instance and cached by `device_constants()`.
    - `_constant_sources()` — the raw arrays whose identity keys the cache.

    The fusion planner (pipeline.py) composes consecutive fusable stages'
    kernels into ONE device program. Stages whose transform is inherently
    host-resident (string rendering, dynamic row counts, host-precision
    contracts) must set `fusable = False` with a non-empty `fusable_reason`
    — scripts/check_fusion_coverage.py enforces that every concrete stage
    states one or the other.
    """

    # fusion contract: True requires transform_kernel; False requires a reason
    fusable: bool = False
    fusable_reason: str = ""
    # column kinds this stage's kernel handles beyond dense arrays
    kernel_supports_sparse: bool = False
    # True when kernel_output_cols are SparseBatch (downstream gating)
    kernel_emits_sparse: bool = False
    # True when the kernel does no floating-point arithmetic (selections,
    # comparisons, casts of whole numbers): nothing the compiler contracts
    # across its boundary can change a bit of the next stage's result, so a
    # fused program needs no barrier behind it and its outputs need not be
    # written out between two stages
    kernel_exact: bool = False

    @abc.abstractmethod
    def transform(self, *inputs: Table) -> List[Table]:
        ...

    def supports_fusion(self) -> bool:
        """Param-level fusion gate — override when some param settings make
        the transform impure (e.g. handleInvalid='skip' drops rows)."""
        return self.fusable

    def transform_kernel(self, consts, cols: Dict[str, Any], ctx: KernelContext) -> Dict[str, Any]:
        raise NotImplementedError(
            f"{type(self).__name__} does not expose a transform kernel"
        )

    def kernel_input_cols(self) -> List[str]:
        """Columns the kernel reads from its input table, derived from the
        stage's column params; override when the derivation doesn't fit."""
        cols: List[str] = []
        for getter in ("get_input_col", "get_features_col"):
            if hasattr(self, getter):
                value = getattr(self, getter)()
                if value:
                    cols.append(value)
        if hasattr(self, "get_input_cols"):
            cols.extend(self.get_input_cols() or ())
        return cols

    def kernel_output_cols(self) -> List[str]:
        """Columns the kernel writes, derived from the stage's column params."""
        cols: List[str] = []
        for getter in (
            "get_output_col",
            "get_prediction_col",
            "get_raw_prediction_col",
        ):
            if hasattr(self, getter):
                value = getattr(self, getter)()
                if value:
                    cols.append(value)
        if hasattr(self, "get_output_cols"):
            cols.extend(self.get_output_cols() or ())
        return cols

    def kernel_output_sparse(self, sparse_inputs: bool) -> bool:
        """Whether the kernel's output columns are SparseBatch, given whether
        one of its inputs is: `kernel_emits_sparse` but for a stage whose
        output follows its inputs (the assembler)."""
        return self.kernel_emits_sparse

    def kernel_static(self) -> Optional[tuple]:
        """What `transform_kernel` reads off the stage while it is traced,
        beside the params: the part of the model data that shapes the program
        (an encoder's category sizes), as a hashable. Two stages of one class
        with equal params and equal `kernel_static` trace to one program, so
        `Pipeline.fit` runs a later fit's transforms with the program an
        earlier fit compiled. None says the stage does not vouch for that,
        and its programs stay its own: the default of a Model, whose kernel
        may read its arrays as constants. A plain Transformer holds nothing
        but params."""
        return ()

    def kernel_ran(self, out_cols: Dict[str, Any]) -> None:
        """Called once a run of a program that holds this stage's kernel,
        with the columns it returned: a stage that counts something a
        transform counts it here, because the kernel's own body runs only
        while it is traced."""

    def kernel_ready(self, cols: Dict[str, Any]) -> bool:
        """Runtime veto hook: `cols` maps this stage's kernel input names to
        the actual columns (or a dense placeholder for columns produced
        earlier in the segment). Override for checks the generic kind gating
        can't express (e.g. Bucketizer's split/dtype round-trip)."""
        return True

    # -- device-constant memoization ----------------------------------------
    def _kernel_constants(self) -> Dict[str, Any]:
        """Host-side constants the kernel needs (model arrays, derived
        scales). Derived values must be computed here — NOT in the kernel —
        when the eager path computes them in host precision."""
        return {}

    def _constant_sources(self) -> tuple:
        """Raw arrays whose object identity versions the constant cache."""
        return ()

    @property
    def model_data_version(self) -> int:
        """Monotone publication counter: bumped by every `set_model_data`
        (auto-routed via `_instrument_model_publication`) and by the
        versioned-publication paths of swap-capable models. Belt to the
        identity braces of `_constant_sources()` — `id()` reuse after GC
        can never serve a stale cached upload past an explicit bump."""
        return self.__dict__.get("_model_data_version", 0)

    def bump_model_data_version(self) -> None:
        """Explicit constants-cache invalidation for a model-data change."""
        self.__dict__["_model_data_version"] = self.model_data_version + 1
        self.__dict__.pop("_device_consts", None)

    def device_constants(self):
        """Device-resident `_kernel_constants()`, uploaded at most once per
        (model arrays, params) state. Model arrays are re-assigned (never
        mutated in place) across this codebase, so object identity of the
        `_constant_sources()` plus the params version — plus the explicit
        `model_data_version` publication counter — is a sound cache key.

        The upload rides the accounted staging funnel under the ledger's
        `model` category: published model constants ARE the resident
        model, so `hbm.live.model` and `residentModelBytes` follow
        publication/invalidation exactly (a republish drops the old
        constants' tree, whose tracked entries close on GC)."""
        token = (
            self.__dict__.get("_params_version", 0),
            self.model_data_version,
            tuple(id(a) for a in self._constant_sources()),
        )
        cached = self.__dict__.get("_device_consts")
        if cached is not None and cached[0] == token:
            return cached[1]
        from .parallel import prefetch

        consts = prefetch.stage_to_device(self._kernel_constants(), category="model")
        self.__dict__["_device_consts"] = (token, consts)
        return consts

    def invalidate_device_constants(self) -> None:
        self.__dict__.pop("_device_consts", None)


class Transformer(AlgoOperator):
    """Marker: a one-in-one-out record-wise AlgoOperator (Transformer.java:31)."""


class Model(Transformer):
    """A Transformer with explicit model data tables (Model.java:31-50).

    Hot-swap protocol (lifecycle.py): a model whose serving arrays may be
    replaced while a compiled plan is live sets `swap_capable = True` and
    implements the three hooks below. The fusion planner then feeds the
    model's tensors as *versioned runtime operands* — the plan cache key
    drops their identities, the jitted segment re-reads the published
    buffers per dispatch, and `publish_model_arrays` becomes a zero-pause,
    zero-recompile pointer swap between batches. Publication MUST be one
    atomic reference assignment of an immutable (version, arrays) record:
    a reader holding the old reference keeps a consistent old model — no
    torn (new arrays, old version) state can ever be observed."""

    # True: model tensors ride the fused path as swappable runtime operands
    swap_capable: bool = False

    def kernel_static(self) -> Optional[tuple]:
        return None

    def set_model_data(self, *inputs: Table) -> "Model":
        raise NotImplementedError(f"{type(self).__name__} does not support set_model_data")

    def get_model_data(self) -> List[Table]:
        raise NotImplementedError(f"{type(self).__name__} does not support get_model_data")

    # -- swap-capable hooks (lifecycle.ModelLifecycle drives these) ----------
    def model_arrays(self) -> tuple:
        """The currently PUBLISHED serving arrays as one consistent tuple
        (read from a single atomic record — never field by field)."""
        raise NotImplementedError(f"{type(self).__name__} is not swap-capable")

    def publish_model_arrays(self, arrays: tuple, version: int) -> None:
        """Atomically publish `(version, arrays)` as the serving model —
        the reference's `set_model_data` + modelDataVersion bump, reborn
        as a single reference swap."""
        raise NotImplementedError(f"{type(self).__name__} is not swap-capable")

    def kernel_constants_for(self, arrays: tuple, version: int = 0):
        """`_kernel_constants()` computed from an ARBITRARY candidate
        arrays tuple (not the published one) — the promotion gate runs
        canary batches against candidates without publishing them."""
        raise NotImplementedError(f"{type(self).__name__} is not swap-capable")


class Estimator(Stage):
    """A stage that fits a Model from training tables (Estimator.java:30).

    Checkpoint contract (enforced by scripts/check_checkpoint_coverage.py,
    tier-1 via tests/test_checkpoint_coverage.py): every concrete
    estimator must declare `checkpointable`. True means its iterative fit
    routes through the JobSnapshot API (flink_ml_tpu/ckpt/) — via
    `run_sgd`/`optimize_stream`, `iterate_unbounded`, or direct
    `save_job_snapshot`/`load_job_snapshot` calls — so a preempted fit
    resumes from the last epoch boundary under the process-wide
    `config.iteration_checkpoint_dir`. False requires a non-empty
    `checkpoint_reason` saying why there is no resumable mid-fit state
    (e.g. a single-pass aggregation whose restart simply recomputes)."""

    checkpointable: Optional[bool] = None
    checkpoint_reason: str = ""

    @abc.abstractmethod
    def fit(self, *inputs: Table) -> Model:
        ...
