"""Span tracing core — nested, structured, always-on-cheap.

A span is one timed region of host control flow: a pipeline stage fit, a
training epoch, a packed device→host readback, an XLA compile. Spans nest
through a `contextvars.ContextVar`, so the parent chain survives threads
spawned with a copied context and is correct under generators.

Emission targets (either or both, process-wide):

- JSONL file — set `FLINK_ML_TPU_TRACE_FILE` (or `configure(trace_file=)`).
  One JSON object per line, schema:
  `{"name", "spanId", "parentId", "startUs", "durUs", "attrs"}` with
  `startUs` monotonic microseconds from the process trace origin.
- ring buffer — set `FLINK_ML_TPU_TRACE_RING=<n>` (or
  `configure(ring_size=n)`); `drain_ring()` returns and clears it.

With no sink configured `span()` returns a shared no-op context manager:
one global load + one call, no allocation — the always-on budget the
instrumented hot layers rely on (bounded by a micro-benchmark test).

Completed spans are also folded into the flat `utils.metrics` registry
(`span.<name>` timers), so `metrics.snapshot()` keeps working as the one
aggregate view.

A few phases a fit (`phase()`: `fit.total`, `fit.extract`, `fit.stage` and
`fit.layout` inside it, `fit.launch`, `fit.readback`) are spans that do not
wait for a sink: always counted (`<name>.ns`, `<name>.n`), and `fml.<name>`
host events of whatever `jax.profiler` trace is being taken, so that an idle
gap of the device can be named by what the host was doing in it
(`report.render_device_profile`).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Optional

from ..utils import metrics
from . import timeline

# Monotonic origin for startUs: perf_counter_ns at import. JSONL consumers
# only need ordering + durations, not wall-clock identity.
_ORIGIN_NS = time.perf_counter_ns()

_ids = itertools.count(1)
_current: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "flink_ml_tpu_obs_span", default=None
)

_lock = threading.Lock()
_trace_path: Optional[str] = None
_trace_file = None  # lazily-opened append handle for _trace_path
_ring: Optional[deque] = None
_enabled = False  # fast-path flag: True iff a sink is configured


def enabled() -> bool:
    """True when a trace sink (file, ring, or the timeline flight
    recorder) is configured."""
    return _enabled


def _refresh_enabled() -> None:
    """Recompute the span fast-path flag; the timeline flight recorder
    counts as a sink (timeline.configure calls this)."""
    global _enabled
    _enabled = (
        _trace_path is not None or _ring is not None or timeline.enabled()
    )
    if _enabled:
        install_jax_hooks()


def configure(
    trace_file: Optional[str] = None, ring_size: Optional[int] = None
) -> None:
    """(Re)configure the process-wide trace sinks. `None`/0 for both
    disables tracing entirely (the no-op fast path — unless the timeline
    flight recorder is configured, which keeps spans flowing)."""
    global _trace_path, _trace_file, _ring
    with _lock:
        if _trace_file is not None:
            _trace_file.close()
            _trace_file = None
        _trace_path = trace_file or None
        _ring = deque(maxlen=int(ring_size)) if ring_size else None
    _refresh_enabled()


def _init_from_env() -> None:
    path = os.environ.get("FLINK_ML_TPU_TRACE_FILE")
    ring = os.environ.get("FLINK_ML_TPU_TRACE_RING")
    if path or ring:
        configure(trace_file=path, ring_size=int(ring) if ring else None)


def drain_ring():
    """Return and clear the in-memory ring buffer's span records."""
    with _lock:
        if _ring is None:
            return []
        out = list(_ring)
        _ring.clear()
    return out


def _emit(record: Dict[str, Any]) -> None:
    global _trace_file
    with _lock:
        if _ring is not None:
            _ring.append(record)
        if _trace_path is not None:
            if _trace_file is None:
                _trace_file = open(_trace_path, "a", buffering=1)
            _trace_file.write(json.dumps(record) + "\n")


class _NoopSpan:
    """Shared do-nothing span — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, key: str, value) -> None:
        pass


_NOOP = _NoopSpan()


class Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "_start_ns", "_token")
    _marks = True  # begin and end marks on the timeline's host lane

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def __enter__(self):
        if not _jax_hooks_installed:
            # configure() may have run before jax was imported; by the time
            # real spans open, any jax work below them has imported it
            install_jax_hooks()
        parent = _current.get()
        self.parent_id = parent.span_id if parent is not None else 0
        self.span_id = next(_ids)
        self._token = _current.set(self)
        if self._marks and timeline.enabled():  # flight recorder: a live begin mark
            timeline.record_begin(timeline.host_lane(), self.name, ref=self.span_id)
        self._start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._close(time.perf_counter_ns(), exc_type)
        return False

    def _close(self, end_ns: int, exc_type) -> None:
        _current.reset(self._token)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        dur_ns = end_ns - self._start_ns
        metrics.record_time("span." + self.name, dur_ns / 1e9)
        if self._marks and timeline.enabled():
            timeline.record_end(
                timeline.host_lane(), self.name, ref=self.span_id, **self.attrs
            )
        _emit(
            {
                "name": self.name,
                "spanId": self.span_id,
                "parentId": self.parent_id,
                "startUs": (self._start_ns - _ORIGIN_NS) / 1000.0,
                "durUs": dur_ns / 1000.0,
                "attrs": self.attrs,
            }
        )


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, bound by the first phase
PHASE_PREFIX = "fml."  # not "perf.": that prefix is the benchmark's own


class Phase(Span):
    """One of the few phases a fit is split into (`fit.extract`, `fit.stage`
    with `fit.layout` inside it, `fit.launch`, `fit.readback`, all inside
    `fit.total`): once a fit on the whole-fit routes, and never per epoch,
    batch or request, where `span` keeps its no-op path. (`fit.launch` is
    the dispatch funnel's, so the chunked routes, stream, checkpointed and
    iteration, count one a chunk.) With one pair of clock reads it is
    counted whether or not anything listens (`<name>.ns` and `<name>.n` in
    `utils.metrics`), lies on the host plane of any profile being taken as
    `fml.<name>`, on the device planes' clock, and is an ordinary span
    record where a sink is configured. `dur_ns` and `start_ns` hold the
    reads, for a call site that keeps a timer of its own; one that also puts
    an event of its own on the timeline passes `marks=False`, and the
    phase leaves its begin and end marks off the host lane. The online
    loop's phases (`online.batch` with `online.ingest`, `online.launch` and
    `online.publish` inside it) are counted once a global batch; a phase
    that turns out to hold no work (the wait that found the stream at its
    end) is taken back with `void()` and leaves the counters as they were."""

    __slots__ = ("dur_ns", "_annotation", "_sunk", "_marks", "_void")

    def __init__(self, name: str, marks: bool = True):
        self.name = name
        self.attrs = {}
        self._marks = marks
        self._void = False

    def void(self) -> None:
        self._void = True

    @property
    def start_ns(self) -> int:
        return self._start_ns

    def __enter__(self):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        # an annotation only while a profile is being taken: asking costs a
        # tenth of making one that nothing records
        self._annotation = None
        if _TraceAnnotation.is_enabled():
            self._annotation = _TraceAnnotation(PHASE_PREFIX + self.name)
            self._annotation.__enter__()
        self._sunk = _enabled
        if self._sunk:
            return Span.__enter__(self)
        self._start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end_ns = time.perf_counter_ns()
        self.dur_ns = end_ns - self._start_ns
        if not self._void:
            metrics.inc_counter(self.name + ".ns", self.dur_ns)
            metrics.inc_counter(self.name + ".n")
        if self._sunk:
            self._close(end_ns, exc_type)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


phase = Phase  # spelled like `span`


def span(name: str, **attrs):
    """Context manager timing a named region nested under the current span.

    Inside the block, `set_attr`/`add_attr` attach further attributes
    (e.g. results known only at the end). With no sink configured this
    returns a shared no-op object — the call itself is the only cost."""
    if not _enabled:
        return _NOOP
    return Span(name, attrs)


def event(name: str, **attrs) -> None:
    """Zero-duration mark under the current span (e.g. a collective op
    recorded at trace time, a device-loop run summary)."""
    if not _enabled:
        return
    parent = _current.get()
    _emit(
        {
            "name": name,
            "spanId": next(_ids),
            "parentId": parent.span_id if parent is not None else 0,
            "startUs": (time.perf_counter_ns() - _ORIGIN_NS) / 1000.0,
            "durUs": 0.0,
            "attrs": attrs,
        }
    )


def current_span() -> Optional[Span]:
    return _current.get()


def add_attr(key: str, value) -> None:
    """Attach an attribute to the innermost active span (no-op outside)."""
    sp = _current.get()
    if sp is not None:
        sp.attrs[key] = value


def emit_completed(name: str, start_ns: int, dur_s: float, **attrs) -> None:
    """Record a span whose timing was measured externally (e.g. an XLA
    compile reported by jax.monitoring after the fact)."""
    if not _enabled:
        return
    parent = _current.get()
    _emit(
        {
            "name": name,
            "spanId": next(_ids),
            "parentId": parent.span_id if parent is not None else 0,
            "startUs": (start_ns - _ORIGIN_NS) / 1000.0,
            "durUs": dur_s * 1e6,
            "attrs": attrs,
        }
    )


# ---------------------------------------------------------------------------
# device/runtime accounting: readbacks, XLA compiles
# ---------------------------------------------------------------------------

def account_readback(nbytes: int, seconds: float, arrays: int = 1) -> None:
    """Fold one device→host transfer into the registry (+ a trace span).
    Called by the explicit readback funnels (`utils.packing`, the benchmark
    runner's phase barriers) — the paths every fit/transform readback rides."""
    metrics.inc_counter("readback.count")
    metrics.inc_counter("readback.bytes", int(nbytes))
    metrics.record_time("readback", seconds)
    if timeline.enabled():
        end_ns = time.perf_counter_ns()
        timeline.record_complete(
            timeline.LANE_READBACK,
            "readback",
            end_ns - int(seconds * 1e9),
            int(seconds * 1e9),
            bytes=int(nbytes),
            arrays=arrays,
        )
    if _enabled:
        emit_completed(
            "readback",
            time.perf_counter_ns() - int(seconds * 1e9),
            seconds,
            category="readback",
            bytes=int(nbytes),
            arrays=arrays,
        )


def account_collective(
    op: str,
    nbytes: int,
    chunks: int,
    axis: str,
    dense_equiv_bytes: int = None,
) -> None:
    """Fold one collective call into the registry (+ a trace event). Fired
    at TRACE time by the wrappers in parallel/collectives.py — once per
    compiled program, when the op's shapes are known. `nbytes` is the
    per-participant payload; `chunks` the bucket/leaf count the payload was
    decomposed into. For sparse index-value reductions `dense_equiv_bytes`
    is the payload the densified gradient would have moved; the running
    `collective.sparse_ratio` gauge (sparse bytes / dense-equivalent bytes
    across every sparse reduce traced so far) is THE traffic-proportionality
    metric: << 1 means gradient bytes scale with nnz, not dim."""
    metrics.inc_counter(f"collective.{op}.calls")
    metrics.inc_counter(f"collective.{op}.bytes", int(nbytes))
    if chunks > 1:
        metrics.inc_counter(f"collective.{op}.chunks", int(chunks))
    if dense_equiv_bytes:
        metrics.inc_counter("collective.sparse.bytes", int(nbytes))
        metrics.inc_counter(
            "collective.sparse.dense_equiv_bytes", int(dense_equiv_bytes)
        )
        metrics.set_gauge(
            "collective.sparse_ratio",
            metrics.get_counter("collective.sparse.bytes")
            / max(metrics.get_counter("collective.sparse.dense_equiv_bytes"), 1),
        )
    if timeline.enabled():
        timeline.record_instant(
            timeline.LANE_COLLECTIVE, f"collective.{op}", bytes=int(nbytes), axis=axis
        )
    if _enabled:
        attrs = dict(category="collective", bytes=int(nbytes), chunks=int(chunks), axis=axis)
        if dense_equiv_bytes:
            attrs["denseEquivBytes"] = int(dense_equiv_bytes)
        event(f"collective.{op}", **attrs)


def account_host_sync(kind: str = "drain", count: int = 1) -> None:
    """Fold one blocking host↔device synchronization point into the
    registry: a convergence-scalar drain, a packed fit-result readback, a
    checkpoint carry pull. `host_sync_count` is THE dispatch-pipeline
    regression metric — every sync blocks the host on the device, so a
    loop that syncs O(maxIter) times instead of O(maxIter/K) is visible
    as a counter jump in any BENCH delta."""
    metrics.inc_counter("iteration.host_sync", count)
    metrics.inc_counter(f"iteration.host_sync.{kind}", count)
    if timeline.enabled():
        timeline.record_instant(timeline.host_lane(), f"host_sync.{kind}")


def set_dispatch_depth(depth: int) -> None:
    """Record the in-flight dispatch depth a pipelined loop ran at (gauge;
    embedded in BENCH entry deltas next to host_sync_count)."""
    metrics.set_gauge("iteration.dispatch_depth", depth)


_jax_hooks_installed = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def install_jax_hooks() -> bool:
    """Register a `jax.monitoring` listener translating backend-compile
    events into `jit.compiles`/`jit.compile` metrics and `category=compile`
    spans. Idempotent; deferred until jax is already imported so this
    module never pays the jax import itself."""
    global _jax_hooks_installed
    if _jax_hooks_installed:
        return True
    import sys

    if "jax" not in sys.modules:
        return False
    import jax.monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event != _COMPILE_EVENT:
            return
        # REAL XLA backend compiles only: program-bank executable loads
        # (compilebank.py) never fire this event — they tick the distinct
        # jit.bankLoads counter instead, which is what keeps the
        # zero-compile pins (scripts/coldstart_smoke.py,
        # tests/test_modelstore.py) honest when the bank satisfies a
        # program without a compile.
        metrics.inc_counter("jit.compiles")
        metrics.record_time("jit.compile", duration)
        from . import hist

        hist.record("jit.compileMs", duration * 1000.0)
        if _enabled:
            emit_completed(
                "jit.compile",
                time.perf_counter_ns() - int(duration * 1e9),
                duration,
                category="compile",
            )

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _jax_hooks_installed = True
    return True


# ---------------------------------------------------------------------------
# automatic stage instrumentation (wired from api.Stage.__init_subclass__)
# ---------------------------------------------------------------------------

def _wrap_stage_method(fn, op: str):
    import functools

    from . import memledger

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        if op == "fit":
            # the fit.total phase and the per-fit HBM watermark
            # (hbm.peak.fit) are always on, like the metrics registry:
            # no sink required
            with Phase("fit.total"), memledger.fit_peak_scope():
                if not _enabled:
                    return fn(self, *args, **kwargs)
                with Span("stage." + op, {"stage": type(self).__name__}):
                    return fn(self, *args, **kwargs)
        if not _enabled:
            return fn(self, *args, **kwargs)
        with Span("stage." + op, {"stage": type(self).__name__}):
            return fn(self, *args, **kwargs)

    wrapper._obs_instrumented = True
    return wrapper


def instrument_stage_methods(cls) -> None:
    """Wrap a Stage subclass's own `fit`/`transform` in `stage.fit` /
    `stage.transform` spans. Inherited (already wrapped) definitions are
    left alone, so each call produces exactly one span."""
    for op in ("fit", "transform"):
        fn = cls.__dict__.get(op)
        if fn is None or not callable(fn):
            continue
        if getattr(fn, "_obs_instrumented", False) or getattr(
            fn, "__isabstractmethod__", False
        ):
            continue
        setattr(cls, op, _wrap_stage_method(fn, op))


_init_from_env()
