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
(`report.render_device_profile`). Every blocking read of the device goes
through one funnel of the same form, `sync(kind, x)`: the wait apart from
the copy. The outermost fit of a thread is counted once (`fit.outer`), and
the cycle collector's pauses have a name (`host.gc`).
"""

from __future__ import annotations

import contextvars
import gc
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
            self._count()
        if self._sunk:
            self._close(end_ns, exc_type)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False

    def _count(self) -> None:
        metrics.inc_counter(self.name + ".ns", self.dur_ns)
        metrics.inc_counter(self.name + ".n")


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
# device/runtime accounting: blocking reads, collectives, XLA compiles, pauses
# ---------------------------------------------------------------------------

class _Fits(threading.local):
    """The fits open on this thread (a pipeline's fit holds its stages'):
    how many deep, and the ordinal of the outermost one, the one identifier
    every span of one fit shares."""

    depth = 0
    ordinal = 0


_fits = _Fits()
_fit_ordinals = itertools.count(1)


class _SyncStep(Phase):
    """One of the two steps of `sync`, `sync.<kind>.wait` or
    `sync.<kind>.copy`: a phase that counts its time alone (the funnel
    counts the sync), and adds it to the open fit's sum."""

    __slots__ = ("_sum",)

    def __init__(self, kind: str, step: str):
        Phase.__init__(self, "sync." + kind + "." + step, marks=False)
        self._sum = "fit.sync." + step + ".ns"
        if _enabled:
            self.attrs = {"category": "readback"}
            if _fits.depth:
                self.attrs["fit"] = _fits.ordinal

    def _count(self) -> None:
        metrics.inc_counter(self.name + ".ns", self.dur_ns)
        if _fits.depth:
            metrics.inc_counter(self._sum, self.dur_ns)


def sync(kind: str, x, arrays: int = 1, copy: bool = True):
    """THE funnel of a blocking read: the host waits for the device value
    `x` and takes it, in two steps, each one pair of clock reads. The
    **wait** (`jax.block_until_ready`: the device's work and the runtime's
    notice of it) and then the **copy** (`np.asarray(jax.device_get(x))`:
    what is left of the transfer once the result is ready, and the host
    copy; explicit, so that it passes `jax.transfer_guard("disallow")`).
    The transfer is requested before the wait (`copy_to_host_async`), so it
    starts when the device has the result, not a round trip later. Returns
    the host array.

    Always counted, as a `Phase` is: `sync.<kind>.wait.ns`,
    `sync.<kind>.copy.ns`, `sync.<kind>.n`, `sync.<kind>.bytes`, and inside
    a fit the sums `fit.sync.wait.ns`, `fit.sync.copy.ns`, `fit.sync.bytes`
    (with `fit.outer.ns`: a fit's wall = its own host time + wait + copy).
    While a profile is taken the steps are `fml.sync.<kind>.wait` and
    `fml.sync.<kind>.copy` on its host plane, inside whatever phase holds
    the call; under a sink they are span records (`category=readback`,
    and inside a fit `fit` = the ordinal of the outermost one), and on the
    timeline the `readback` lane's events. Every sync is also one
    `iteration.host_sync` / `iteration.host_sync.<kind>` and one
    `readback.count` / `readback.bytes` (of `arrays` arrays packed).

    `copy=False` is a wait alone (the online loop's fence): nothing is
    read, so it ticks no `iteration.host_sync*` and no `readback.*`, and
    returns None; `x` may then be any pytree of device arrays."""
    import jax
    import numpy as np

    with _SyncStep(kind, "wait") as wait:
        if copy:
            # asked for before the wait, as `jax.device_get` asks: the transfer
            # queues behind the program and not behind the host's notice of its end
            x.copy_to_host_async()
        # tpulint: disable=host-sync-leak -- THE accounted funnel: the wait of a blocking read, timed apart from its copy
        jax.block_until_ready(x)
    metrics.inc_counter("sync." + kind + ".n")
    if timeline.enabled():
        timeline.record_complete(timeline.LANE_READBACK, wait.name, wait.start_ns, wait.dur_ns)
    if not copy:
        return None
    with _SyncStep(kind, "copy") as copied:
        host = np.asarray(jax.device_get(x))
        if _enabled:
            copied.attrs["bytes"] = host.nbytes
            copied.attrs["arrays"] = arrays
    metrics.inc_counter("sync." + kind + ".bytes", host.nbytes)
    if _fits.depth:
        metrics.inc_counter("fit.sync.bytes", host.nbytes)
    # every sync blocks the host on the device: a loop that syncs
    # O(maxIter) times instead of O(maxIter/K) is a jump of these counters
    metrics.inc_counter("iteration.host_sync")
    metrics.inc_counter("iteration.host_sync." + kind)
    metrics.inc_counter("readback.count")
    metrics.inc_counter("readback.bytes", host.nbytes)
    if timeline.enabled():
        timeline.record_instant(timeline.host_lane(), "host_sync." + kind)
        timeline.record_complete(
            timeline.LANE_READBACK, copied.name, copied.start_ns, copied.dur_ns,
            bytes=host.nbytes, arrays=arrays,
        )
    return host


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


def set_dispatch_depth(depth: int) -> None:
    """Record the in-flight dispatch depth a pipelined loop ran at (gauge)."""
    metrics.set_gauge("iteration.dispatch_depth", depth)


_jax_hooks_installed = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def install_jax_hooks() -> bool:
    """Register a `jax.monitoring` listener translating backend-compile
    events into `jit.compiles`/`jit.compile.ns`/`jit.compile` metrics (a
    pause by compilation has a length as well as a count) and
    `category=compile` spans. Idempotent; deferred until jax is already
    imported so this module never pays the jax import itself."""
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
        metrics.inc_counter("jit.compile.ns", int(duration * 1e9))
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
# the cycle collector's pauses
# ---------------------------------------------------------------------------

_gc_start_ns = 0
_gc_annotation = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """`gc.callbacks` entry (jax installs one of its own the same way):
    every collection adds to `host.gc.ns` and `host.gc.n`, a full one
    (generation 2: tens of milliseconds over a large heap) also to
    `host.gc.full.n`, and is `fml.host.gc` on the clock of a profile being
    taken and an event of the timeline's host lane; younger generations get
    neither. It emits no span record: a collection can start inside
    `_emit`, under its lock."""
    global _gc_start_ns, _gc_annotation
    if phase == "start":
        if info["generation"] == 2 and _TraceAnnotation is not None and _TraceAnnotation.is_enabled():
            _gc_annotation = _TraceAnnotation(PHASE_PREFIX + "host.gc")
            _gc_annotation.__enter__()
        _gc_start_ns = time.perf_counter_ns()
        return
    start_ns, _gc_start_ns = _gc_start_ns, 0
    if not start_ns:  # installed while a collection ran
        return
    dur_ns = time.perf_counter_ns() - start_ns
    metrics.inc_counter("host.gc.ns", dur_ns)
    metrics.inc_counter("host.gc.n")
    if info["generation"] == 2:
        metrics.inc_counter("host.gc.full.n")
        if _gc_annotation is not None:
            _gc_annotation.__exit__(None, None, None)
            _gc_annotation = None
        if timeline.enabled():
            timeline.record_complete(timeline.host_lane(), "host.gc", start_ns, dur_ns)


gc.callbacks.append(_on_gc)


# ---------------------------------------------------------------------------
# automatic stage instrumentation (wired from api.Stage.__init_subclass__)
# ---------------------------------------------------------------------------

def _wrap_stage_method(fn, op: str):
    import functools

    from . import memledger

    @functools.wraps(fn)
    def traced(self, *args, **kwargs):
        if not _enabled:
            return fn(self, *args, **kwargs)
        with Span("stage." + op, {"stage": type(self).__name__}):
            return fn(self, *args, **kwargs)

    wrapper = traced
    if op == "fit":

        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            # the fit.total phase and the per-fit HBM watermark
            # (hbm.peak.fit) are always on, like the metrics registry: no
            # sink required. `fit.total` counts every estimator, a
            # pipeline's stages too; the outermost fit of the thread is
            # also `fit.outer`, once, from the same clock reads, and opens
            # the ordinal its syncs' spans carry
            fits = _fits
            outermost = fits.depth == 0
            if outermost:
                fits.ordinal = next(_fit_ordinals)
            fits.depth += 1
            total = Phase("fit.total")
            try:
                with total, memledger.fit_peak_scope():
                    return traced(self, *args, **kwargs)
            finally:
                fits.depth -= 1
                if outermost:
                    metrics.inc_counter("fit.outer.ns", total.dur_ns)
                    metrics.inc_counter("fit.outer.n")

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
