"""Hierarchical observability layer — span tracing + runtime accounting.

The reference delegates observability to the Flink web UI, slf4j and
per-operator metric groups; this package is the TPU-native equivalent the
flat registry in `utils/metrics.py` cannot provide: *where* a slow
`Pipeline.fit` spends its time, split into compute / collective / readback
/ compile, without re-running under the device profiler.

Three layers:

- `tracing` — a context-var-based `span(name, **attrs)` API producing
  nested spans with monotonic timestamps, emitted as structured JSONL
  (`FLINK_ML_TPU_TRACE_FILE`) or an in-memory ring buffer
  (`FLINK_ML_TPU_TRACE_RING`), and aggregated into `metrics.snapshot()`.
  The no-op path (no sink configured) is a shared singleton context
  manager — cheap enough to stay always-on. `tracing.phase` is the
  always-counted form for the few phases of a fit: counters without a
  sink, `fml.*` host events in any `jax.profiler` trace.
- `timeline` — the flight recorder: a bounded lock-cheap ring of
  begin/end events (`FLINK_ML_TPU_TIMELINE_RING` /
  `FLINK_ML_TPU_TIMELINE_FILE`) with thread + logical-stream lanes,
  exported as Chrome/Perfetto trace-event JSON
  (`scripts/obs_timeline.py`) and reduced to per-chunk dispatch-wall
  attribution (`wall = dispatch + device + readback + idle-gap`).
- `hist` — mergeable log2-bucketed streaming histograms
  (p50/p90/p99/p999, fixed memory) for SLO latency/size distributions.
- `exporters` — render `metrics.snapshot()` (and the histogram
  registry) as JSON or Prometheus text, with a name-collision check.
- `report` — reduce a JSONL trace to per-stage / per-epoch time-breakdown
  tables with category accounting, and a `jax.profiler` trace to the
  device's busy and idle time with the idle time by fit phase (see
  `scripts/obs_report.py`).

See docs/observability.md for the full surface and a worked example.
"""

from . import hist, timeline  # noqa: F401
from .tracing import (  # noqa: F401
    add_attr,
    configure,
    current_span,
    drain_ring,
    enabled,
    event,
    install_jax_hooks,
    phase,
    set_dispatch_depth,
    span,
    sync,
)
