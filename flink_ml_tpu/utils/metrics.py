"""Process-wide metrics + profiling surface.

The reference delegates observability to the Flink web UI, slf4j, and
per-operator metric groups — its only custom metric is the online models'
`modelDataVersion` gauge (OnlineKMeansModel.java:161-166,
OnlineLogisticRegressionModel.java:133); the benchmark module adds
wall-clock/throughput accounting (BenchmarkUtils.java:131-144). The
TPU-native equivalents here:

- `timed(name)` — accumulate wall-clock spans per named phase (the
  benchmark runner times datagen/fit/transform/collect; the iteration
  runtime times epochs);
- `set_gauge`/`inc_counter` — the metric-group analogue (online models
  publish modelDataVersion here);
- `profile_trace(dir)` — a `jax.profiler` trace scope producing
  TensorBoard-loadable device profiles (SURVEY.md §5 called for this
  "from day one").

Everything is a plain module-level registry: `snapshot()` returns a copy,
`reset()` clears — cheap enough to stay always-on.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List

# [count, total seconds, last seconds] a name: a server records a time with
# every fit and every readback for as long as it lives
_timers: Dict[str, List[float]] = {}
_gauges: Dict[str, float] = {}
_counters: Dict[str, int] = {}


@contextmanager
def timed(name: str):
    """Accumulate the wall-clock duration of this block under `name`."""
    start = time.perf_counter()
    try:
        yield
    finally:
        record_time(name, time.perf_counter() - start)


def record_time(name: str, seconds: float) -> None:
    stats = _timers.get(name)
    if stats is None:
        _timers[name] = [1, seconds, seconds]
    else:
        stats[0] += 1
        stats[1] += seconds
        stats[2] = seconds


def set_gauge(name: str, value: float) -> None:
    _gauges[name] = value


def get_gauge(name: str, default=None):
    return _gauges.get(name, default)


def inc_counter(name: str, delta: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + delta


def get_counter(name: str, default: int = 0) -> int:
    return _counters.get(name, default)


def timer_totals() -> Dict[str, float]:
    """Total seconds per phase."""
    return {k: float(v[1]) for k, v in _timers.items()}


def snapshot() -> Dict[str, Dict]:
    """A copyable view of every metric: per-phase {count, totalMs, lastMs},
    gauges, counters."""
    return {
        "timers": {
            k: {"count": v[0], "totalMs": v[1] * 1000.0, "lastMs": v[2] * 1000.0}
            for k, v in _timers.items()
        },
        "gauges": dict(_gauges),
        "counters": dict(_counters),
    }


def snapshot_delta(before: Dict[str, Dict], after: Dict[str, Dict]) -> Dict[str, Dict]:
    """The registry activity between two `snapshot()` calls: timer and
    counter increments (entries that did not move are dropped), gauges as
    of `after`. The benchmark runner embeds this per entry so every BENCH
    json carries its own span/readback/compile evidence."""
    timers = {}
    for name, stats in after["timers"].items():
        prev = before["timers"].get(name, {"count": 0, "totalMs": 0.0})
        count = stats["count"] - prev["count"]
        if count:
            timers[name] = {
                "count": count,
                "totalMs": stats["totalMs"] - prev["totalMs"],
                "lastMs": stats["lastMs"],
            }
    counters = {}
    for name, value in after["counters"].items():
        delta = value - before["counters"].get(name, 0)
        if delta:
            counters[name] = delta
    return {"timers": timers, "gauges": dict(after["gauges"]), "counters": counters}


def reset() -> None:
    _timers.clear()
    _gauges.clear()
    _counters.clear()


@contextmanager
def profile_trace(log_dir: str):
    """Capture a jax.profiler device trace for this block (view with
    TensorBoard's profile plugin). No-op overhead when not used."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
