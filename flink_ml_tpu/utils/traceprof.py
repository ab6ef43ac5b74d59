"""Profiler-trace capture + analysis for benchmark evidence.

Runs a callable under ``jax.profiler.trace`` and reduces the emitted
chrome-format trace (``*.trace.json.gz``) to the numbers perf work needs:
device-busy time, HBM bytes actually accessed, model FLOPs executed, and a
per-HLO-category breakdown. This replaces the flop-model MFU in bench.py
with measurements from the device timeline — the reference's benchmark
harness times whole jobs (BenchmarkUtils.java:131-144) and cannot see
inside them; here the trace separates device compute from the host
dispatch+readback wall that dominates small jobs.

No tensorboard/tensorflow dependency: the trace.json.gz the profiler
writes alongside the xplane.pb is parsed directly with gzip+json.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, Optional


def capture_trace(fn: Callable[[], Any], trace_dir: Optional[str] = None) -> Dict:
    """Run ``fn`` under the JAX profiler; return ``analyze_trace`` of the
    newest trace plus the traced call's host wall time."""
    import jax

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="flink_ml_tpu_trace_")
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        fn()
    wall_s = time.perf_counter() - t0
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    )
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    stats = analyze_trace(paths[-1])
    stats["wallMs"] = wall_s * 1000.0
    stats["tracePath"] = paths[-1]
    return stats


def analyze_trace(path: str) -> Dict:
    """Reduce a chrome-format JAX profiler trace to device-side totals.

    Device busy time is the sum of "XLA Modules" spans (module executions
    never overlap on a core); bytes/FLOPs come from per-op stats on the
    "XLA Ops" thread (``bytes_accessed`` / ``model_flops``, the stats the
    profiler derives from the HLO cost model against the *executed*
    program)."""
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])

    device_pids = set()
    thread_names: Dict[tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name" and str(
            e.get("args", {}).get("name", "")
        ).startswith("/device:"):
            device_pids.add(e["pid"])
        if e.get("name") == "thread_name":
            thread_names[(e["pid"], e.get("tid"))] = e.get("args", {}).get("name", "")

    busy_us = 0.0
    modules = []
    op_bytes = 0
    op_flops = 0
    ops_us = 0.0
    by_category: Dict[str, Dict[str, float]] = {}
    top_ops: Dict[str, Dict[str, float]] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        tname = thread_names.get((e["pid"], e.get("tid")), "")
        dur = float(e.get("dur", 0.0))
        if tname == "XLA Modules":
            busy_us += dur
            modules.append({"name": e.get("name", ""), "durUs": dur})
        elif tname == "XLA Ops":
            args = e.get("args", {}) or {}
            b = int(args.get("bytes_accessed", 0))
            fl = int(args.get("model_flops", 0))
            op_bytes += b
            op_flops += fl
            ops_us += dur
            cat = args.get("hlo_category", "unknown")
            agg = by_category.setdefault(
                cat, {"durUs": 0.0, "bytes": 0, "flops": 0, "count": 0}
            )
            agg["durUs"] += dur
            agg["bytes"] += b
            agg["flops"] += fl
            agg["count"] += 1
            op = top_ops.setdefault(
                e.get("name", ""), {"durUs": 0.0, "bytes": 0, "count": 0}
            )
            op["durUs"] += dur
            op["bytes"] += b
            op["count"] += 1

    busy_s = busy_us / 1e6
    return {
        "deviceBusyMs": busy_us / 1000.0,
        "deviceOpsMs": ops_us / 1000.0,
        "numModuleExecutions": len(modules),
        "hbmBytesAccessed": op_bytes,
        "modelFlops": op_flops,
        "hbmGBps": (op_bytes / busy_s / 1e9) if busy_s > 0 else None,
        "flopsPerSec": (op_flops / busy_s) if busy_s > 0 else None,
        "byCategory": {
            k: v
            for k, v in sorted(
                by_category.items(), key=lambda kv: -kv[1]["durUs"]
            )
        },
        "topOps": {
            k: v
            for k, v in sorted(top_ops.items(), key=lambda kv: -kv[1]["durUs"])[:12]
        },
    }
