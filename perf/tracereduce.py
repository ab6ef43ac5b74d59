"""From a profiler trace to the numbers the per-layer metrics read.

A corrected copy of `flink_ml_tpu/utils/traceprof.analyze_trace`: device busy
time is the union of the intervals in which something runs on a device, taken
per device (the original summed module spans over all devices); operations are
summed by name with their self time, so that a `while` does not count its body
twice; idle gaps are named by the benchmark span that covered them. Nothing
here reads the profiler's `bytes_accessed` or `model_flops`.

Works on a plain form of the trace, which the tests keep a recorded one in:
{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]}
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter", "collective-permute")

Interval = Tuple[float, float]


def load_xplane(path: str, span_prefix: str = "perf.") -> dict:
    """The plain form of an .xplane.pb: the device planes' module and op
    lines, and the host's events whose names start with `span_prefix`."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = [
                {
                    "name": line.name,
                    "events": [[e.name, e.start_ns, e.duration_ns] for e in line.events],
                }
                for line in plane.lines
                if line.name in (MODULE_LINE, OP_LINE)
            ]
            planes.append({"name": plane.name, "lines": lines})
        elif plane.name == HOST_PLANE:
            events = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines
                for e in line.events
                if e.name.startswith(span_prefix)
            ]
            planes.append({"name": plane.name, "lines": [{"name": "spans", "events": events}]})
    return {"planes": planes}


def load_newest(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return load_xplane(paths[-1])


def op_name(event_name: str) -> str:
    """`%fusion.4 = f32[...] fusion(...)` -> `fusion.4`; a module's
    `jit_f(1234)` -> `jit_f`."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name:
        name = name[: name.rindex("(")]
    return name


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events: List[list]) -> Dict[str, float]:
    """Summed self time (ns) by op name on one line: an event's duration
    minus that of the events nested directly inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + max(entry[2], 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([op_name(name), start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def reduce(trace: dict, span: str, need_device: bool = True):
    """The traced window, from the first `span` event's start to the last
    one's end, reduced per device. Times in seconds. A trace without a
    device plane is an error, or None for a rehearsal off the chip."""
    spans = sorted(
        (start, start + dur)
        for plane in trace["planes"] if plane["name"] == HOST_PLANE
        for line in plane["lines"]
        for name, start, dur in line["events"] if name == span
    )
    if not spans:
        raise RuntimeError(f"the trace holds no {span!r} span")
    lo, hi = spans[0][0], spans[-1][1]
    devices = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        modules = [e for e in lines.get(MODULE_LINE, []) if e[1] + e[2] > lo and e[1] < hi]
        ops = [e for e in lines.get(OP_LINE, []) if e[1] + e[2] > lo and e[1] < hi]
        busy = clip(union([(s, s + d) for _, s, d in modules + ops]), lo, hi)
        by_module: Dict[str, float] = {}
        for name, _, dur in modules:
            by_module[op_name(name)] = by_module.get(op_name(name), 0.0) + dur / 1e9
        devices.append(
            {
                "name": plane["name"],
                "busy": busy,
                "busy_s": total(busy) / 1e9,
                "modules_s": by_module,
                "ops_s": {k: v / 1e9 for k, v in self_times(ops).items()},
            }
        )
    if not devices:
        if need_device:
            raise RuntimeError("the trace holds no device plane")
        return None
    window_s = (hi - lo) / 1e9
    fullest = max(devices, key=lambda d: d["busy_s"])
    n = len(devices)
    ops_mean: Dict[str, float] = {}
    modules_mean: Dict[str, float] = {}
    for device in devices:
        for name, seconds in device["ops_s"].items():
            ops_mean[name] = ops_mean.get(name, 0.0) + seconds / n
        for name, seconds in device["modules_s"].items():
            modules_mean[name] = modules_mean.get(name, 0.0) + seconds / n
    collective_s = sum(
        seconds for name, seconds in ops_mean.items() if name.startswith(COLLECTIVES)
    )
    gaps = idle_gaps(fullest["busy"], spans, lo, hi, span)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_s,
        "spans": len(spans),
        "span_s": sum(e - s for s, e in spans) / 1e9,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "busy_s_fullest": fullest["busy_s"],
        "busy_in_spans_s": total(
            [iv for s, e in spans for iv in clip(fullest["busy"], s, e)]
        ) / 1e9,
        "modules_s": modules_mean,
        "ops_s": ops_mean,
        "collective_s": collective_s,
        "gaps_s": gaps,
        "breakdown": {"device_ops": top(ops_mean), "idle_gaps": top(gaps)},
    }


def idle_gaps(busy: List[Interval], spans: List[Interval], lo: float, hi: float, span: str) -> Dict[str, float]:
    """Idle seconds of one device in [lo, hi] by what the host was doing:
    inside a span before the device's first work there (`<span>:dispatch`,
    the host stages and launches), after its last (`<span>:readback`, the
    result comes to the host), between two programs of one span
    (`<span>:between_programs`), or outside every span (`between:<span>`)."""
    out: Dict[str, float] = {}

    def add(name: str, seconds: float):
        if seconds > 0:
            out[name] = out.get(name, 0.0) + seconds / 1e9

    cursor = lo
    for start, end in spans:
        add(f"between:{span}", total(idle_in(busy, cursor, start)))
        inside = clip(busy, start, end)
        if not inside:
            add(f"{span}:no_device_work", end - start)
        else:
            add(f"{span}:dispatch", inside[0][0] - start)
            add(f"{span}:readback", end - inside[-1][1])
            for (_, a), (b, _) in zip(inside, inside[1:]):
                add(f"{span}:between_programs", b - a)
        cursor = max(cursor, end)
    add(f"between:{span}", total(idle_in(busy, cursor, hi)))
    return out


def idle_in(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no busy interval covers."""
    if hi <= lo:
        return []
    out, cursor = [], lo
    for start, end in clip(busy, lo, hi):
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < hi:
        out.append((cursor, hi))
    return out
