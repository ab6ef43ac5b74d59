"""Trace reduction — JSONL spans → per-stage / per-epoch breakdown tables.

Consumes the JSONL a run writes under `FLINK_ML_TPU_TRACE_FILE` and
answers the question the flat registry cannot: where did the wall time of
each pipeline stage / training epoch go, split into

- `collective` — host-side collective funnels (+ trace-time collective op
  events, reported as count/bytes),
- `readback`   — device→host transfers (packed readbacks, phase barriers),
- `compile`    — XLA backend compiles (jax.monitoring),
- `cache`      — native datacache traffic,
- `compute`    — the residual: device execution + host compute dispatched
  under the span (synchronous host-driven steps make this the dominant
  real-work bucket).

Category times are summed over each container's *outermost* categorized
descendants, so nested categorized spans never double-count and the five
buckets sum to the container's wall time exactly.
"""

from __future__ import annotations

import json
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

from .tracing import PHASE_PREFIX

CATEGORIES = ("collective", "readback", "compile", "cache")
_STAGE_NAMES = ("pipeline.stage", "stage.fit", "stage.transform")


def load_trace(path: str) -> List[Dict]:
    """Parse a JSONL trace file; tolerates trailing partial lines."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return records


def sanitize_records(records: Iterable[Dict]) -> "Tuple[List[Dict], int]":
    """Normalize a possibly ring-truncated / mid-span-truncated record
    stream into well-formed span records: timeline-style begin/end events
    (`ph` B/E) are paired into spans, complete/instant timeline events
    become spans, and records missing the span schema are dropped.
    Returns (clean records, dropped count) — dropped counts unmatched
    begins/ends (their partner fell off the ring or the file was cut
    mid-span) plus unrecognizable records. Never raises."""
    clean: List[Dict] = []
    dropped = 0
    open_begins: Dict[object, Dict] = {}
    synth_id = -1  # synthesized span ids stay clear of real ones
    for r in records:
        if not isinstance(r, dict):
            dropped += 1
            continue
        ph = r.get("ph")
        if ph == "B":
            open_begins[(r.get("lane"), r.get("ref"), r.get("name"))] = r
            continue
        if ph == "E":
            begin = open_begins.pop((r.get("lane"), r.get("ref"), r.get("name")), None)
            if begin is None:
                dropped += 1  # begin fell off the ring
                continue
            clean.append(
                {
                    "name": r.get("name", "?"),
                    "spanId": r.get("ref") if r.get("ref") is not None else synth_id,
                    "parentId": 0,
                    "startUs": float(begin.get("tsUs", 0.0)),
                    "durUs": max(
                        0.0, float(r.get("tsUs", 0.0)) - float(begin.get("tsUs", 0.0))
                    ),
                    "attrs": r.get("args") or {},
                }
            )
            synth_id -= 1
            continue
        if ph in ("X", "i"):
            clean.append(
                {
                    "name": r.get("name", "?"),
                    "spanId": synth_id,
                    "parentId": 0,
                    "startUs": float(r.get("tsUs", 0.0)),
                    "durUs": float(r.get("durUs", 0.0)),
                    "attrs": r.get("args") or {},
                }
            )
            synth_id -= 1
            continue
        if "name" in r and "spanId" in r:
            r.setdefault("parentId", 0)
            r.setdefault("startUs", 0.0)
            r.setdefault("durUs", 0.0)
            r.setdefault("attrs", {})
            clean.append(r)
            continue
        dropped += 1
    dropped += len(open_begins)  # ends lost to truncation
    return clean, dropped


class Trace:
    """Indexed view of a span list: parent/child links + category sums."""

    def __init__(self, records: Iterable[Dict]):
        # defensively span-shaped only: callers SHOULD sanitize first
        # (sanitize_records), but a stray malformed record must degrade
        # to "skipped", not a KeyError ten frames down
        self.records = [
            r for r in records if isinstance(r, dict) and "spanId" in r
        ]
        self.by_id = {r["spanId"]: r for r in self.records}
        self.children: Dict[int, List[Dict]] = {}
        for r in self.records:
            self.children.setdefault(r.get("parentId", 0), []).append(r)

    def ancestors(self, record: Dict):
        parent = self.by_id.get(record.get("parentId", 0))
        while parent is not None:
            yield parent
            parent = self.by_id.get(parent.get("parentId", 0))

    def descendants(self, record: Dict):
        stack = list(self.children.get(record["spanId"], ()))
        while stack:
            r = stack.pop()
            yield r
            stack.extend(self.children.get(r["spanId"], ()))

    @staticmethod
    def category(record: Dict) -> Optional[str]:
        return (record.get("attrs") or {}).get("category")

    def _categorized_between(self, record: Dict, container: Dict) -> bool:
        """True when a categorized span sits strictly between `record` and
        `container` on the parent chain."""
        parent = self.by_id.get(record.get("parentId", 0))
        while parent is not None and parent["spanId"] != container["spanId"]:
            if self.category(parent) in CATEGORIES:
                return True
            parent = self.by_id.get(parent.get("parentId", 0))
        return False

    def breakdown(self, record: Dict) -> Dict[str, float]:
        """Wall-time split of one container span: categorized time from its
        outermost categorized descendants, `compute` as the residual."""
        wall = float(record.get("durUs", 0.0))
        out = {c: 0.0 for c in CATEGORIES}
        for d in self.descendants(record):
            cat = self.category(d)
            if cat not in out:
                continue
            # outermost-categorized only: a readback nested inside a cache
            # span (or any categorized ancestor below `record`) is already
            # paid by its enclosing categorized span
            if self._categorized_between(d, record):
                continue
            out[cat] += float(d.get("durUs", 0.0))
        out["compute"] = max(0.0, wall - sum(out.values()))
        out["wall"] = wall
        return out

    def collective_stats(self, record: Dict) -> Dict[str, Dict[str, float]]:
        """Trace-time collective op events under a container: count + bytes
        per op (zero-duration — dispatched into the XLA program)."""
        stats: Dict[str, Dict[str, float]] = {}
        for d in self.descendants(record):
            name = d.get("name", "")
            if not name.startswith("collective."):
                continue
            attrs = d.get("attrs") or {}
            agg = stats.setdefault(name[len("collective."):], {"count": 0, "bytes": 0})
            agg["count"] += 1
            agg["bytes"] += int(attrs.get("bytes", 0))
        return stats


def stage_records(trace: Trace) -> List[Dict]:
    """The stage-level containers: `pipeline.stage` spans when a Pipeline
    ran, else outermost `stage.fit`/`stage.transform` spans."""
    pipeline_stages = [r for r in trace.records if r.get("name") == "pipeline.stage"]
    if pipeline_stages:
        return sorted(pipeline_stages, key=lambda r: r.get("startUs", 0.0))
    out = []
    for r in trace.records:
        if r.get("name") not in ("stage.fit", "stage.transform"):
            continue
        if any(a.get("name") in _STAGE_NAMES for a in trace.ancestors(r)):
            continue
        out.append(r)
    return sorted(out, key=lambda r: r.get("startUs", 0.0))


def epoch_records(trace: Trace) -> List[Dict]:
    return sorted(
        (r for r in trace.records if r.get("name") == "iteration.epoch"),
        key=lambda r: r.get("startUs", 0.0),
    )


def run_summaries(trace: Trace) -> List[Dict]:
    """`iteration.run` records — the per-run summary the on-device
    while_loop path emits instead of per-epoch spans."""
    return sorted(
        (r for r in trace.records if r.get("name") == "iteration.run"),
        key=lambda r: r.get("startUs", 0.0),
    )


def compile_cost(trace: Trace) -> List[Dict]:
    """Per-kernel compile cost and the AOT-program-bank hit/load split
    (docs/performance.md §12).

    `bank.compile` spans carry kernel attribution (the bank's AOT
    trace+lower+compile, backfilling a miss); `jit.compile` spans with no
    `bank.compile` ancestor are backend compiles the bank never saw
    (raw-jit paths, op-by-op host compiles) and aggregate into one
    unattributed row. `bank.hit` / `bank.load` events count warm
    executions and warm-loaded entries per kernel."""
    rows: Dict[str, Dict] = {}

    def row(kernel: str) -> Dict:
        return rows.setdefault(
            kernel,
            {"kernel": kernel, "compiles": 0, "compileMs": 0.0,
             "bankHits": 0, "bankLoads": 0},
        )

    for r in trace.records:
        name = r.get("name")
        kernel = (r.get("attrs") or {}).get("kernel") or "?"
        if name == "bank.compile":
            entry = row(kernel)
            entry["compiles"] += 1
            entry["compileMs"] += float(r.get("durUs", 0.0)) / 1000.0
        elif name == "bank.hit":
            row(kernel)["bankHits"] += 1
        elif name == "bank.load":
            row(kernel)["bankLoads"] += 1
        elif name == "jit.compile" and not any(
            a.get("name") == "bank.compile" for a in trace.ancestors(r)
        ):
            entry = row("(unattributed XLA compile)")
            entry["compiles"] += 1
            entry["compileMs"] += float(r.get("durUs", 0.0)) / 1000.0
    return sorted(
        rows.values(), key=lambda e: (-e["compileMs"], e["kernel"])
    )


def _stage_label(record: Dict) -> str:
    attrs = record.get("attrs") or {}
    stage = attrs.get("stage", "?")
    if record.get("name") == "pipeline.stage":
        op = attrs.get("op", "")
        idx = attrs.get("index")
        prefix = f"[{idx}] " if idx is not None else ""
        return f"{prefix}{stage}.{op}" if op else f"{prefix}{stage}"
    op = record["name"].rsplit(".", 1)[-1]
    return f"{stage}.{op}"


def _table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    def fmt(cells):
        return "  ".join(c.ljust(w) if i == 0 else c.rjust(w)
                         for i, (c, w) in enumerate(zip(cells, widths)))
    lines = [fmt(headers), fmt(["-" * w for w in widths])]
    lines.extend(fmt(r) for r in rows)
    return "\n".join(lines)


def _breakdown_row(label: str, b: Dict[str, float]) -> List[str]:
    wall = b["wall"]
    cells = [label, f"{wall / 1000.0:.1f}"]
    for cat in ("compute",) + CATEGORIES:
        pct = 100.0 * b.get(cat, 0.0) / wall if wall > 0 else 0.0
        cells.append(f"{b.get(cat, 0.0) / 1000.0:.1f} ({pct:.0f}%)")
    return cells


def render_report(records: List[Dict], max_epochs: int = 20) -> str:
    """The human-readable report: stage table, epoch table, run summaries,
    collective traffic, and the dominant time category."""
    trace = Trace(records)
    sections = []
    headers = ["", "wallMs", "compute", "collective", "readback", "compile", "cache"]

    stages = stage_records(trace)
    totals = {c: 0.0 for c in ("wall", "compute") + CATEGORIES}
    if stages:
        rows = []
        for r in stages:
            b = trace.breakdown(r)
            rows.append(_breakdown_row(_stage_label(r), b))
            for k in totals:
                totals[k] += b.get(k, 0.0)
        rows.append(_breakdown_row("TOTAL", totals))
        sections.append("== Per-stage breakdown ==\n" + _table(headers, rows))
    else:
        sections.append("== Per-stage breakdown ==\n(no stage spans in trace)")

    epochs = epoch_records(trace)
    if epochs:
        rows = []
        shown = epochs if len(epochs) <= max_epochs else epochs[:max_epochs]
        etotals = {c: 0.0 for c in ("wall", "compute") + CATEGORIES}
        for r in epochs:
            b = trace.breakdown(r)
            for k in etotals:
                etotals[k] += b.get(k, 0.0)
        for r in shown:
            b = trace.breakdown(r)
            label = f"epoch {(r.get('attrs') or {}).get('epoch', '?')}"
            rows.append(_breakdown_row(label, b))
        if len(epochs) > len(shown):
            rows.append([f"... {len(epochs) - len(shown)} more", "", "", "", "", "", ""])
        rows.append(_breakdown_row(f"TOTAL ({len(epochs)} epochs)", etotals))
        sections.append("== Per-epoch breakdown ==\n" + _table(headers, rows))

    runs = run_summaries(trace)
    if runs:
        lines = []
        for r in runs:
            attrs = r.get("attrs") or {}
            n = attrs.get("epochs")
            wall_ms = float(r.get("durUs", 0.0)) / 1000.0
            per = f", {wall_ms / n:.2f} ms/epoch" if n else ""
            lines.append(
                f"- mode={attrs.get('mode', '?')} epochs={n} "
                f"wallMs={wall_ms:.1f}{per}"
                + (f" finalCriteria={attrs['finalCriteria']:.4g}"
                   if "finalCriteria" in attrs else "")
            )
        sections.append(
            "== Iteration runs (on-device loops report one summary span) ==\n"
            + "\n".join(lines)
        )

    cost = compile_cost(trace)
    if cost:
        # full kernel ids live in the JSON payload (scripts/obs_report.py
        # --format json); the text table elides the middle to stay scannable
        def _elide(kernel: str, width: int = 72) -> str:
            if len(kernel) <= width:
                return kernel
            half = (width - 3) // 2
            return kernel[:half] + "..." + kernel[-half:]

        rows = [
            [
                _elide(e["kernel"]),
                str(e["compiles"]),
                f"{e['compileMs']:.1f}",
                str(e["bankHits"]),
                str(e["bankLoads"]),
            ]
            for e in cost
        ]
        rows.append([
            "TOTAL",
            str(sum(e["compiles"] for e in cost)),
            f"{sum(e['compileMs'] for e in cost):.1f}",
            str(sum(e["bankHits"] for e in cost)),
            str(sum(e["bankLoads"] for e in cost)),
        ])
        sections.append(
            "== Compile cost (AOT program bank, docs/performance.md §12) ==\n"
            + _table(
                ["kernel", "compiles", "compileMs", "bankHits", "bankLoads"],
                rows,
            )
        )

    # collective traffic across the whole trace
    root = {"spanId": 0, "durUs": 0.0}
    trace.children.setdefault(0, [])
    coll = trace.collective_stats(root)
    if coll:
        rows = [
            [op, str(int(s["count"])), f"{int(s['bytes'])}"]
            for op, s in sorted(coll.items())
        ]
        sections.append(
            "== Collective ops (recorded at trace time; bytes = payload per call) ==\n"
            + _table(["op", "calls", "bytes"], rows)
        )

    if totals["wall"] > 0:
        cats = OrderedDict((c, totals.get(c, 0.0)) for c in ("compute",) + CATEGORIES)
        dominant = max(cats, key=cats.get)
        pct = 100.0 * cats[dominant] / totals["wall"]
        sections.append(
            f"Dominant category: {dominant} "
            f"({cats[dominant] / 1000.0:.1f} ms, {pct:.0f}% of stage wall time)"
        )

    return "\n\n".join(sections)


# ---------------------------------------------------------------------------
# device profile: busy, idle, and what the host was doing in each idle gap
# ---------------------------------------------------------------------------

_DEVICE_PLANE = "/device:"
_HOST_PLANE = "/host:CPU"
_DEVICE_LINES = ("XLA Modules", "XLA Ops")


def load_device_profile(path: str) -> Dict:
    """A jax.profiler trace in plain form:
    {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]}
    holding each device plane's module and op lines and the host's `fml.*`
    phase annotations. `path` is a profiler log dir (its newest
    `.xplane.pb`), one such file, or a `.json` file already in this form."""
    import glob
    import os

    if os.path.isdir(path):
        found = sorted(
            glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
        )
        if not found:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        entry = {"name": plane.name}
        if plane.name.startswith(_DEVICE_PLANE):
            entry["lines"] = [
                {
                    "name": line.name,
                    "events": [[e.name, e.start_ns, e.duration_ns] for e in line.events],
                }
                for line in plane.lines
                if line.name in _DEVICE_LINES
            ]
        elif plane.name == _HOST_PLANE:
            events = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines
                for e in line.events
                if e.name.startswith(PHASE_PREFIX)
            ]
            entry["lines"] = [{"name": "phases", "events": events}]
        else:
            continue
        planes.append(entry)
    return {"planes": planes}


def _union(intervals) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _innermost(phases) -> List[Tuple[float, float, str]]:
    """Nested (start, end, name) phases cut into sorted, disjoint pieces,
    each named by the innermost phase over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name), outermost first
    cursor = 0.0

    def emit(upto: float) -> float:
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][1]))
        return max(cursor, upto)

    for start, end, name in sorted(phases, key=lambda p: (p[0], -p[1])):
        while stack and stack[-1][0] <= start:
            cursor = emit(stack[-1][0])
            stack.pop()
        cursor = emit(start) if stack else start
        stack.append((end, name))
    while stack:
        cursor = emit(stack[-1][0])
        stack.pop()
    return out


def _idle_by_phase(gaps, pieces) -> Dict[str, float]:
    """Seconds of the sorted, disjoint idle `gaps` under each of the sorted,
    disjoint `(start, end, phase)` pieces; `outside` where none lies."""
    idle: Dict[str, float] = {}
    j = 0
    for start, end in gaps:
        while j < len(pieces) and pieces[j][1] <= start:
            j += 1
        named, k = 0.0, j
        while k < len(pieces) and pieces[k][0] < end:
            a, b, phase = pieces[k]
            part = min(b, end) - max(a, start)
            idle[phase] = idle.get(phase, 0.0) + part / 1e9
            named += part
            k += 1
        if end - start > named:
            idle["outside"] = idle.get("outside", 0.0) + (end - start - named) / 1e9
    return idle


def reduce_device_profile(profile: Dict) -> Optional[Dict]:
    """Busy and idle seconds of the busiest device over the profile's
    window (first `fml.*` phase's start to the last one's end; with no
    phase, first to last device event), device seconds by program, and the
    idle seconds by the innermost phase over each part of each gap
    (`outside` where the host was in none). None without a device plane."""
    phases = [
        (start, start + dur, name[len(PHASE_PREFIX):])
        for plane in profile["planes"] if plane["name"] == _HOST_PLANE
        for line in plane["lines"]
        for name, start, dur in line["events"]
        if name.startswith(PHASE_PREFIX)
    ]
    devices = []  # (plane, its events by line)
    for plane in profile["planes"]:
        if plane["name"].startswith(_DEVICE_PLANE):
            lines = {line["name"]: line["events"] for line in plane["lines"]}
            if any(lines.get(name) for name in _DEVICE_LINES):
                devices.append((plane, lines))
    if not devices:
        return None
    events_of = lambda lines: [e for name in _DEVICE_LINES for e in lines.get(name, [])]
    if phases:
        lo, hi = min(p[0] for p in phases), max(p[1] for p in phases)
    else:
        lo = min(e[1] for _, lines in devices for e in events_of(lines))
        hi = max(e[1] + e[2] for _, lines in devices for e in events_of(lines))
    inside = lambda events: [e for e in events if e[1] + e[2] > lo and e[1] < hi]
    busy, busy_ns = [], -1.0
    for candidate, candidate_lines in devices:  # the busiest device decides
        intervals = [
            (max(s, lo), min(e, hi))
            for s, e in _union((s, s + d) for _, s, d in inside(events_of(candidate_lines)))
        ]
        total = sum(e - s for s, e in intervals)
        if total > busy_ns:
            busy, busy_ns, plane, lines = intervals, total, candidate, candidate_lines
    modules = inside(lines.get(_DEVICE_LINES[0], []))
    gaps, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = end
    if cursor < hi:
        gaps.append((cursor, hi))
    programs: Dict[str, float] = {}
    for module, _, dur in modules:
        module = module.split("(", 1)[0]  # jit_f(1234) -> jit_f
        programs[module] = programs.get(module, 0.0) + dur / 1e9
    busy_s = busy_ns / 1e9
    return {
        "device": plane["name"],
        "devices": len(devices),
        "windowS": (hi - lo) / 1e9,
        "busyS": busy_s,
        "idleS": (hi - lo) / 1e9 - busy_s,
        "programsS": programs,
        "idleByPhaseS": _idle_by_phase(gaps, _innermost(phases)),
    }


def render_device_profile(path: str) -> str:
    """The operator's view of a jax.profiler trace of one or more fits: for
    the busiest device, busy and idle time, device seconds by program, and
    the idle time by what the program says the host was doing in it."""
    head = f"== Device profile ({path}) =="
    try:
        stats = reduce_device_profile(load_device_profile(path))
    except FileNotFoundError as e:
        return f"{head}\n({e})"
    if stats is None:
        return f"{head}\n(no device plane in the profile)"
    window = stats["windowS"]

    def rows(seconds: Dict[str, float], of: float) -> List[List[str]]:
        """Largest first (the dict's own order where two are equal)."""
        return [
            [k, f"{v:.6f}", f"{100.0 * v / of:.1f}%" if of > 0 else "-"]
            for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])
        ]

    busy, idle = stats["busyS"], stats["idleS"]
    sections = [
        head,
        f"{stats['device']} (busiest of {stats['devices']}), window {window:.6f} s",
        f"busy {busy:.6f} s ({100.0 * busy / window:.1f}%), "
        f"idle {idle:.6f} s ({100.0 * idle / window:.1f}%)",
        "Device seconds by program:\n"
        + _table(["program", "seconds", "of window"], rows(stats["programsS"], window)),
    ]
    sections.append(
        "Idle seconds by phase (innermost fml.* phase over each part of a gap):\n"
        + _table(
            ["phase", "seconds", "of idle"],
            rows(stats["idleByPhaseS"], stats["idleS"]),
        )
    )
    return "\n\n".join(sections)
