"""Flight recorder (obs/timeline.py) — ring semantics, Chrome export,
dispatch-wall attribution, and the acceptance workload: a traced chunked
LR fit exports a valid >=4-lane Perfetto timeline and the benchmark
runner's `dispatchGapMs` agrees with `wallMs - hostDispatchMs`."""

import json
import threading
import time

import numpy as np
import pytest

from flink_ml_tpu import config
from flink_ml_tpu.obs import timeline, tracing
from flink_ml_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _clean():
    timeline.configure()
    tracing.configure()
    metrics.reset()
    yield
    timeline.configure()
    tracing.configure()
    metrics.reset()
    config.iteration_chunk_size = None


# ---------------------------------------------------------------------------
# ring core
# ---------------------------------------------------------------------------

def test_ring_orders_and_bounds():
    ring = timeline.TimelineRing(16)
    for i in range(40):
        ring.append(("i", "flow", f"e{i}", i, 0, None, None))
    events, truncated = ring.events()
    assert len(events) == 16
    assert truncated == 40 - 16
    # the ring keeps the NEWEST events, in order
    assert [e[2] for e in events] == [f"e{i}" for i in range(24, 40)]


def test_ring_concurrent_writers_lose_nothing():
    """8 threads x 500 events into a large ring: every event lands
    exactly once (the lock-free slot-claim contract)."""
    timeline.configure(ring_size=8192)
    n_threads, per_thread = 8, 500

    def writer(tid):
        for i in range(per_thread):
            timeline.record_instant("flow", f"w{tid}", i=i)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events, truncated = timeline.snapshot_events()
    assert truncated == 0
    # the writers' lane alone: the HBM ledger samples the `memory` lane whenever
    # an earlier test file's device arrays are collected, which may be now
    events = [e for e in events if e["lane"] == "flow"]
    assert len(events) == n_threads * per_thread
    by_writer = {}
    for e in events:
        by_writer.setdefault(e["name"], []).append(e["args"]["i"])
    assert all(sorted(v) == list(range(per_thread)) for v in by_writer.values())


def test_drain_resets():
    timeline.configure(ring_size=64)
    timeline.record_instant("flow", "a")
    assert len(timeline.drain()) == 1
    assert timeline.drain() == []
    assert timeline.enabled()  # drain keeps recording


def test_spans_flow_to_timeline_without_trace_sink():
    """Configuring ONLY the timeline still activates span tracing, and
    spans land as begin/end pairs on the thread's host lane."""
    timeline.configure(ring_size=256)
    assert tracing.enabled()
    with tracing.span("outer", kind="fit"):
        with tracing.span("inner"):
            pass
    events, _ = timeline.snapshot_events()
    phases = [(e["ph"], e["name"]) for e in events]
    assert ("B", "outer") in phases and ("E", "outer") in phases
    assert ("B", "inner") in phases and ("E", "inner") in phases
    ends = {e["name"]: e for e in events if e["ph"] == "E"}
    assert ends["outer"]["args"] == {"kind": "fit"}
    assert all(e["lane"].startswith("host:") for e in events)


def test_noop_cost_under_1us():
    """Disabled flight recorder: one module-global load per call (the
    pinned always-on budget, alongside the span no-op test)."""
    assert not timeline.enabled()
    n = 100_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            timeline.record_instant("flow", "noop")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"no-op timeline record costs {best * 1e9:.0f}ns/call"


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_schema_and_lanes():
    timeline.configure(ring_size=256)
    timeline.record_begin("host:MainThread", "fit", ref=1)
    timeline.record_complete(timeline.LANE_DISPATCH, "dispatch.chunk", 0, 10_000, start=0, end=4)
    timeline.record_complete(timeline.LANE_READBACK, "readback", 10_000, 2_000, bytes=8)
    timeline.record_instant(timeline.LANE_FLOW, "q.put", depth=1)
    timeline.record_end("host:MainThread", "fit", ref=1)
    doc = timeline.to_chrome()
    json.dumps(doc)  # serializable = loadable
    assert doc["otherData"]["unmatchedDropped"] == 0
    lanes = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("name") == "thread_name"
    }
    assert lanes == {"host:MainThread", "dispatch", "readback", "flow"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"fit", "dispatch.chunk", "readback"}
    for e in xs:
        assert set(e) >= {"ph", "pid", "tid", "name", "ts", "dur"}
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert instants and instants[0]["s"] == "t"


def test_chrome_export_drops_unmatched_pairs():
    """Ring truncation breaks B/E pairs; the export drops them with a
    count instead of crashing or emitting a broken trace."""
    timeline.configure(ring_size=256)
    timeline.record_end("host:t", "lostBegin", ref=7)  # B fell off the ring
    timeline.record_begin("host:t", "neverEnded", ref=8)
    timeline.record_begin("host:t", "ok", ref=9)
    timeline.record_end("host:t", "ok", ref=9)
    doc = timeline.to_chrome()
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == ["ok"]
    assert doc["otherData"]["unmatchedDropped"] == 2


def test_dump_and_load_roundtrip(tmp_path):
    timeline.configure(ring_size=64)
    timeline.record_complete(timeline.LANE_DISPATCH, "dispatch.chunk", 0, 1000, start=0, end=1)
    timeline.record_instant(timeline.LANE_FLOW, "q.put", depth=2)
    path = str(tmp_path / "events.jsonl")
    assert timeline.dump_jsonl(path) == 2
    loaded = timeline.load_events(path)
    assert [e["name"] for e in loaded] == ["dispatch.chunk", "q.put"]
    # a truncated final line (killed process) is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"ph": "i", "lane": "flow", "na')
    assert len(timeline.load_events(path)) == 2


# ---------------------------------------------------------------------------
# dispatch-wall attribution
# ---------------------------------------------------------------------------

def test_attribution_identity_synthetic():
    """wall = dispatch + device + readback + idle-gap, exactly, with
    overlapping intervals counted once (priority dispatch > readback >
    device)."""
    ms = 1_000_000  # ns per ms
    events = [
        # chunk 0: dispatch [0,2ms), device [2,6ms), readback [6,7ms);
        # next dispatch at 10ms -> idle [7,10) = 3ms
        {"ph": "X", "lane": "dispatch", "name": "dispatch.chunk", "tsUs": 0.0,
         "durUs": 2000.0, "args": {"start": 0, "end": 4}},
        {"ph": "X", "lane": "device", "name": "device.chunk(est)", "tsUs": 2000.0,
         "durUs": 4000.0},
        {"ph": "X", "lane": "readback", "name": "readback", "tsUs": 6000.0,
         "durUs": 1000.0},
        # chunk 1: dispatch [10,11ms), device overlapping dispatch
        # [10,13ms) -> device contributes only [11,13) = 2ms
        {"ph": "X", "lane": "dispatch", "name": "dispatch.chunk", "tsUs": 10000.0,
         "durUs": 1000.0, "args": {"start": 4, "end": 8}},
        {"ph": "X", "lane": "device", "name": "device.chunk(est)", "tsUs": 10000.0,
         "durUs": 3000.0},
    ]
    attr = timeline.dispatch_attribution(events)
    assert attr["gapCount"] == 2
    assert attr["epochs"] == 8
    assert attr["windowMs"] == pytest.approx(13.0)
    assert attr["dispatchMs"] == pytest.approx(3.0)
    assert attr["deviceMs"] == pytest.approx(6.0)
    assert attr["readbackMs"] == pytest.approx(1.0)
    assert attr["idleGapMs"] == pytest.approx(3.0)
    total = sum(attr[k] for k in ("dispatchMs", "deviceMs", "readbackMs", "idleGapMs"))
    assert total == pytest.approx(attr["wallMs"])
    assert attr["perEpoch"]["wallMs"] == pytest.approx(attr["wallMs"] / 8)


def test_attribution_empty_without_dispatch_lane():
    assert timeline.dispatch_attribution([]) == {}
    assert timeline.dispatch_attribution(
        [{"ph": "i", "lane": "flow", "name": "x", "tsUs": 0.0, "durUs": 0.0}]
    ) == {}


# ---------------------------------------------------------------------------
# the acceptance workload: traced chunked LR fit
# ---------------------------------------------------------------------------

def _chunked_lr_fit(tmp_path, max_iter=56, chunk=8):
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD

    config.iteration_chunk_size = chunk
    rng = np.random.RandomState(3)
    X = rng.randn(400, 8).astype(np.float32)
    y = (X @ np.linspace(1, -1, 8) > 0).astype(np.float32)
    sgd = SGD(
        max_iter=max_iter,
        global_batch_size=100,
        tol=0.0,
        checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_interval=chunk,
    )
    return sgd.optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)


def test_traced_chunked_fit_exports_four_lanes(tmp_path):
    """ISSUE 12 acceptance: a traced chunked LR fit (maxIter >= 50)
    exports valid Chrome trace JSON with at least the host-dispatch,
    device, readback and flow lanes, and the attribution identity holds
    over the fit's dispatch window."""
    timeline.configure(ring_size=16384)
    _, _, epochs = _chunked_lr_fit(tmp_path)
    assert epochs == 56
    doc = timeline.to_chrome()
    json.dumps(doc)
    lanes = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("name") == "thread_name"
    }
    assert {"dispatch", "device", "readback", "flow"} <= lanes
    assert any(lane.startswith("host:") for lane in lanes)
    assert doc["otherData"]["unmatchedDropped"] == 0

    attr = timeline.dispatch_attribution()
    assert attr["gapCount"] == 56 // 8
    assert attr["epochs"] == 56
    parts = sum(attr[k] for k in ("dispatchMs", "deviceMs", "readbackMs", "idleGapMs"))
    assert parts == pytest.approx(attr["wallMs"], rel=1e-6)
    assert attr["dispatchMs"] > 0 and attr["readbackMs"] > 0

    # the dump -> CLI -> Perfetto path works on the same recording
    events_path = str(tmp_path / "events.jsonl")
    timeline.dump_jsonl(events_path)
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "scripts/obs_timeline.py", events_path,
         "-o", str(tmp_path / "t.json"), "--attribution"],
        capture_output=True, text=True, cwd=str(_repo_root()),
    )
    assert out.returncode == 0, out.stderr
    assert "lanes" in out.stdout and "idleGapMs" in out.stdout
    exported = json.load(open(tmp_path / "t.json"))
    assert exported["traceEvents"]


def _repo_root():
    import os

    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_runner_dispatch_gap_consistent_with_wall(mesh8):
    """ISSUE 12 acceptance: the benchmark runner emits dispatchGapMs
    consistent with wallMs - hostDispatchMs within 5% (wall = the work
    phases), plus gapCount/hostDispatchMs as first-class fields, and the
    timeline attribution embeds when the flight recorder is on."""
    from flink_ml_tpu.benchmark.runner import run_benchmark

    timeline.configure(ring_size=32768)
    entry = {
        "stage": {
            "className": "org.apache.flink.ml.classification.logisticregression.LogisticRegression",
            "paramMap": {"maxIter": 50, "globalBatchSize": 512},
        },
        "inputData": {
            "className": "org.apache.flink.ml.benchmark.datagenerator.common.LabeledPointWithWeightGenerator",
            "paramMap": {
                "colNames": [["features", "label", "weight"]],
                "numValues": 1024,
                "vectorDim": 8,
            },
        },
    }
    result = run_benchmark("LR-dispatch-gap", entry)
    wall_ms = (
        result["phaseTimesMs"].get("fit", 0.0)
        + result["phaseTimesMs"].get("transform", 0.0)
    )
    assert result["gapCount"] >= 1
    assert result["hostDispatchMs"] > 0
    expected = wall_ms - result["hostDispatchMs"]
    assert abs(result["dispatchGapMs"] - expected) <= 0.05 * wall_ms + 1e-6
    attr = result["dispatchAttribution"]
    assert attr is not None and attr["gapCount"] >= 1
    assert "chunks" not in attr  # bounded BENCH payload
    json.dumps(result)  # BENCH payload stays serializable
