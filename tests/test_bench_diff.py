"""bench_diff regression gate (scripts/bench_diff.py) — file-shape
normalization (headline / driver wrapper / truncated-tail recovery),
direction + threshold policy, and the two acceptance cases: the
synthetic 20% wallMs regression exits nonzero, a clean pair in the
driver's wrapper shape exits zero."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FIXDIR = os.path.join(_ROOT, "tests", "fixtures", "bench_diff")


def _load_module():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(_ROOT, "scripts", "bench_diff.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_diff = _load_module()


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "scripts", "bench_diff.py"), *args],
        capture_output=True,
        text=True,
        cwd=_ROOT,
    )


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------

def test_metric_directions():
    assert bench_diff.metric_direction("totalTimeMs") == "lower"
    assert bench_diff.metric_direction("wallMs") == "lower"
    assert bench_diff.metric_direction("epochMsAmortized") == "lower"
    assert bench_diff.metric_direction("hostSyncCount") == "lower"
    assert bench_diff.metric_direction("relDiff") == "lower"
    # the elastic supervisor's SLO leaves (ISSUE 15): detection latency
    # and recovery wall regress upward
    assert bench_diff.metric_direction("elasticRecovery.detectionMs") == "lower"
    assert bench_diff.metric_direction("elasticRecovery.recoveryWallMs") == "lower"
    assert bench_diff.metric_direction("inputThroughput") == "higher"
    assert bench_diff.metric_direction("trainedExamplesPerSec") == "higher"
    assert bench_diff.metric_direction("trainLoopMFU_trace") == "higher"
    assert bench_diff.metric_direction("vsPublishedBaseline") == "higher"
    assert bench_diff.metric_direction("numChips") is None
    assert bench_diff.metric_direction("h2dBytes") is None  # info by default
    # the whole-fit dispatch gate: sync/dispatch counts and host-dispatch
    # wall are direction-gated, so a resident-path regression fails CI
    assert bench_diff.metric_direction("hostDispatchMs") == "lower"
    assert bench_diff.metric_direction("dispatchCount") == "lower"
    assert bench_diff.metric_direction("wholeFitFallbacks") == "lower"
    # the chunked reference side of wholeFitDispatch is informational
    assert bench_diff.metric_direction("hostSyncCountChunked") is None
    assert bench_diff.metric_direction("dispatchCountChunked") is None
    # device-memory leaves (the HBM ledger, ISSUE 16): a fit holding more
    # HBM or a fatter resident model regresses upward
    assert bench_diff.metric_direction("peakHbmBytes") == "lower"
    assert bench_diff.metric_direction("residentModelBytes") == "lower"
    assert bench_diff.metric_direction("kmeans.peakHbmBytes") == "lower"
    # AOT program bank (docs/performance.md §12): a slower banked cold
    # start or any miss on the declared program space gates by default
    assert bench_diff.metric_direction("aotColdStart.coldStartMs") == "lower"
    assert bench_diff.metric_direction("aotColdStart.baselineColdStartMs") == "lower"
    assert bench_diff.metric_direction("aotColdStart.bankMisses") == "lower"


def test_hbm_memory_regression_fails_gate():
    """A fit whose peak HBM footprint doubles must REGRESS at the default
    threshold — memory is gated like latency, no explicit --rule needed."""
    rows = bench_diff.diff_entries(
        {"lr": {"peakHbmBytes": 1_000_000.0, "residentModelBytes": 4096.0}},
        {"lr": {"peakHbmBytes": 2_000_000.0, "residentModelBytes": 4096.0}},
        0.15,
        [],
    )
    verdicts = {r["path"]: r["verdict"] for r in rows}
    assert verdicts["lr.peakHbmBytes"] == "REGRESSED"
    assert verdicts["lr.residentModelBytes"] == "ok"
    # shrinking memory is an improvement, never a regression
    improved = bench_diff.diff_entries(
        {"lr": {"peakHbmBytes": 2_000_000.0}},
        {"lr": {"peakHbmBytes": 1_000_000.0}},
        0.15,
        [],
    )
    assert improved[0]["verdict"] != "REGRESSED"


def test_whole_fit_dispatch_regressions_fail_gate():
    """A whole-fit entry whose fit stops being resident (hostSyncCount
    1 -> 61, dispatchCount 1 -> 60, hostDispatchMs up) must REGRESS even
    at the default threshold — these leaves are gated by direction, no
    explicit --rule needed."""
    old = {
        "wholeFitDispatch": {
            "hostSyncCount": 1.0,
            "dispatchCount": 1.0,
            "hostDispatchMs": 6.0,
        }
    }
    new = {
        "wholeFitDispatch": {
            "hostSyncCount": 61.0,
            "dispatchCount": 60.0,
            "hostDispatchMs": 300.0,
        }
    }
    rows = bench_diff.diff_entries(old, new, 0.15, [])
    verdicts = {r["path"]: r["verdict"] for r in rows}
    assert verdicts["wholeFitDispatch.hostSyncCount"] == "REGRESSED"
    assert verdicts["wholeFitDispatch.dispatchCount"] == "REGRESSED"
    assert verdicts["wholeFitDispatch.hostDispatchMs"] == "REGRESSED"
    # the zero-tolerance CI rule pins hostSyncCount exactly
    strict = bench_diff.diff_entries(
        {"wholeFitDispatch": {"hostSyncCount": 1.0}},
        {"wholeFitDispatch": {"hostSyncCount": 2.0}},
        0.15,
        [("*.hostSyncCount", 0.0)],
    )
    assert strict[0]["verdict"] == "REGRESSED"


def test_multihost_checkpoint_gating_directions():
    """multiHostCheckpoint (ISSUE 14): the per-host-count save walls and
    the kill@commit resume wall are direction-gated (lower); shard sizing
    is informational (bytes-per-host is a layout fact, not a speed)."""
    assert (
        bench_diff.metric_direction("multiHostCheckpoint.host4.savePerEpochMs")
        == "lower"
    )
    assert (
        bench_diff.metric_direction("multiHostCheckpoint.resumeWallMs")
        == "lower"
    )
    assert (
        bench_diff.metric_direction("multiHostCheckpoint.host4.shardBytesPerHost")
        is None
    )
    old = {
        "multiHostCheckpoint": bench_diff.flatten(
            {
                "host4": {"savePerEpochMs": 20.0, "shardBytesPerHost": 300.0},
                "resumeWallMs": 100.0,
            }
        )
    }
    new = {
        "multiHostCheckpoint": bench_diff.flatten(
            {
                "host4": {"savePerEpochMs": 30.0, "shardBytesPerHost": 600.0},
                "resumeWallMs": 150.0,
            }
        )
    }
    rows = bench_diff.diff_entries(old, new, 0.15, [])
    verdicts = {r["path"]: r["verdict"] for r in rows}
    assert verdicts["multiHostCheckpoint.host4.savePerEpochMs"] == "REGRESSED"
    assert verdicts["multiHostCheckpoint.resumeWallMs"] == "REGRESSED"
    assert verdicts["multiHostCheckpoint.host4.shardBytesPerHost"] == "info"


def test_cold_time_informational_by_default():
    rows = bench_diff.diff_entries(
        {"e": {"coldTimeMs": 100.0}}, {"e": {"coldTimeMs": 200.0}}, 0.15, []
    )
    assert rows[0]["verdict"] == "info"
    # ...unless an explicit rule gates it
    rows = bench_diff.diff_entries(
        {"e": {"coldTimeMs": 100.0}},
        {"e": {"coldTimeMs": 200.0}},
        0.15,
        [("e.coldTimeMs", 0.5)],
    )
    assert rows[0]["verdict"] == "REGRESSED"


def test_threshold_and_direction_semantics():
    old = {"e": {"totalTimeMs": 100.0, "inputThroughput": 1000.0}}
    ok = {"e": {"totalTimeMs": 110.0, "inputThroughput": 900.0}}
    bad = {"e": {"totalTimeMs": 130.0, "inputThroughput": 700.0}}
    rows = {r["path"]: r for r in bench_diff.diff_entries(old, ok, 0.15, [])}
    assert rows["e.totalTimeMs"]["verdict"] == "ok"
    assert rows["e.inputThroughput"]["verdict"] == "ok"
    rows = {r["path"]: r for r in bench_diff.diff_entries(old, bad, 0.15, [])}
    assert rows["e.totalTimeMs"]["verdict"] == "REGRESSED"
    assert rows["e.inputThroughput"]["verdict"] == "REGRESSED"
    # improvements never fail
    better = {"e": {"totalTimeMs": 50.0, "inputThroughput": 2000.0}}
    rows = bench_diff.diff_entries(old, better, 0.15, [])
    assert all(r["verdict"] == "improved" for r in rows)


def test_small_time_jitter_not_gated():
    rows = bench_diff.diff_entries(
        {"e": {"fitTimeMs": 1.0}}, {"e": {"fitTimeMs": 3.0}}, 0.15, []
    )
    assert rows[0]["verdict"] == "ok"  # below the 5ms jitter floor


def test_cpu_baseline_entry_informational():
    rows = bench_diff.diff_entries(
        {"cpuBaseline": {"totalTimeMs": 20000.0}},
        {"cpuBaseline": {"totalTimeMs": 90000.0}},
        0.15,
        [],
    )
    assert rows[0]["verdict"] == "info"  # host speed is not our regression


# ---------------------------------------------------------------------------
# normalization + recovery
# ---------------------------------------------------------------------------

def test_normalize_headline_and_wrapper():
    headline = {"value": 1.0, "vs_baseline": 2.0, "details": {"kmeans": {"totalTimeMs": 5.0}}}
    entries = bench_diff.normalize(headline)
    assert entries["headline"] == {"value": 1.0, "vs_baseline": 2.0}
    assert entries["kmeans"]["totalTimeMs"] == 5.0
    wrapper = {"n": 1, "cmd": "x", "rc": 0, "tail": "", "parsed": headline}
    assert bench_diff.normalize(wrapper) == entries


def test_tail_recovery_outermost_fragments():
    """A truncated driver tail (headline JSON cut mid-line) still yields
    the complete per-entry fragments — outermost only, so a nested dict
    inside a recovered entry is not double-reported."""
    tail = (
        '4810.43, "unit": "records/s/chip", "det'  # cut headline
        '"kmeans": {"coldTimeMs": 800.0, "totalTimeMs": 200.0, '
        '"inner": {"x": 1.0}}, '
        '"sweep": {"file": "benchmarks/SWEEP.json"}'
    )
    wrapper = {"n": 5, "cmd": "x", "rc": 0, "tail": tail, "parsed": None}
    entries = bench_diff.normalize(wrapper)
    assert "kmeans" in entries
    assert entries["kmeans"]["totalTimeMs"] == 200.0
    assert "inner" not in entries  # nested fragment folded into kmeans
    assert "sweep" not in entries  # no numeric leaves -> not an entry


def _headline(scale=1.0):
    """A bench.py headline line in the shape the round driver recorded:
    a long trace entry first, then the stage entries."""
    return {
        "metric": "logisticregression_train_throughput",
        "value": 1.0e8 / scale,
        "unit": "records/s/chip",
        "details": {
            "logisticregressionTrace": {
                "wallMs": 120.0 * scale,
                "topOps": {
                    f"fusion.{i}": {"durUs": 50.0 + i, "bytes": 32000240, "count": 20}
                    for i in range(40)
                },
            },
            "sparseWideLR": {"coldTimeMs": 2300.0 * scale, "totalTimeMs": 1600.0 * scale},
            "kmeans": {"coldTimeMs": 936.0 * scale, "totalTimeMs": 93.0 * scale,
                       "vsPublishedBaseline": 76.9},
            "sweep": {"file": "benchmarks/SWEEP.json", "meta": {"numEntries": 44}},
        },
    }


def _driver_wrapper(headline, keep=None):
    """The driver's record of a bench run: the last `keep` characters of
    stdout (None = all of it, parsed)."""
    line = json.dumps(headline)
    parsed = headline if keep is None else None
    return {"n": 5, "cmd": "python bench.py", "rc": 0,
            "tail": line if keep is None else line[-keep:], "parsed": parsed}


def test_cut_driver_tail_recovers_entries():
    """A wrapper whose single JSON line outgrew the tail the driver keeps
    (`parsed: null`, the head cut mid-entry) still yields the entries that
    survive whole in the tail."""
    line = json.dumps(_headline())
    keep = len(line) - line.index('"fusion.30"')  # cut inside the trace entry
    wrapper = _driver_wrapper(_headline(), keep=keep)
    with pytest.raises(ValueError):
        json.loads(wrapper["tail"])
    entries = bench_diff.normalize(wrapper)
    assert "sparseWideLR" in entries and "kmeans" in entries
    assert entries["kmeans"]["totalTimeMs"] > 0


def test_flatten_skips_registry_and_bounds_depth():
    entry = {
        "totalTimeMs": 5.0,
        "ok": True,
        "metrics": {"counters": {"x": 1}},
        "dispatchAttribution": {"windowMs": 4.0, "perEpoch": {"wallMs": 1.0}},
    }
    flat = bench_diff.flatten(entry)
    assert flat["totalTimeMs"] == 5.0
    assert "ok" not in flat  # bools are not metrics
    assert not any(k.startswith("metrics") for k in flat)
    assert flat["dispatchAttribution.windowMs"] == 4.0


# ---------------------------------------------------------------------------
# acceptance: CLI exit codes
# ---------------------------------------------------------------------------

def test_cli_synthetic_20pct_wallms_regression_exits_nonzero():
    out = _run_cli(
        os.path.join(_FIXDIR, "BENCH_base.json"),
        os.path.join(_FIXDIR, "BENCH_regressed.json"),
        "--check",
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "REGRESSED" in out.stdout
    assert "wallMs" in out.stdout


def test_cli_clean_wrapper_pair_exits_zero(tmp_path):
    """A clean pair in the driver's wrapper shape — one parsed, one with a
    cut tail, the second 2% faster — passes the gate."""
    line = json.dumps(_headline(0.98))
    keep = len(line) - line.index('"fusion.30"')
    for name, wrapper in (
        ("BENCH_a.json", _driver_wrapper(_headline())),
        ("BENCH_b.json", _driver_wrapper(_headline(0.98), keep=keep)),
    ):
        with open(tmp_path / name, "w") as f:
            json.dump(wrapper, f)
    out = _run_cli(str(tmp_path / "BENCH_a.json"), str(tmp_path / "BENCH_b.json"), "--check")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 regression(s)" in out.stdout


def test_cli_json_format_and_rules():
    out = _run_cli(
        os.path.join(_FIXDIR, "BENCH_base.json"),
        os.path.join(_FIXDIR, "BENCH_regressed.json"),
        "--format", "json",
        "--rule", "logisticregressionTrace.*=0.5",
    )
    assert out.returncode == 0, out.stdout + out.stderr  # 20% < 50% override
    doc = json.loads(out.stdout)
    assert doc["regressions"] == 0
    assert any(r["path"] == "logisticregressionTrace.wallMs" for r in doc["rows"])


def test_cli_latest_pair_and_usage_errors(tmp_path):
    for name, wall in (("BENCH_r01.json", 100.0), ("BENCH_r02.json", 101.0)):
        with open(tmp_path / name, "w") as f:
            json.dump({"e": {"totalTimeMs": wall}}, f)
    out = _run_cli("--latest", "--dir", str(tmp_path))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "BENCH_r01.json" in out.stdout and "BENCH_r02.json" in out.stdout
    assert _run_cli().returncode == 0  # no args -> usage text, rc 0
    assert _run_cli("only_one.json").returncode == 2
    assert _run_cli("missing_a.json", "missing_b.json").returncode == 2


def test_aot_cold_start_regressions_fail_gate():
    """A banked cold start that slows past threshold, or any bank miss
    appearing on the declared program space, must REGRESS by default;
    the CI --rule pins serveTraceCount at exactly zero (the no-compile
    serving SLA, docs/performance.md §12)."""
    rows = bench_diff.diff_entries(
        {"aotColdStart": {"coldStartMs": 400.0, "bankMisses": 0.0}},
        {"aotColdStart": {"coldStartMs": 900.0, "bankMisses": 2.0}},
        0.15,
        [],
    )
    verdicts = {r["path"]: r["verdict"] for r in rows}
    assert verdicts["aotColdStart.coldStartMs"] == "REGRESSED"
    assert verdicts["aotColdStart.bankMisses"] == "REGRESSED"
    strict = bench_diff.diff_entries(
        {"aotColdStart": {"serveTraceCount": 0.0}},
        {"aotColdStart": {"serveTraceCount": 1.0}},
        0.15,
        [("aotColdStart.serveTraceCount", 0.0)],
    )
    assert strict[0]["verdict"] == "REGRESSED"
