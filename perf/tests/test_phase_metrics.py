"""The readers of the program's fit phases (`fit.*.ns`, `fit.total.n`) and of
its compile counter: on a `run` made by hand, and in a traced rehearsal of
perf/run.py against the program as it is."""

import json

import pytest

import run as harness

NS = 1_000_000  # a millisecond


def reader(name):
    return harness.load_module("metrics", name).read


def hand_made(counters):
    return {"counters": counters, "window": {"attempted": 4}, "trace": None}


WHOLE = {
    "fit.total.n": 4, "fit.total.ns": 40 * NS,
    "fit.extract.n": 4, "fit.extract.ns": 2 * NS,
    "fit.stage.ns": 10 * NS, "fit.layout.ns": 4 * NS,  # the layout lies inside the staging
    "fit.launch.ns": 8 * NS, "fit.readback.ns": 12 * NS,
    "jit.compiles": 3,
}


@pytest.mark.parametrize(
    "name, value",
    [
        ("fit_prelaunch_ms", 3.0),  # (2 + 10) ms over 4 fits
        ("fit_launch_ms", 2.0),
        ("fit_finish_ms", 2.0),  # (40 - 2 - 10 - 8 - 12) ms over 4 fits
        ("window_compiles", 3),
    ],
)
def test_readers_on_a_hand_made_run(name, value):
    assert reader(name)(hand_made(WHOLE)) == pytest.approx(value)


def test_the_layout_is_not_counted_beside_the_staging():
    counters = {k: v for k, v in WHOLE.items() if k != "fit.layout.ns"}  # the flat route
    assert reader("fit_prelaunch_ms")(hand_made(counters)) == pytest.approx(3.0)
    assert reader("fit_finish_ms")(hand_made(counters)) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"])
def test_fits_without_the_phases_report_nothing(name):
    # a pipeline's fit, a scaler's or KMeans' counts `fit.total` and none of
    # the phases: a mean over all fits would be of another thing
    counters = dict(WHOLE, **{"fit.total.n": 6})
    assert reader(name)(hand_made(counters)) is None


@pytest.mark.parametrize("name", ["fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"])
def test_a_program_without_phases_reports_nothing(name):
    # the parent commit: counters, but none of this PR's
    assert reader(name)(hand_made({"iteration.host_sync": 4})) is None


def test_no_compile_in_the_window_reads_zero():
    assert reader("window_compiles")(hand_made({"iteration.host_sync": 4})) == 0


def test_traced_rehearsal_reports_all_four(capsys):
    cell = harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"][0]["name"]
    code = harness.main(
        ["--workload", cell, "--rehearse-on-cpu", "--seed", "2147484001", "--seconds", "0.5", "--trace", "1"]
    )
    assert code == 0
    metrics = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["metrics"]
    for name in ("fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"):
        assert metrics[name]["unit"] == "ms" and metrics[name]["value"] > 0
    assert metrics["window_compiles"] == {"value": 0, "unit": "count"}
    assert metrics["host_syncs_per_fit"]["value"] == 1.0
