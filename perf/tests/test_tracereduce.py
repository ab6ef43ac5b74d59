"""perf/tracereduce.py on a recorded trace and on one made by hand.

`trace_v5e_two_fits.json` is two fits of lr-dense-100.pass recorded on one
v5e chip (my chip run, PR 24), in tracereduce's plain form with the HLO text
of each op cut down to its name.
"""

import json
import os

import pytest

import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_v5e_two_fits.json")) as f:
        return tr.reduce(json.load(f), "perf.fit")


def test_recorded_window_and_busy(recorded):
    assert recorded["spans"] == 2
    assert recorded["window_s"] == pytest.approx(0.057474974)
    assert recorded["busy_s"] == pytest.approx(0.050536295)
    assert recorded["busy_s_fullest"] == recorded["busy_s"]  # one device
    assert recorded["busy_in_spans_s"] == pytest.approx(0.050536295)


def test_recorded_programs_and_ops(recorded):
    assert recorded["modules_s"]["jit__sgd_train_flat"] == pytest.approx(0.050533916)
    ops = dict(recorded["breakdown"]["device_ops"])
    assert ops["multiply_reduce_fusion.5"] == pytest.approx(0.023541367)
    assert ops["multiply_reduce_fusion.4"] == pytest.approx(0.023512014)
    # the while's own time is what its body does not cover, not the loop's length
    assert ops["while.5"] == pytest.approx(0.000580256)
    # self times add up to the busy time: nothing is counted twice
    assert sum(recorded["ops_s"].values()) == pytest.approx(recorded["busy_s"], rel=1e-3)
    assert recorded["collective_s"] == 0


def test_recorded_gaps_cover_the_idle_time(recorded):
    gaps = recorded["gaps_s"]
    assert set(gaps) == {
        "perf.fit:dispatch", "perf.fit:readback", "perf.fit:between_programs", "between:perf.fit",
    }
    assert sum(gaps.values()) == pytest.approx(recorded["window_s"] - recorded["busy_s"])
    assert len(recorded["breakdown"]["idle_gaps"]) <= 10


def by_hand():
    """Two devices, times in ns. Device 0: a while of 100..500 holding two ops
    and an all-reduce; device 1 is busy 100..300 only. One span 0..600, and a
    second, 700..800, in which nothing runs."""
    ops0 = [
        ["%while.1 = x", 100, 400],
        ["%fusion.2 = f32[8] fusion(...)", 100, 100],
        ["%all-reduce.3 = f32[8] all-reduce(...)", 250, 50],
        ["%fusion.2 = f32[8] fusion(...)", 300, 100],
    ]
    return {
        "planes": [
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": [["jit_train(123)", 100, 400]]},
                {"name": "XLA Ops", "events": ops0},
            ]},
            {"name": "/device:TPU:1", "lines": [
                {"name": "XLA Modules", "events": [["jit_train(123)", 100, 200]]},
                {"name": "XLA Ops", "events": [["%fusion.2 = x", 100, 200]]},
            ]},
            {"name": "/host:CPU", "lines": [
                {"name": "spans", "events": [["perf.fit", 0, 600], ["perf.fit", 700, 100], ["other", 0, 5]]},
            ]},
        ]
    }


def test_busy_is_a_union_per_device_not_a_sum_over_devices():
    r = tr.reduce(by_hand(), "perf.fit")
    assert r["window_s"] == pytest.approx(800e-9)
    assert r["busy_s_fullest"] == pytest.approx(400e-9)  # not 400 + 200
    assert r["busy_s"] == pytest.approx(300e-9)  # mean of the devices
    assert r["modules_s"]["jit_train"] == pytest.approx(300e-9)


def test_self_times_and_collectives_by_hand():
    r = tr.reduce(by_hand(), "perf.fit")
    # device 0: fusion.2 200, all-reduce 50, while 400 - 250; device 1: fusion.2 200
    assert r["ops_s"]["fusion.2"] == pytest.approx(200e-9)
    assert r["ops_s"]["all-reduce.3"] == pytest.approx(25e-9)
    assert r["ops_s"]["while.1"] == pytest.approx(75e-9)
    assert r["collective_s"] == pytest.approx(25e-9)


def test_gaps_are_named_by_the_covering_span():
    gaps = tr.reduce(by_hand(), "perf.fit")["gaps_s"]
    assert gaps == {
        "perf.fit:dispatch": pytest.approx(100e-9),
        "perf.fit:readback": pytest.approx(100e-9),
        "between:perf.fit": pytest.approx(100e-9),
        "perf.fit:no_device_work": pytest.approx(100e-9),
    }


def test_a_trace_without_spans_or_devices_is_refused():
    trace = by_hand()
    with pytest.raises(RuntimeError):
        tr.reduce(trace, "perf.serve")
    trace["planes"] = [p for p in trace["planes"] if not p["name"].startswith("/device")]
    with pytest.raises(RuntimeError):
        tr.reduce(trace, "perf.fit")
    assert tr.reduce(trace, "perf.fit", need_device=False) is None


def test_names():
    assert tr.op_name("%fusion.4 = f32[100]{0} fusion(f32[] %p), kind=kLoop") == "fusion.4"
    assert tr.op_name("jit__sgd_train_flat(14303377257775465215)") == "jit__sgd_train_flat"
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.idle_in([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
