"""The reader of the program's dense-epoch counters (`dense_epoch.one_pass`,
`dense_epoch.reduce`): on a `run` made by hand, its entry in BENCHMARK.json,
and in a traced rehearsal of the one-chip dense cell against the program as
it is."""

import json

import pytest

import run as harness

read = harness.load_module("metrics", "dense_one_pass_share").read


def hand_made(counters):
    return {"counters": counters, "window": {"attempted": 4}, "trace": None}


@pytest.mark.parametrize(
    "counters, value",
    [
        ({"dense_epoch.one_pass": 3, "dense_epoch.reduce": 1}, 75.0),  # both: one fit of four was turned away
        ({"dense_epoch.one_pass": 4}, 100.0),  # the one-chip dense cell
        ({"dense_epoch.reduce": 4}, 0.0),  # four chips: laid-out batches keep the reduce form
        ({"iteration.host_sync": 4, "layout.general": 4}, None),  # a sparse fit; the parent counts neither
        ({}, None),
    ],
)
def test_reader_on_a_hand_made_run(counters, value):
    assert read(hand_made(counters)) == value


def test_metric_lists_the_dense_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "dense_one_pass_share"]
    assert entry["workloads"] == ["lr-dense-100.pass", "lr-dense-100.pass-x4"]
    assert entry["moves"] == "trained_rows_per_s" and entry["source"] == "program_counter"
    assert entry["layer"] == "Device programs" and entry["better"] == "higher" and entry["unit"] == "%"
    assert bench["per_layer"][-1] is entry  # appended: nothing that was there moved
    assert not harness.wanted_metrics(bench, "lr-sparse-1m.partitions", True).count(entry)


def test_traced_rehearsal_of_the_dense_cell_reports_it(capsys):
    code = harness.main(
        ["--workload", "lr-dense-100.pass", "--rehearse-on-cpu", "--seed", "2147484030", "--seconds", "0.5", "--trace", "1"]
    )
    assert code == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"]
    # a CPU table is on no TPU: every fit keeps the reduce form
    assert result["metrics"]["dense_one_pass_share"] == {"value": 0.0, "unit": "%"}
