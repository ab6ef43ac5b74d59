"""The reader of the program's cross-term counters (`lloyd.product.short`,
`lloyd.product.full`): on a `run` made by hand, its entry in BENCHMARK.json,
and in a traced rehearsal of the k-means cell against the program as it is."""

import json

import pytest

import run as harness

CELL = "kmeans-mnist8m.refit"
read = harness.load_module("metrics", "lloyd_short_product_share").read


@pytest.mark.parametrize(
    "counters, value",
    [
        ({"lloyd.iterations": 15, "lloyd.product.short": 3}, 100.0),  # the cell: every fit found its pixels exact
        ({"lloyd.iterations": 15, "lloyd.product.full": 3}, 0.0),  # a table of general floats, or one on no TPU
        ({"lloyd.iterations": 20, "lloyd.product.short": 3, "lloyd.product.full": 1}, 75.0),  # a window of both
        ({"lloyd.iterations": 15, "lloyd.blocks": 6090}, None),  # the parent counts neither
        ({"iteration.host_sync": 4}, None),  # a window of other stages' fits
        ({}, None),
    ],
)
def test_reader_on_a_hand_made_run(counters, value):
    assert read({"counters": counters, "window": {"attempted": 4}, "trace": None}) == value


def test_metric_lists_the_kmeans_cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "lloyd_short_product_share"]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "trained_rows_per_s" and entry["source"] == "program_counter"
    assert entry["layer"] == "Device programs" and entry["better"] == "higher" and entry["unit"] == "%"
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert harness.wanted_metrics(bench, CELL, True).count(entry) == 1
    assert not harness.wanted_metrics(bench, CELL, False).count(entry)  # a per-layer metric: traced runs only
    assert not harness.wanted_metrics(bench, "lr-dense-100.pass", True).count(entry)


def test_traced_rehearsal_of_the_kmeans_cell_reports_it(capsys):
    code = harness.main(
        ["--workload", CELL, "--rehearse-on-cpu", "--seed", "2147484041", "--seconds", "0.3", "--trace", "1"]
    )
    assert code == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"]
    # a CPU table is on no TPU: nothing is looked at and every fit keeps the full product
    assert result["metrics"]["lloyd_short_product_share"] == {"value": 0.0, "unit": "%"}
    assert result["metrics"]["host_syncs_per_fit"]["value"] == 1.0
