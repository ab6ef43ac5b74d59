"""The reader of the program's sparse-epoch counters (`sparse_epoch.entries`,
`sparse_epoch.entries_gathered`): on a `run` made by hand, on a run of a
program that counts neither (the parent: nothing, and no error), and in a
traced rehearsal of the sparse cell against the program as it is."""

import json

import pytest

import run as harness

read = harness.load_module("metrics", "sparse_gather_share").read


def hand_made(counters):
    return {"counters": counters, "window": {"attempted": 4}, "trace": None}


@pytest.mark.parametrize(
    "counters, value",
    [
        # ten fits, a batch of 100,000 rows x 39 fields each, 12 of the fields gathered
        ({"sparse_epoch.entries": 39_000_000, "sparse_epoch.entries_gathered": 12_000_000}, 100 * 12 / 39),
        # in one fit of ten the plan did not hold three more fields
        ({"sparse_epoch.entries": 39_000_000, "sparse_epoch.entries_gathered": 12_300_000}, 100 * 123 / 390),
        ({"sparse_epoch.entries": 3_900_000, "sparse_epoch.entries_gathered": 3_900_000}, 100.0),  # no plan
        ({"sparse_epoch.entries": 3_900_000}, 0.0),  # a table of dictionaries and constants alone
        ({"iteration.host_sync": 4, "dense_epoch.one_pass": 4}, None),  # a dense fit
        ({"iteration.host_sync": 4}, None),  # the parent's sparse fit: it counts no entries
        ({}, None),
    ],
)
def test_reader_on_a_hand_made_run(counters, value):
    assert read(hand_made(counters)) == (value if value is None else pytest.approx(value))


def test_traced_rehearsal_of_the_sparse_cell_reports_it(capsys):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    listed = [m["name"] for m in harness.wanted_metrics(bench, "lr-sparse-1m.partitions", True)]
    if "sparse_gather_share" not in listed:
        pytest.skip("BENCHMARK.json does not list the metric in the sparse cell")
    code = harness.main(
        ["--workload", "lr-sparse-1m.partitions", "--rehearse-on-cpu", "--seed", "2147484032", "--seconds", "0.5", "--trace", "1"]
    )
    assert code == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"]
    # a CPU table is on no TPU: every fit keeps the general program, every entry is gathered
    assert result["metrics"]["sparse_gather_share"] == {"value": 100.0, "unit": "%"}
    assert result["metrics"]["host_syncs_per_fit"]["value"] == 1
