"""The reader of the program's layout counters (`layout.exchange`,
`layout.general`): on a `run` made by hand, and in a traced rehearsal of the
four-chip cell against the program as it is."""

import json

import pytest

import run as harness

read = harness.load_module("metrics", "layout_exchange_share").read


def hand_made(counters):
    return {"counters": counters, "window": {"attempted": 4}, "trace": None}


@pytest.mark.parametrize(
    "counters, value",
    [
        ({"layout.exchange": 4, "layout.general": 4}, 50.0),  # four dense fits: X exchanged, y a 1-D column
        ({"layout.exchange": 8, "layout.general": 4}, 100 * 8 / 12),  # four sparse fits: both leaves, and y
        ({"layout.exchange": 8}, 100.0),
        ({"layout.general": 8}, 0.0),  # a program that has the exchange and could not take it
        ({"iteration.host_sync": 4}, None),  # one data shard lays nothing out; the parent counts neither
        ({}, None),
    ],
)
def test_reader_on_a_hand_made_run(counters, value):
    assert read(hand_made(counters)) == value


def test_metric_lists_only_the_cell_that_lays_batches_out():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "layout_exchange_share"]
    assert entry["workloads"] == ["lr-dense-100.pass-x4"]
    assert entry["moves"] == "trained_rows_per_s" and entry["source"] == "program_counter"
    assert not harness.wanted_metrics(bench, "lr-dense-100.pass", True).count(entry)


def test_traced_rehearsal_of_the_four_chip_cell_reports_it(capsys):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("the rehearsal of the four-chip cell needs four devices")
    code = harness.main(
        ["--workload", "lr-dense-100.pass-x4", "--rehearse-on-cpu", "--seed", "2147484003", "--seconds", "0.5", "--trace", "1"]
    )
    assert code == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"]
    # the CPU keeps a table's rows major, where the general form is already one all-to-all
    assert result["metrics"]["layout_exchange_share"] == {"value": 0.0, "unit": "%"}
