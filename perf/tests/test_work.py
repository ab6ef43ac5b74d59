"""perf/work.py against the values worked by hand in ISSUE 24."""

import json
import os

import pytest

import work

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"globalBatchSize": 100000}


def test_dense_epoch():
    counted = work.dense_lr_epoch({"dim": 100}, PARAMS)
    assert counted == {"bytes": 40_000_000 + 800_000 + 800, "flops": 40_000_000}


def test_sparse_epoch():
    counted = work.sparse_lr_epoch({"dim": 1_000_000, "nnz": 39}, PARAMS)
    assert counted == {"bytes": 31_200_000 + 400_000 + 8_000_000, "flops": 15_600_000}


@pytest.mark.parametrize("chips", [1, 4])
def test_least_seconds_is_the_hbm_bound_on_the_v5e(chips):
    with open(os.path.join(PERF, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    least = work.least_seconds(work.dense_lr_epoch({"dim": 100}, PARAMS), peak, chips)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(40_800_800 / 819e9 / chips)
    assert least["flops_seconds"] == pytest.approx(4e7 / 197e12 / chips)


def test_every_configuration_names_a_counter_that_exists():
    configs = os.path.join(PERF, "configs")
    for name in os.listdir(configs):
        with open(os.path.join(configs, name)) as f:
            config = json.load(f)
        counted = getattr(work, config["work"])(config["data"], config["stage"]["params"])
        assert counted["bytes"] > 0 and counted["flops"] > 0


def test_a_chip_trace_without_the_training_program_is_an_error():
    import run as harness

    reader = harness.load_module("metrics", "epoch_roofline")
    run = {
        "trace": {"modules_s": {"jit_renamed": 1.0}}, "least_per_unit": {"seconds": 1e-9},
        "config": {"train_programs": ["jit__sgd_train_flat"]}, "window": {"units": [100000]},
    }
    with pytest.raises(RuntimeError, match="jit_renamed"):
        reader.read(run)
    run["trace"]["modules_s"]["jit__sgd_train_flat"] = 2.0
    assert reader.read(run) == pytest.approx(100000 * 1e-9 / 2.0 * 100.0)
    assert reader.read(dict(run, least_per_unit=None)) is None  # off the chip there is no peak
