"""perf/run.py end to end at the rehearsal size, off the chip: the sound
program is correct; the control and every fault a cell can have are not.

The faults are planted by putting the plain reference, broken, where the
program's estimator stands (perf/faults.py); the rest of the run is the
harness's own: tables, window, comparison, result line.
"""

import json

import numpy as np
import pytest

import faults
import run as harness

CELLS = [w["name"] for w in harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"]]
ARGS = ["--seed", "2147483999", "--seconds", "0.5", "--trace", "0"]


def result_of(capsys, cell, extra=()):
    code = harness.main(["--workload", cell, "--rehearse-on-cpu", *ARGS, *extra])
    out, err = capsys.readouterr()
    assert code == 0
    return json.loads(out.strip().splitlines()[-1]), err


def plant(monkeypatch, fault=None, precision="float32", chips=None):
    def make_stage(ctx, params):
        return faults.ReferenceStage(
            ctx.load("reference", ctx.cell["config"]),
            ctx.load("tables", ctx.config["data"]["table"]),
            ctx.config["data"], params, chips or ctx.chips, fault, precision,
        )

    monkeypatch.setattr(harness.Context, "make_stage", make_stage)


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_is_correct_and_the_line_is_whole(capsys, cell):
    result, err = result_of(capsys, cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"  # the line names where it ran
    assert "setup_s" in result["metrics"] and "trained_rows_per_s" in result["metrics"]
    assert list(result)[-1] == "compared"
    for name, entry in result["compared"].items():
        assert f"compared {name} = " in err and "limit" in err
    assert err.strip().splitlines()[-1] == "correct = True"


@pytest.mark.parametrize("cell", CELLS)
def test_the_sound_reference_in_the_programs_place_is_correct(capsys, monkeypatch, cell):
    plant(monkeypatch)
    assert result_of(capsys, cell)[0]["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_bfloat16_is_not_correct(capsys, monkeypatch, cell):
    plant(monkeypatch, precision="bfloat16")
    result, err = result_of(capsys, cell)
    assert result["correct"] is False
    assert "FAILED" in err and err.strip().splitlines()[-1] == "correct = False"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "no_exchange", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, cell, fault):
    # no_exchange: what the first of four chips would return without the all-reduce
    plant(monkeypatch, fault=fault, chips=4 if fault == "no_exchange" else None)
    result, _ = result_of(capsys, cell)
    assert result["correct"] is False
    assert not all(entry["ok"] for entry in result["compared"].values())


def test_a_failed_fit_is_counted_and_not_correct(capsys, monkeypatch):
    class Raises:
        calls = 0

        def fit(self, table):
            Raises.calls += 1
            if Raises.calls > 1:  # the warm-up fit succeeds
                raise RuntimeError("planted")
            return faults.Model(np.zeros(3))

    monkeypatch.setattr(harness.Context, "make_stage", lambda ctx, params: Raises())
    result, _ = result_of(capsys, CELLS[0])
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0


def test_without_a_chip_there_is_no_result(capsys):
    with pytest.raises(SystemExit) as stop:
        harness.main(["--workload", CELLS[0], *ARGS])
    assert stop.value.code != 0
    assert capsys.readouterr().out == ""


def test_traced_rehearsal_reports_only_what_it_can_read(capsys):
    result, _ = result_of(capsys, CELLS[0], ["--trace", "1"])
    assert result["correct"] is True
    assert "host_syncs_per_fit" in result["metrics"]
    assert "epoch_roofline" not in result["metrics"]  # no device trace off the chip
