"""What the path configuration brought: the work counter, the grid, the
generator `fleet_fit_loop` and its faults through perf/run.py at the
rehearsal size, and the two readers of the program's `fleet.*` counters."""

import json

import numpy as np
import pytest

import run as harness

CELL = "lr-regpath-100.path"
CONFIG = harness.load_json(harness.PERF, "configs", "lr-regpath-100.json")
TRAFFIC = harness.load_json(harness.PERF, "traffic", "path.json")
ARGS = ["--seed", "2147484101", "--seconds", "0.3", "--trace", "0"]
faults = harness.load_module("", "faults_fleet")
generator = harness.load_module("generators", "fleet_fit_loop")


def test_the_grid_is_glmnets_hundred_log_spaced_values():
    fleet, grid = CONFIG["stage"]["fleet"], CONFIG["stage"]["params"]["reg"]
    assert fleet["members"] == len(grid) == TRAFFIC["members"] == 100 and fleet["param"] == "reg"
    assert grid == [fleet["top"] * fleet["min_ratio"] ** (i / 99) for i in range(100)]
    assert grid[0] == 1.0 and grid[-1] == pytest.approx(1e-4) and all(a > b for a, b in zip(grid, grid[1:]))
    # the shipped hyperparameters, and nothing but the grid listed
    shared = {k: v for k, v in CONFIG["stage"]["params"].items() if k != "reg"}
    assert shared == {"learningRate": 0.1, "elasticNet": 0.0, "globalBatchSize": 100000, "tol": 1e-06}
    assert TRAFFIC["rows"] % 100000 == 0 and TRAFFIC["max_iter"] == 2 * TRAFFIC["rows"] // 100000


def test_fewer_members_take_values_along_the_whole_grid():
    params = CONFIG["stage"]["params"]
    assert generator.grid_of(params, 100) == ("reg", params["reg"])
    name, four = generator.grid_of(params, 4)
    assert name == "reg" and four == [params["reg"][i] for i in (0, 33, 66, 99)]
    with pytest.raises(ValueError):
        generator.grid_of(params, 101)
    with pytest.raises(ValueError):
        generator.grid_of({"reg": 0.1, "tol": 0.0}, 4)  # no grid at all


@pytest.mark.parametrize("members", [1, 4, 100])
def test_the_counters_bytes_do_not_grow_with_the_members_but_by_their_coefficients(members):
    counter = harness.load_module("counters", CONFIG["work"])
    params = dict(CONFIG["stage"]["params"], reg=[0.1] * members)
    counted = counter.fleet_lr_epoch(CONFIG["data"], params)
    batch, dim = 100_000, 100
    assert counted["bytes"] == batch * dim * 4 + 2 * batch * 4 + 2 * members * dim * 4
    assert counted["flops"] == 4 * batch * dim * members
    solo = harness.load_module("", "work").dense_lr_epoch(CONFIG["data"], params)
    assert counted["bytes"] - solo["bytes"] == 2 * (members - 1) * dim * 4  # the batch ONCE, whatever N
    assert counted["flops"] == members * solo["flops"]


def test_at_the_cells_size_the_hbm_peak_binds_and_the_flops_are_a_hundredfold():
    counter = harness.load_module("counters", CONFIG["work"])
    counted = counter.fleet_lr_epoch(CONFIG["data"], CONFIG["stage"]["params"])
    assert counted == {"bytes": 40_000_000 + 800_000 + 80_000, "flops": 4_000_000_000}
    least = harness.load_module("", "work").least_seconds(
        counted, harness.load_json(harness.PERF, "peaks.json")["TPU v5 lite"], 1
    )
    assert least["bound"] == "hbm" and least["seconds"] == pytest.approx(40_880_000 / 819e9)
    assert least["flops_seconds"] == pytest.approx(4e9 / 197e12)


def result_of(capsys, extra=()):
    code = harness.main(["--workload", CELL, "--rehearse-on-cpu", *ARGS, *extra])
    out, err = capsys.readouterr()
    assert code == 0
    return json.loads(out.strip().splitlines()[-1]), err


def plant(monkeypatch, fault=None, precision="float32"):
    """The reference where the program's `FitFleet` stands: the generator asks
    the program's module for the class when it builds the job."""
    import flink_ml_tpu.fleet as program

    reference = harness.load_module("reference", "lr-regpath-100")
    maker = harness.load_module("tables", CONFIG["data"]["table"])
    monkeypatch.setattr(program, "FitFleet", faults.planted(reference, maker, CONFIG["data"], fault, precision))


def test_the_rehearsal_runs_end_to_end_as_one_fleet_in_place(capsys):
    result, err = result_of(capsys, ["--trace", "1"])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) == {"coef_gap", "coef_max_gap", "failed"}
    metrics = result["metrics"]
    assert metrics["fleet_members_per_fit"] == {"value": 4.0, "unit": "count"}  # the rehearsal's four
    assert metrics["fleet_in_place_share"] == {"value": 100.0, "unit": "%"}
    assert metrics["host_syncs_per_fit"]["value"] == 1.0 and metrics["window_compiles"]["value"] == 0
    assert {
        "fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms", "fit_wait_ms", "fit_d2h_ms", "fit_host_self_ms",
        "host_gc_ms_per_s",
    } <= set(metrics)
    assert "epoch_roofline" not in metrics and "fit_mfu" not in metrics  # no device trace off the chip
    assert err.strip().splitlines()[-1] == "correct = True"


def rehearsal_context():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Context(bench, harness.named(bench["workloads"], CELL, "workload"), 2147484109, True)
    harness.devices_or_exit(ctx)
    return ctx


def test_a_row_is_counted_once_an_epoch_whatever_the_members():
    ctx = rehearsal_context()
    state = generator.setup(ctx)
    win = generator.window(ctx, state, 0.2)
    numbers = generator.check(ctx, state, win)
    rehearsal = TRAFFIC["rehearsal"]
    assert win["units"] == [rehearsal["max_iter"] * 100000] * len(win["ops"]) and win["ops"]
    assert all(np.shape(fit) == (rehearsal["members"], 100) for _, fit in win["answers"])
    assert numbers["coef_gap"] < 1e-5 and numbers["coef_max_gap"] < 1e-5


def test_the_sound_reference_in_the_fleets_place_is_correct(capsys, monkeypatch):
    plant(monkeypatch)
    result, _ = result_of(capsys, ["--trace", "1"])
    assert result["correct"] is True
    # a stand-in counts no fleet fit: the two readers find nothing to read
    assert "fleet_members_per_fit" not in result["metrics"] and "fleet_in_place_share" not in result["metrics"]


def test_the_control_in_bfloat16_is_not_correct(capsys, monkeypatch):
    plant(monkeypatch, precision="bfloat16")
    result, err = result_of(capsys)
    assert result["correct"] is False and "FAILED" in err


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_fleet_fit_is_not_correct(capsys, monkeypatch, fault):
    plant(monkeypatch, fault=fault)
    result, _ = result_of(capsys)
    assert result["correct"] is False
    assert not all(entry["ok"] for entry in result["compared"].values())


def test_a_member_is_held_to_its_own_norm(capsys):
    """One member out of place among members whose norms differ by orders:
    the comparison is member by member, so the small one is heard."""
    ctx = type("Ctx", (), {"compare": harness.load_module("", "compare")})
    want = np.array([[1e-3, 2e-3], [1.0, 2.0], [10.0, 20.0]])
    assert generator.compared(ctx, want, [want.copy()]) == {"coef_gap": 0.0, "coef_max_gap": 0.0}
    off = want.copy()
    off[0] *= 1.5  # 1e-3 of the whole matrix's norm, half of its own
    numbers = generator.compared(ctx, want, [want.copy(), off])
    assert numbers["coef_gap"] == pytest.approx(0.5) and numbers["coef_max_gap"] == pytest.approx(0.5)
    assert generator.compared(ctx, want, [want[:2]])["coef_gap"] == np.inf  # a member missing
    assert generator.compared(ctx, want, [])["coef_gap"] == np.inf


READERS = {name: harness.load_module("metrics", name).read for name in ("fleet_members_per_fit", "fleet_in_place_share")}


@pytest.mark.parametrize(
    "counters, members, share",
    [
        ({"fleet.fits": 3, "fleet.modelsTrained": 300, "fleet.in_place": 3}, 100.0, 100.0),  # the cell
        ({"fleet.fits": 3, "fleet.modelsTrained": 300}, 100.0, 0.0),  # the parent: every fleet laid out
        ({"fleet.fits": 4, "fleet.modelsTrained": 36, "fleet.in_place": 1, "layout.general": 6}, 9.0, 25.0),
        ({"iteration.host_sync": 4, "fit.outer.n": 4}, None, None),  # a window of solo fits
        ({}, None, None),
    ],
)
def test_readers_on_a_hand_made_run(counters, members, share):
    run = {"counters": counters, "window": {"attempted": 4}, "trace": None}
    assert READERS["fleet_members_per_fit"](run) == members
    assert READERS["fleet_in_place_share"](run) == share


def test_the_metrics_list_the_cell_and_the_host_account_gained_it():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = by_name[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "trained_rows_per_s"
        assert entry["source"] == "program_counter" and entry["better"] == "higher"
        assert entry["layer"] == "Training engine and dispatch pipeline"
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in (
        "fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms", "fit_wait_ms", "fit_d2h_ms", "fit_host_self_ms",
        "host_gc_ms_per_s",
    ):
        assert CELL in by_name[name]["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "path"
    names = [m["name"] for m in harness.wanted_metrics(bench, CELL, False)]
    assert names == ["trained_rows_per_s", "setup_s"]  # no fit_p95_ms: a window holds too few fits for a tail
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
