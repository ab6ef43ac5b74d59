"""What the k-means configuration brought: the image table maker, the work
counter, the generator's faults through perf/run.py at the rehearsal size, and
the reader of the program's `lloyd.*` counters."""

import json

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import run as harness

CELL = "kmeans-mnist8m.refit"
CONFIG = harness.load_json(harness.PERF, "configs", "kmeans-mnist8m.json")
ARGS = ["--seed", "2147484007", "--seconds", "0.3", "--trace", "0"]
faults = harness.load_module("", "faults_lloyd")


def made(seed, rows=135 * 24):
    maker = harness.load_module("tables", CONFIG["data"]["table"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return np.asarray(maker.make(jax.random.PRNGKey(seed), rows, CONFIG["data"], mesh)["features"])


def test_pixels_are_whole_numbers_and_a_fifth_of_them_are_lit():
    rows = made(3)
    assert rows.shape == (135 * 24, 784) and rows.dtype == np.float32
    assert rows.min() == 0.0 and rows.max() == 255.0 and (rows == np.round(rows)).all()
    assert 0.15 < (rows > 0).mean() < 0.24
    assert ((rows > 0).mean(axis=1) > 0.05).all()  # no empty image


def test_the_same_seed_gives_the_same_table_and_another_seed_another():
    first, again, other = made(11), made(11), made(12)
    assert (first == again).all() and (first != other).any()


def test_rows_are_variants_of_their_base_and_nearer_to_it_than_to_another():
    rows, bases = made(5), 24
    assert CONFIG["data"]["variants"] == 135 and len(rows) == bases * 135
    by_base = rows.reshape(135, bases, 784)  # row r is variant r // bases of base r % bases
    distance = lambda a, b: np.sqrt(((a - b) ** 2).sum(-1))
    assert (by_base[0] != by_base[1]).any()  # a variant is not a copy
    within = distance(by_base[:60], by_base[60:120]).mean()
    across = distance(by_base[:60], np.roll(by_base[60:120], 1, axis=1)).mean()
    assert within < 0.75 * across


def test_a_table_that_is_not_whole_bases_or_not_on_one_device_is_refused():
    maker = harness.load_module("tables", CONFIG["data"]["table"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="whole number of bases"):
        maker.make(jax.random.PRNGKey(0), 1000, CONFIG["data"], mesh)
    with pytest.raises(ValueError, match="one device"):
        maker.make(jax.random.PRNGKey(0), 135 * 4, CONFIG["data"], Mesh(np.array(jax.devices()[:4]), ("data",)))


def test_lloyd_iteration_counts_the_table_once_and_the_cross_term():
    counter = harness.load_module("counters", CONFIG["work"])
    counted = getattr(counter, CONFIG["work"])(CONFIG["data"], CONFIG["stage"]["params"])
    n, d, k = 2_700_000, 784, 4096
    assert counted == {"bytes": n * d * 4 + 2 * k * d * 4, "flops": 2 * n * k * d + 3 * n * d}
    assert counted["bytes"] == 8_492_890_112 and counted["flops"] == 17_347_176_000_000
    work = harness.load_module("", "work")
    least = work.least_seconds(counted, harness.load_json(harness.PERF, "peaks.json")["TPU v5 lite"], 1)
    assert least["bound"] == "flops" and least["seconds"] == pytest.approx(0.0880567, rel=1e-5)


def test_the_unit_of_the_cell_is_the_tables_rows():
    traffic = harness.load_json(harness.PERF, "traffic", "refit.json")
    assert CONFIG["stage"]["params"]["globalBatchSize"] == traffic["rows"] == 2_700_000
    assert traffic["rows"] % CONFIG["data"]["variants"] == 0
    generator = harness.load_module("generators", traffic["generator"])
    params = generator.StageParams(CONFIG["stage"]["params"], 5400)
    assert "globalBatchSize" not in dict(params.items()) and params["globalBatchSize"] == 5400
    with pytest.raises(KeyError):
        params["learningRate"]


def result_of(capsys, extra=()):
    code = harness.main(["--workload", CELL, "--rehearse-on-cpu", *ARGS, *extra])
    out, err = capsys.readouterr()
    assert code == 0
    return json.loads(out.strip().splitlines()[-1]), err


def plant(monkeypatch, fault=None, precision="float32"):
    def make_stage(ctx, params):
        return faults.ReferenceStage(
            ctx.load("reference", ctx.cell["config"]),
            ctx.load("tables", ctx.config["data"]["table"]),
            ctx.config["data"], params, fault, precision,
        )

    monkeypatch.setattr(harness.Context, "make_stage", make_stage)


def test_the_rehearsal_is_correct_and_counts_iterations_times_rows(capsys):
    result, err = result_of(capsys, ["--trace", "1"])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) == {
        "centroid_gap", "count_gap", "step_centroid_gap", "step_centroid_max_gap", "step_count_gap", "failed",
    }
    metrics = result["metrics"]
    assert metrics["lloyd_table_copies_per_fit"] == {"value": 0.0, "unit": "count"}
    assert metrics["host_syncs_per_fit"]["value"] == 1.0 and metrics["window_compiles"]["value"] == 0
    assert {"fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"} <= set(metrics)
    assert err.strip().splitlines()[-1] == "correct = True"


def test_the_sound_reference_with_centroids_and_counts_is_correct(capsys, monkeypatch):
    plant(monkeypatch)
    result, _ = result_of(capsys)
    assert result["correct"] is True
    assert result["metrics"]["trained_rows_per_s"]["value"] > 0


def test_the_control_in_bfloat16_is_not_correct(capsys, monkeypatch):
    plant(monkeypatch, precision="bfloat16")
    result, err = result_of(capsys)
    assert result["correct"] is False and "FAILED" in err


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_lloyd_fit_is_not_correct(capsys, monkeypatch, fault):
    plant(monkeypatch, fault=fault)
    result, _ = result_of(capsys)
    assert result["correct"] is False
    assert not all(entry["ok"] for entry in result["compared"].values())


def test_one_altered_centroid_is_seen_by_the_largest_miss_and_not_by_the_counts(capsys, monkeypatch):
    plant(monkeypatch, fault="centroid_altered")
    compared = result_of(capsys)[0]["compared"]
    assert compared["count_gap"]["value"] == 0.0 and compared["count_gap"]["ok"]
    assert compared["step_centroid_max_gap"]["value"] > 0.4 and not compared["step_centroid_max_gap"]["ok"]


read = harness.load_module("metrics", "lloyd_table_copies_per_fit").read


@pytest.mark.parametrize(
    "counters, value",
    [
        ({"lloyd.iterations": 20, "lloyd.blocks": 8120}, 0.0),  # four fits in place
        ({"lloyd.iterations": 20, "lloyd.table_copy": 4}, 1.0),  # each padded, cast or re-sharded its table
        ({"lloyd.iterations": 20, "lloyd.table_copy": 2}, 0.5),
        ({"iteration.host_sync": 4}, None),  # a window of other stages' fits; a program that counts none of it
        ({}, None),
    ],
)
def test_table_copies_on_a_hand_made_run(counters, value):
    assert read({"counters": counters, "window": {"attempted": 4}, "trace": None}) == value


def test_the_metric_lists_the_cell_and_the_phase_metrics_gained_it():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry = by_name["lloyd_table_copies_per_fit"]
    assert entry["workloads"] == [CELL] and entry["moves"] == "trained_rows_per_s"
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    for name in ("fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"):
        assert by_name[name]["workloads"][-1] == CELL
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "refit"
    names = [m["name"] for m in harness.wanted_metrics(bench, CELL, False)]
    assert names == ["trained_rows_per_s", "setup_s"]  # no fit_p95_ms: a window holds two fits
