"""The pipeline cell's own files at the rehearsal size, off the chip: the raw
table, the plain reference, the feature stages' counter, the generator's
comparison and the four readers."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import run as harness

CELL = "criteo-onehot-pipeline.day-partitions"
CONFIG = harness.load_json(harness.PERF, "configs", "criteo-onehot-pipeline.json")
TRAFFIC = harness.load_json(harness.PERF, "traffic", "day-partitions.json")
SMALL = dict(CONFIG["data"], **TRAFFIC["rehearsal"]["data"])
ROWS = 20_000


def made(seed, rows=ROWS, data=SMALL):
    maker = harness.load_module("tables", CONFIG["data"]["table"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return maker.make(jax.random.PRNGKey(seed), rows, data, mesh)


def test_the_configuration_is_the_published_one_hot_model():
    data = CONFIG["data"]
    hashed = harness.load_json(harness.PERF, "configs", "lr-sparse-1m.json")
    assert data["cardinalities"] == hashed["data"]["cardinalities"] and data["integer_fields"] == 13
    assert data["dim"] == 13 + sum(c - 1 for c in data["cardinalities"]) == 33_762_564
    assert data["nnz"] == 13 + 26 and len(data["count_sigmas"]) == 13
    assert CONFIG["stage"] == hashed["stage"]  # the trainer and its shipped hyperparameters
    encoder = CONFIG["pipeline"][1]["params"]
    assert CONFIG["pipeline"][2]["params"]["inputCols"] == ["snum", *encoder["outputCols"]]
    assert TRAFFIC["rows"] * TRAFFIC["partitions"] == 32_000_000 and TRAFFIC["max_iter"] == 20


def test_raw_columns_are_counts_indices_and_labels_with_the_whole_logs_dictionary():
    arrays = {name: np.asarray(a) for name, a in made(7).items()}
    cards = SMALL["cardinalities"]
    assert arrays["numeric"].shape == (ROWS, 13) and arrays["numeric"].dtype == np.float32
    counts = np.expm1(arrays["numeric"].astype(np.float64))
    assert np.allclose(counts, np.round(counts), atol=1e-3) and counts.min() >= 0  # log(1 + a whole count)
    assert (arrays["numeric"].std(axis=0) > 0).all()
    for f, card in enumerate(cards):
        column = arrays[f"C{f + 1}"]
        assert column.dtype == np.int32 and column.min() >= 0 and column.max() == card - 1
        assert column[f] == card - 1  # row f holds field f's last index
        if card >= 500:  # Zipf(1): the first rank takes ln 2 / ln(N + 1) of the rows
            share = np.bincount(column).max() / ROWS
            assert 0.5 * math.log(2) / math.log(card + 1) < share < 2 * math.log(2) / math.log(card + 1)
    assert set(np.unique(arrays["label"])) == {0.0, 1.0}
    again, other = made(7), made(8)
    assert all((np.asarray(again[name]) == arrays[name]).all() for name in arrays)
    assert (np.asarray(other["C3"]) != arrays["C3"]).any()


def test_the_table_maker_refuses_a_dimension_that_is_not_the_one_hot_models():
    with pytest.raises(ValueError, match="not dim"):
        made(1, data=dict(SMALL, dim=SMALL["dim"] + 1))
    with pytest.raises(ValueError, match="no bijection"):
        made(1, data=dict(SMALL, cardinalities=[103, *SMALL["cardinalities"][1:]], dim=SMALL["dim"] + 63))


def test_the_reference_is_the_four_stages_written_out():
    reference = harness.load_module("reference", "criteo-onehot-pipeline")
    arrays = made(3, rows=4_000)
    params = dict(CONFIG["stage"]["params"], globalBatchSize=1000, maxIter=3)
    coeff, epochs, loss, stats = reference.fit(arrays, SMALL, params)
    numeric = np.asarray(arrays["numeric"], np.float64)
    assert stats["sizes"] == tuple(SMALL["cardinalities"]) and epochs == 3 and 0.6 < loss < 0.75
    np.testing.assert_allclose(stats["mean"], numeric.mean(axis=0), rtol=1e-5)
    np.testing.assert_allclose(stats["std"], numeric.std(axis=0, ddof=1), rtol=1e-5)
    assert coeff.shape == (SMALL["dim"],)
    # the assembled rows, and three epochs over them in numpy
    ids, values, dim = reference.assembled(
        arrays["numeric"], tuple(arrays[f"C{f + 1}"] for f in range(26)), stats["std"], stats["sizes"], "float32"
    )
    ids, values = np.asarray(ids), np.asarray(values, np.float64)
    assert dim == SMALL["dim"] and ids.shape == (4_000, 39)
    assert (ids[:, :13] == np.arange(13)).all()
    first = 13 + int(np.cumsum([0, *[c - 1 for c in SMALL["cardinalities"]]])[2])
    column = np.asarray(arrays["C3"])
    dropped = column == SMALL["cardinalities"][2] - 1
    assert (ids[~dropped, 15] == first + column[~dropped]).all() and (ids[dropped, 15] == -1).all()
    assert (values[:, 13:] == (ids[:, 13:] >= 0)).all()
    w, label = np.zeros(dim), np.asarray(arrays["label"], np.float64)
    for e in range(3):
        rows = slice(e * 1000, (e + 1) * 1000)
        idx, val, sign = np.where(ids[rows] >= 0, ids[rows], 0), values[rows], 2 * label[rows] - 1
        mult = -sign / (1 + np.exp((val * w[idx]).sum(axis=1) * sign))
        grad = np.zeros(dim)
        np.add.at(grad, idx, val * mult[:, None])
        w -= 0.1 / 1000 * grad
    np.testing.assert_allclose(np.asarray(coeff), w, rtol=1e-4, atol=1e-9)
    # the control rounds its operands: another model, by more than the cell's limits allow
    control = reference.fit(arrays, SMALL, params, precision="bfloat16")[0]
    assert np.linalg.norm(np.asarray(control) - w) / np.linalg.norm(w) > TRAFFIC["limits"]["coef_gap"]


def test_the_counter_is_the_raw_columns_once_and_the_assembled_rows_once():
    counter = harness.load_module("counters", "pipeline_prep").pipeline_prep
    counted = counter(CONFIG["data"], 2_000_000)
    assert counted["bytes"] == 2_000_000 * (13 * 4 + 26 * 4 + 39 * 8) == 936_000_000
    assert counted["flops"] == 2_000_000 * 39 * 3


def run_with(counters=None, trace=None, window=None, config=CONFIG):
    return {"counters": counters or {}, "trace": trace, "window": window or {}, "config": config}


def test_the_readers_read_the_programs_counters_and_nothing_where_there_are_none():
    read = lambda name, run: harness.load_module("metrics", name).read(run)
    counters = {
        "pipeline.fit.n": 4, "pipeline.prep.ns": 48_000_000, "pipeline.prep.readback_bytes": 1356,
        "assembler.sparse_out": 4,
    }
    run = run_with(counters)
    assert read("pipeline_prep_ms", run) == pytest.approx(12.0)
    assert read("prep_readback_bytes_per_fit", run) == pytest.approx(339.0)
    assert read("assembled_sparse_share", run) == 100.0
    assert read("assembled_sparse_share", run_with({"assembler.sparse_out": 1, "assembler.dense_out": 3})) == 25.0
    for name in ("pipeline_prep_ms", "prep_readback_bytes_per_fit", "assembled_sparse_share", "prep_roofline"):
        assert read(name, run_with()) is None  # an older program counts none of this


def test_prep_roofline_is_the_least_time_over_the_prep_programs_device_time():
    read = harness.load_module("metrics", "prep_roofline").read
    trace = {"spans": 5, "modules_s": {"jit__pipeline_prep": 0.020, "jit__fit_stats": 0.003, "jit__fit_sizes": 0.002, "jit__sgd_train_flat": 2.0}}
    least = 936_000_000 / 819e9
    assert read(run_with(trace=trace, window={"prep_least_s_a_fit": least})) == pytest.approx(5 * least / 0.025 * 100)
    assert read(run_with(trace=trace, window={"prep_least_s_a_fit": None})) is None  # off the chip
    assert read(run_with(trace={"spans": 5, "modules_s": {"jit__sgd_train_flat": 2.0}}, window={"prep_least_s_a_fit": least})) is None
    assert read(run_with(trace=trace, window={"prep_least_s_a_fit": least}, config={"train_programs": []})) is None


def result_of(capsys, extra=()):
    code = harness.main(["--workload", CELL, "--rehearse-on-cpu", "--seed", "3000000019", "--seconds", "0.5", *extra])
    out, err = capsys.readouterr()
    assert code == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_a_traced_rehearsal_reports_the_pipelines_counters(capsys):
    result, _ = result_of(capsys, ["--trace", "1"])
    metrics = result["metrics"]
    assert result["correct"] is True and set(result["compared"]) >= {"sizes_gap", "scaler_gap", "coef_gap", "coef_max_gap"}
    assert metrics["assembled_sparse_share"]["value"] == 100.0
    assert metrics["prep_readback_bytes_per_fit"]["value"] < 4096
    assert metrics["pipeline_prep_ms"]["value"] > 0
    assert metrics["host_syncs_per_fit"]["value"] == 4  # five on a TPU, where the trainer plans its columns
    assert metrics["window_compiles"]["value"] == 0
    assert "prep_roofline" not in metrics  # no device trace off the chip


def test_a_wrong_size_or_a_wrong_deviation_is_not_correct(capsys, monkeypatch):
    generator = harness.load_module("generators", "pipeline_fit_loop")
    reference = harness.load_module("reference", "criteo-onehot-pipeline")
    arrays = made(5, rows=4_000)
    params = dict(CONFIG["stage"]["params"], globalBatchSize=1000, maxIter=2)
    coeff, _, _, stats = reference.fit(arrays, SMALL, params)
    sound = {
        "mean": np.asarray(stats["mean"]), "std": np.asarray(stats["std"]), "sizes": stats["sizes"],
        "coefficient": np.asarray(coeff),
    }
    compare = harness.load_module("", "compare")
    limits = TRAFFIC["limits"]
    assert compare.verdict(generator.gaps(sound, coeff, stats), limits)[0]
    population = dict(sound, std=sound["std"] * math.sqrt(3_999 / 4_000))  # n in the place of n - 1
    sized = dict(sound, sizes=(sound["sizes"][0] + 1, *sound["sizes"][1:]))
    altered = dict(sound, coefficient=np.where(np.arange(len(sound["coefficient"])) == 20, -sound["coefficient"], sound["coefficient"]))
    for answer, number in ((population, "scaler_gap"), (sized, "sizes_gap"), (altered, "coef_max_gap")):
        correct, compared = compare.verdict(generator.gaps(answer, coeff, stats), limits)
        assert not correct and not compared[number]["ok"], number


def test_a_program_that_densifies_is_refused_before_a_table_is_made(monkeypatch):
    from flink_ml_tpu.models.feature import vectorassembler

    generator = harness.load_module("generators", "pipeline_fit_loop")
    generator.refuse_a_program_that_densifies()
    monkeypatch.setattr(vectorassembler, "SPARSE_RATIO", 1e9)  # an assembler that never assembles sparse
    with pytest.raises(RuntimeError, match="densifies"):
        generator.refuse_a_program_that_densifies()
