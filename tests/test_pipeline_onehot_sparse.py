"""The one-hot pipeline on the device at small cardinalities: a sparse
assembly is the dense one un-densified, the encoder's device fit is its host
fit, `Pipeline.fit` agrees with the benchmark's plain reference, and the
number of host syncs does not grow with the number of encoded columns."""

import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from flink_ml_tpu import Pipeline, PipelineModel, Table, config
from flink_ml_tpu.api import KernelContext
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.feature.onehotencoder import OneHotEncoder
from flink_ml_tpu.models.feature.standardscaler import StandardScaler
from flink_ml_tpu.models.feature.vectorassembler import VectorAssembler, assembles_sparse
from flink_ml_tpu.table import SparseBatch, register_device_pytrees
from flink_ml_tpu.utils import metrics

ROOT = Path(__file__).resolve().parent.parent
CARDS = [3, 7, 50, 11, 5, 40, 9, 64]
ROWS, BATCH = 512, 128


def raw_columns(fields: int, seed: int = 5, rows: int = ROWS) -> dict:
    """13 numeric values a row and `fields` index columns; row f holds field
    f's last index, which `dropLast` turns into an empty slot."""
    rng = np.random.default_rng(seed)
    cols = {
        "numeric": np.log1p(np.floor(np.exp(rng.normal(size=(rows, 13))))).astype(np.float32),
        "label": rng.integers(0, 2, rows).astype(np.float32),
    }
    for f in range(fields):
        column = rng.integers(0, CARDS[f], rows).astype(np.int32)
        column[f] = CARDS[f] - 1
        cols[f"C{f + 1}"] = column
    return cols


def on_device(cols: dict) -> Table:
    return Table({name: jax.device_put(col) for name, col in cols.items()})


def one_hot_pipeline(fields: int, max_iter: int = 4) -> Pipeline:
    ins, outs = [f"C{f + 1}" for f in range(fields)], [f"O{f + 1}" for f in range(fields)]
    return Pipeline(
        [
            StandardScaler().set_input_col("numeric").set_output_col("snum"),
            OneHotEncoder().set_input_cols(*ins).set_output_cols(*outs),
            VectorAssembler().set_input_cols("snum", *outs).set_output_col("features"),
            LogisticRegression().set_max_iter(max_iter).set_global_batch_size(BATCH),
        ]
    )


def densified(col) -> np.ndarray:
    if isinstance(col, SparseBatch):
        return SparseBatch(col.size, np.asarray(col.indices), np.asarray(col.values)).to_dense()
    return np.asarray(col, np.float64)


def assembly_inputs(nan: bool = False):
    """A dense matrix, two one-hot columns with a dropped last category (an
    empty slot) and a scalar column, with the dense assembly of the same rows."""
    rng = np.random.default_rng(11)
    a = rng.random((6, 2)).astype(np.float32)
    if nan:
        a[2, 1] = np.nan
    wide = SparseBatch(40, np.asarray([[3], [-1], [39], [0], [-1], [17]], np.int32),
                       np.asarray([[1], [0], [1], [1], [0], [1]], np.float32))
    small = SparseBatch(3, np.asarray([[1], [0], [-1], [2], [2], [-1]], np.int32),
                        np.asarray([[1], [1], [0], [1], [1], [0]], np.float32))
    c = rng.random(6).astype(np.float32)
    cols = {"a": a, "wide": wide, "small": small, "c": c}
    dense = np.hstack([a, wide.to_dense(), small.to_dense(), c[:, None]])
    return cols, dense


def device_cols(cols: dict) -> dict:
    return {
        name: SparseBatch(col.size, jax.device_put(col.indices), jax.device_put(col.values.astype(np.float32)))
        if isinstance(col, SparseBatch) else jax.device_put(col)
        for name, col in cols.items()
    }


def through_transform(stage, cols):
    return stage.transform(Table(cols))[0].column("o")


def through_kernel(stage, cols):
    register_device_pytrees()
    ctx = KernelContext()
    out = jax.jit(lambda cols: stage.transform_kernel({}, dict(cols), ctx)["o"])(cols)
    return out


@pytest.mark.parametrize(
    "through,where",
    [(through_transform, "host"), (through_transform, "device"), (through_kernel, "device")],
    ids=["transform-host", "transform-device", "transform_kernel"],
)
@pytest.mark.parametrize(
    "inputs,sparse",
    [
        (("a", "wide", "small", "c"), True),  # 5 stored entries of 46: sparse by the reference's rule
        (("wide",), True),
        (("a", "small", "c"), False),  # 4 stored entries of 6: 4 * 1.5 is no less than 6, dense
        (("a", "c"), False),  # no sparse input, no sparse output
    ],
)
def test_an_assembly_is_sparse_by_the_reference_rule_and_equals_the_dense_one(inputs, sparse, through, where):
    cols, _ = assembly_inputs()
    cols = {name: cols[name] for name in inputs}
    expected = np.hstack([densified(cols[name]).reshape(6, -1) for name in inputs])
    stage = VectorAssembler().set_input_cols(*inputs).set_output_col("o")
    out = through(stage, device_cols(cols) if where == "device" else cols)
    assert isinstance(out, SparseBatch) == sparse == assembles_sparse(list(cols.values()))
    np.testing.assert_array_equal(densified(out), expected.astype(np.float32))
    if sparse:
        ids = np.asarray(out.indices)
        assert out.size == expected.shape[1] and ids.shape[1] == sum(
            cols[n].indices.shape[1] if isinstance(cols[n], SparseBatch) else densified(cols[n]).reshape(6, -1).shape[1]
            for n in inputs
        )
        # a dropped last category stays an empty slot, where the input had it
        assert (ids == -1).sum() == sum((cols[n].indices == -1).sum() for n in inputs if isinstance(cols[n], SparseBatch))


@pytest.mark.parametrize("where", ["host", "device"])
def test_a_sparse_assembly_handles_invalid_values_as_a_dense_one(where):
    cols, dense = assembly_inputs(nan=True)
    if where == "device":
        cols = device_cols(cols)
    stage = VectorAssembler().set_input_cols("a", "wide", "small", "c").set_output_col("o")
    with pytest.raises(ValueError, match="NaN"):
        stage.transform(Table(cols))
    kept = stage.set_handle_invalid("keep").transform(Table(cols))[0].column("o")
    assert isinstance(kept, SparseBatch)
    np.testing.assert_array_equal(densified(kept), dense.astype(np.float32))
    skipped = stage.set_handle_invalid("skip").transform(Table(cols))[0]
    assert skipped.num_rows == 5
    np.testing.assert_array_equal(densified(skipped.column("o")), np.delete(dense, 2, axis=0).astype(np.float32))


def test_a_fused_assembly_guards_against_nan_once():
    cols, _ = assembly_inputs(nan=True)
    model = PipelineModel([VectorAssembler().set_input_cols("a", "wide", "small", "c").set_output_col("o")])
    with pytest.raises(ValueError, match="NaN"):
        model.transform(Table(device_cols(cols)))


def test_input_sizes_hold_a_sparse_input_to_its_size():
    cols, _ = assembly_inputs()
    stage = VectorAssembler().set_input_cols("a", "wide").set_output_col("o")
    stage.set_input_sizes(2, 40).transform(Table(cols))
    with pytest.raises(ValueError, match="declared inputSizes"):
        stage.set_input_sizes(2, 39).transform(Table(cols))


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_the_encoder_fits_device_columns_as_it_fits_host_columns(dtype):
    cols = {name: col.astype(dtype) for name, col in raw_columns(4).items() if name.startswith("C")}
    names = sorted(cols)
    encoder = OneHotEncoder().set_input_cols(*names).set_output_cols(*[n.replace("C", "O") for n in names])
    before = metrics.snapshot()
    host = encoder.fit(Table(cols))
    device = encoder.fit(on_device(cols))
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert list(device.category_sizes) == list(host.category_sizes) == CARDS[:4]
    assert counters["onehot.fit.host"] == counters["onehot.fit.device"] == 4
    assert counters["iteration.host_sync"] == 1  # the device fit's one readback; the host fit has none
    encoded_host = host.transform(Table(cols))[0]
    encoded_device = device.transform(on_device(cols))[0]
    for name in encoder.get_output_cols():
        np.testing.assert_array_equal(densified(encoded_device.column(name)), densified(encoded_host.column(name)))


@pytest.mark.parametrize("bad", [-1.0, 2.5, np.nan])
def test_the_encoder_refuses_on_the_device_what_it_refuses_on_the_host(bad):
    cols = {"C1": raw_columns(1)["C1"].astype(np.float32), "C2": raw_columns(2)["C2"].astype(np.float32)}
    cols["C2"][100] = bad
    encoder = OneHotEncoder().set_input_cols("C1", "C2").set_output_cols("O1", "O2")
    for table in (Table(cols), on_device(cols)):
        with pytest.raises(ValueError, match="column C2"):
            encoder.fit(table)


def test_a_device_transform_reads_back_once_and_names_the_bad_column():
    cols = {name: col for name, col in raw_columns(3).items() if name.startswith("C")}
    names = sorted(cols)
    model = OneHotEncoder().set_input_cols(*names).set_output_cols("O1", "O2", "O3").fit(on_device(cols))
    before = metrics.snapshot()
    model.transform(on_device(cols))
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert counters["iteration.host_sync"] == counters["iteration.host_sync.transform"] == 1
    cols["C2"] = cols["C2"].copy()
    cols["C2"][7] = CARDS[1]  # one past the largest index the fit saw
    with pytest.raises(ValueError, match="column C2"):
        model.transform(on_device(cols))


def reference():
    path = ROOT / "perf" / "reference" / "criteo-onehot-pipeline.py"
    spec = importlib.util.spec_from_file_location("perf_reference_criteo_onehot_pipeline", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("where", ["host", "device"])
def test_a_pipeline_fit_agrees_with_the_plain_reference(where):
    fields = 6
    cols = raw_columns(fields, seed=23)
    model = one_hot_pipeline(fields).fit(on_device(cols) if where == "device" else Table(cols))
    data = {"cardinalities": CARDS[:fields]}
    params = {"learningRate": 0.1, "globalBatchSize": BATCH, "tol": 1e-6, "maxIter": 4}
    coeff, epochs, _, stats = reference().fit({k: jax.numpy.asarray(v) for k, v in cols.items()}, data, params)
    scaler, encoder, _, trained = model.stages
    assert tuple(encoder.category_sizes) == stats["sizes"] == tuple(CARDS[:fields])
    assert epochs == 4
    np.testing.assert_allclose(scaler.mean, stats["mean"], rtol=2e-6)
    np.testing.assert_allclose(scaler.std, stats["std"], rtol=2e-6)
    assert np.asarray(trained.coefficient).shape == (13 + sum(c - 1 for c in CARDS[:fields]),)
    np.testing.assert_allclose(trained.coefficient, coeff, rtol=2e-5, atol=1e-8)


def fit_counters(fields: int) -> dict:
    table = on_device(raw_columns(fields))
    one_hot_pipeline(fields).fit(table)  # every program of these shapes compiled
    before = metrics.snapshot()
    with jax.transfer_guard_device_to_host("disallow"):  # no column reaches the host but by an accounted readback
        one_hot_pipeline(fields).fit(table)
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def test_a_device_pipeline_fit_syncs_a_constant_number_of_times():
    few, many = fit_counters(3), fit_counters(8)
    # the scaler's moments, the encoder's sizes, the transforms' guards, the trainer's packed result
    assert few["iteration.host_sync"] == many["iteration.host_sync"] == 4
    for counters, fields in ((few, 3), (many, 8)):
        assert counters.get("onehot.fit.host", 0) == 0 and counters["onehot.fit.device"] == fields
        assert counters["assembler.sparse_out"] == 1 and counters.get("assembler.dense_out", 0) == 0
        assert counters["pipeline.fit.n"] == counters["pipeline.prep.n"] == 1
        assert counters["fit.total.n"] == 4  # the pipeline's own and one a fitted stage
        assert counters.get("jit.compiles", 0) == 0 and counters.get("jit.traces", 0) == 0
        assert 0 < counters["pipeline.prep.readback_bytes"] < 4096
        assert counters["pipeline.prep.ns"] < counters["pipeline.fit.ns"]


def test_a_pipeline_fit_lets_go_of_the_columns_no_later_stage_names():
    """The trainer is handed the assembled column and the label, not the 2 x
    fields one-hot columns or the scaled matrix that led to it."""
    seen = {}

    class Spy(LogisticRegression):
        def fit(self, *inputs):
            seen["columns"] = inputs[0].column_names
            return super().fit(*inputs)

    pipeline = one_hot_pipeline(3)
    pipeline.stages[-1] = Spy().set_max_iter(2).set_global_batch_size(BATCH)
    raw = raw_columns(3)
    pipeline.fit(on_device(raw))
    assert sorted(seen["columns"]) == sorted([*raw, "features"])


@pytest.mark.parametrize("fusion", ["auto", "off"])
def test_a_pipeline_fit_is_the_stage_by_stage_fit(fusion):
    """Fused or eager, transformed lazily or at once: the same models."""
    cols = raw_columns(4, seed=3)
    table = on_device(cols)
    with config.pipeline_fusion_mode(fusion):
        model = one_hot_pipeline(4).fit(table)
    stages, step = one_hot_pipeline(4).stages, table
    fitted = []
    for stage in stages[:-1]:
        fitted.append(stage.fit(step) if hasattr(stage, "fit") else stage)
        step = fitted[-1].transform(step)[0]
    fitted.append(stages[-1].fit(step))
    np.testing.assert_array_equal(model.stages[0].std, fitted[0].std)
    np.testing.assert_array_equal(model.stages[1].category_sizes, fitted[1].category_sizes)
    np.testing.assert_array_equal(np.asarray(model.stages[3].coefficient), np.asarray(fitted[3].coefficient))


def test_pipeline_fits_of_equal_stages_share_one_program_and_others_do_not():
    table = on_device(raw_columns(3))
    one_hot_pipeline(3).fit(table)
    before = metrics.snapshot()
    one_hot_pipeline(3).fit(table)
    assert metrics.snapshot_delta(before, metrics.snapshot())["counters"].get("jit.traces", 0) == 0
    # another category size is another program: the sizes are part of its trace
    other = raw_columns(3)
    other["C3"] = np.minimum(other["C3"], 30)
    before = metrics.snapshot()
    model = one_hot_pipeline(3).fit(on_device(other))
    assert metrics.snapshot_delta(before, metrics.snapshot())["counters"]["jit.traces"] >= 1
    assert list(model.stages[1].category_sizes) == [3, 7, 31]
    assert np.asarray(model.stages[3].coefficient).shape == (13 + 2 + 6 + 30,)


def test_the_benchmark_names_the_pipeline_this_file_fits():
    config_file = json.loads((ROOT / "perf" / "configs" / "criteo-onehot-pipeline.json").read_text())
    classes = [spec["class"].rpartition(".")[2] for spec in config_file["pipeline"]]
    assert classes == ["StandardScaler", "OneHotEncoder", "VectorAssembler"]
    assert config_file["data"]["dim"] == 13 + sum(c - 1 for c in config_file["data"]["cardinalities"])


def test_a_column_a_waiting_stage_reads_outlives_the_segment_that_wrote_it():
    """Scaler -> (an eager stage that reads the scaled matrix) -> trainer: the
    fused scaler's program must return what the stage behind it reads, though
    the trainer does not."""
    from flink_ml_tpu.models.feature.sqltransformer import SQLTransformer

    cols = raw_columns(2, seed=9)
    table = on_device({"numeric": cols["numeric"], "label": cols["label"]})
    staged = [
        StandardScaler().set_input_col("numeric").set_output_col("snum"),
        VectorAssembler().set_input_cols("snum").set_output_col("wide").set_handle_invalid("skip"),  # eager: 'skip'
        LogisticRegression().set_features_col("wide").set_max_iter(2).set_global_batch_size(BATCH),
    ]
    model = Pipeline(staged).fit(table)
    direct = LogisticRegression().set_features_col("snum").set_max_iter(2).set_global_batch_size(BATCH).fit(
        StandardScaler().set_input_col("numeric").set_output_col("snum").fit(table).transform(table)[0]
    )
    np.testing.assert_array_equal(np.asarray(model.stages[-1].coefficient), np.asarray(direct.coefficient))
    # a stage that names no column reads what it likes: nothing is dropped before it
    opaque = [
        StandardScaler().set_input_col("numeric").set_output_col("snum"),
        SQLTransformer().set_statement("SELECT *, label AS y FROM __THIS__"),
        LogisticRegression().set_features_col("snum").set_label_col("y").set_max_iter(2).set_global_batch_size(BATCH),
    ]
    np.testing.assert_array_equal(
        np.asarray(Pipeline(opaque).fit(table).stages[-1].coefficient), np.asarray(direct.coefficient)
    )
