"""What the benchmark finds BY NAME in the program (read-only on `perf/`).

`perf/` measures the package from outside and knows three kinds of its names:
the XLA modules of the training programs (`train_programs` in
`perf/configs/*.json`; `perf/metrics/epoch_roofline.py` raises on the chip
when a trace holds none of them), the counters of `utils.metrics` that the
readers under `perf/metrics/` take from `run["counters"]`, and the phases
behind the `fit_*_ms`, `stream_*_ms` and `pipeline_prep_ms` readers
(`docs/observability.md` "Fit phases", "Online phases", "Pipeline phases"). A
rename in the package is found here, on the CPU, before it costs a chip run:
the name stays, or it changes in a `benchmark` PR together with the file
under `perf/` that reads it. The cases are collected from the benchmark's own
files, so a new configuration or metric adds its case by existing.
"""

import ast
import importlib
import json
import re
from functools import cache
from pathlib import Path

import numpy as np
import pytest

import jax

from flink_ml_tpu import Table
from flink_ml_tpu.linalg import DenseVector
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch, StreamTable
from flink_ml_tpu.utils import metrics

ROOT = Path(__file__).resolve().parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in BENCH["configs"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
METRICS = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
FOLLOW = "keep the name, or change it in a `benchmark` PR together with the file under perf/ that reads it"

# the smallest table the batch exchange admits over four shards
# (tests/test_layout_exchange.py): a shard's piece of a batch is one tile's
# lanes, a width is whole sublanes, a shard holds one slab of batches
# epochs of a toy fit: under a pass of the table's 32 batches, and enough rows
# (9 x 512) for a sparse fit's plan, which reads the rows its epochs reach, to
# find more ids in a spread column than a dictionary holds (4,096)
BATCH, DIM, MAX_ITER, K = 4 * mesh_lib.LANES, mesh_lib.SUBLANES, 9, 4
ROWS = 4 * BATCH * mesh_lib.SUBLANES
# a fit that reads a batch twice: on several shards it lays its table out and
# trains data-parallel, where one of at most a pass walks (every cell's does)
SEVERAL_PASSES = ROWS // BATCH + 1


@cache
def toy_fit(config_name: str, shards: int, max_iter: int = MAX_ITER) -> dict:
    """One cold fit of the configuration's estimator on a toy table over
    `shards` CPU devices: the XLA modules jax lowered for it, by the names it
    gave them, and the counters the fit moved."""
    config = CONFIGS[config_name]
    module, _, cls = config["stage"]["class"].rpartition(".")
    stage = getattr(importlib.import_module(module), cls)()
    online = hasattr(stage, "set_initial_model_data")
    if not online:
        stage.set_max_iter(max_iter)
    lloyd = hasattr(stage, "set_k")
    if lloyd:
        stage.set_k(K)
    else:
        stage.set_global_batch_size(BATCH)
    mesh = mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:shards])
    rng = np.random.default_rng(29)

    def by_rows(arr):
        return jax.device_put(arr, mesh_lib.data_sharding(mesh, arr.ndim))

    # a float16 table is copied once for the Lloyd loop, which the cell's
    # float32 table is not: `lloyd.table_copy` is held by a tick, not an absence;
    # its values are pixel bytes as the cell's are, so that the fit's look
    # finds them exact in bfloat16 and takes the short cross term
    values = rng.random((ROWS, DIM)).astype(np.float32)
    if lloyd:
        values = rng.integers(0, 256, (ROWS, DIM)).astype(np.float16)
    if "nnz" in config["data"]:
        indices = np.sort(rng.integers(0, 40, (ROWS, DIM)).astype(np.int32), axis=1)
        size = 40
        if not online:
            # rows written field by field, a column of every class of
            # `ops/sparse_epoch.py`'s plan: one id, a few, and ids spread
            # over a dimension wider than any dictionary
            size = 1 << 16
            indices[:, 0], indices[:, 1] = 0, 1 + indices[:, 1] % 3
            indices[:, 2:] = rng.integers(4, size, (ROWS, DIM - 2))
        features = SparseBatch(size, by_rows(indices), by_rows(values))
    else:
        features = by_rows(values)
    label = by_rows((values.sum(axis=1) > DIM / 2).astype(np.float32))
    table = Table({"features": features, "label": label})
    fleet = config["stage"].get("fleet")
    if fleet:
        # a fleet configuration: the job is a `FitFleet` of the estimator at
        # the first few values of the grid, every other hyperparameter shared
        from flink_ml_tpu.fleet import FitFleet

        setter = "set_" + fleet["param"]
        grid = config["stage"]["params"][fleet["param"]][:K]
        stage = FitFleet(
            [getattr(stage_class(config_name)().set_max_iter(max_iter).set_global_batch_size(BATCH), setter)(v) for v in grid]
        )
    if "pipeline" in config:
        # a pipeline configuration: its feature stages in front of the
        # estimator, over raw columns of their names (a numeric matrix, index
        # columns of a few categories each, the last index in every one)
        stage, table = pipeline_and_raw_table(config, stage, by_rows, rng, label)
    if online:
        # a stream configuration: the table's rows as a stream of toy sparse
        # batches, one of the global size and one cut and joined, folded to its end
        cuts = [0, BATCH, BATCH + BATCH // 2, 3 * BATCH]
        stream = StreamTable.from_batches(
            [
                Table({"features": SparseBatch(40, indices[a:b], values[a:b]), "label": np.asarray(label)[a:b]})
                for a, b in zip(cuts, cuts[1:])
            ]
        )
        stage.set_initial_model_data(Table({"coefficient": [DenseVector(np.zeros(40))]}))
    lowered = []

    def on_lowering(event, duration, fun_name=None, **_):
        # jax says `jit(<function>)` here and names the XLA module `jit_<function>`
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name.replace("(", "_").rstrip(")"))

    with pytest.MonkeyPatch.context() as patch, mesh_lib.use_mesh(mesh):
        # the CPU keeps a table's rows major and turns the exchange away: say
        # of every table what the TPU says of a narrow one
        patch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
        # ... and is no TPU, where the dense epoch keeps the reduce form: say
        # that too, and the one-shard fit takes the kernel, interpreted
        patch.setattr(mesh_lib, "on_tpu", lambda arr: True)
        # another test of this process may have run the same program at the
        # same shapes: the cold fit is this test's own
        jax.clear_caches()
        jax.monitoring.register_event_duration_secs_listener(on_lowering)
        before = metrics.snapshot()
        try:
            if online:
                stage.fit(stream).process_updates()
            else:
                stage.fit(table)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_lowering)
        counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    return {"lowered": lowered, "counters": counters}


def perf_module(kind: str, name: str):
    """The benchmark's file perf/<kind>/<name>.py as a module, as `perf/run.py` loads it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(f"perf_{kind}_{name}", ROOT / "perf" / kind / (name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def pipeline_and_raw_table(config: dict, estimator, by_rows, rng, label):
    """The configuration's feature stages, built as the benchmark's generator
    builds them, in front of `estimator`, and a toy raw table of their columns."""
    from flink_ml_tpu import Pipeline

    generator = perf_module("generators", "pipeline_fit_loop")
    stages, columns = [generator.make_stage(spec) for spec in config["pipeline"]], {"label": label}
    scaler, encoder = stages[0], stages[1]
    columns[scaler.get_input_col()] = by_rows(rng.random((ROWS, config["data"]["integer_fields"])).astype(np.float32))
    for f, name in enumerate(encoder.get_input_cols()):
        column = rng.integers(0, 3 + f, ROWS).astype(np.int32)
        column[f] = 2 + f
        columns[name] = by_rows(column)
    return Pipeline([*stages, estimator]), Table(columns)


def toy_fit_of_cell(cell_name: str) -> dict:
    cell = CELLS[cell_name]
    return toy_fit(cell["config"], 4 if cell["chips"] > 1 else 1)


def documented_phases(section: str = "Fit phases", family: str = "fit") -> list:
    text = (ROOT / "docs" / "observability.md").read_text()
    section = text.split("## " + section, 1)[1].split("\n## ", 1)[0]
    tables = [t for t in section.split("\n\n") if t.startswith("| phase |")]  # not the counters' tables
    return sorted(set(re.findall(rf"^\| `({family}\.[a-z]+)` \|", "\n".join(tables), flags=re.M)))


def stream_cells() -> list:
    """The cells whose configuration is an online estimator's."""
    return sorted(
        name for name, cell in CELLS.items()
        if CONFIGS[cell["config"]]["stage"]["class"].rpartition(".")[2].startswith("Online")
    )


def counters_read() -> list:
    """(metric, counter) for every dotted string constant of a reader under
    `perf/metrics/`, its docstring apart: the counters it takes from
    `run["counters"]`, a phase being read by its `.ns`."""
    pairs, phases = set(), documented_phases()
    for path in (ROOT / "perf" / "metrics").glob("*.py"):
        tree = ast.parse(path.read_text())
        doc = ast.get_docstring(tree, clean=False)
        for node in ast.walk(tree):
            name = node.value if isinstance(node, ast.Constant) and isinstance(node.value, str) else ""
            if name != doc and re.fullmatch(r"[a-z_]+(\.[a-z_]+)+", name):
                pairs.add((path.stem, name + ".ns" if name in phases else name))
    return sorted(pairs)


@pytest.mark.parametrize(
    "config,name", [(c["name"], p) for c in CONFIGS.values() for p in c["train_programs"]]
)
def test_train_program_is_launched_under_the_name_the_configuration_gives(config, name):
    fits = [toy_fit(config, 1), toy_fit(config, 4)]
    if not hasattr(stage_class(config)(), "set_initial_model_data"):
        fits.append(toy_fit(config, 4, SEVERAL_PASSES))
    assert any(name in fit["lowered"] for fit in fits), (
        f"perf/configs/{config}.json names {name} under train_programs, and on the chip "
        f"perf/metrics/epoch_roofline.py raises when a trace holds none of them, but toy fits over "
        f"one and four shards, and of several passes over four, ran {[fit['lowered'] for fit in fits]}: {FOLLOW}"
    )


def stage_class(config_name: str):
    module, _, cls = CONFIGS[config_name]["stage"]["class"].rpartition(".")
    return getattr(importlib.import_module(module), cls)


def dense_assembly() -> dict:
    """The counters a dense assembly moves: the side of `assembled_sparse_share`
    that its cell exists to show absent, so that no cell's toy fit can hold
    the name."""
    from flink_ml_tpu.models.feature.vectorassembler import VectorAssembler

    before = metrics.snapshot()
    VectorAssembler().set_input_cols("a", "b").set_output_col("o").transform(
        Table({"a": np.ones((4, 2), np.float32), "b": np.ones(4, np.float32)})
    )
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def lloyd_fit_of_general_floats() -> dict:
    """The counters a k-means fit of a table that is not exact in bfloat16
    moves (and every fit on the CPU): the side of `lloyd_short_product_share`
    that the cell's pixel table never takes."""
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    before = metrics.snapshot()
    table = Table({"features": np.random.default_rng(3).random((64, DIM)).astype(np.float32)})
    KMeans().set_k(K).set_max_iter(MAX_ITER).fit(table)
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def dense_fit_of_several_passes_over_four_shards() -> dict:
    """The counters of the dense fit that no cell runs since the walk: one
    that reads a batch twice lays its table out (the exchange for the table,
    the general form for the label) and trains by the reduce form."""
    return toy_fit("lr-dense-100", 4, SEVERAL_PASSES)["counters"]


OTHER_SIDE = {
    "assembler.dense_out": dense_assembly,
    "lloyd.product.full": lloyd_fit_of_general_floats,
    "layout.exchange": dense_fit_of_several_passes_over_four_shards,
    "layout.general": dense_fit_of_several_passes_over_four_shards,
    "dense_epoch.reduce": dense_fit_of_several_passes_over_four_shards,
}


def collection_inside_a_fit() -> dict:
    """The counters a pass of the cycle collector moves while a fit phase is
    open: a toy fit may or may not hold one."""
    import gc

    from flink_ml_tpu.obs import tracing

    before = metrics.snapshot()
    with tracing.phase("fit.total"):
        gc.collect()
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def fence_of_a_ready_state() -> dict:
    """The counters the online loop's fence moves where the state it waits
    for was whole before it asked: the device had run out of queued work. A
    toy stream's fence finds its state ready or not by the CPU's own timing."""
    import jax.numpy as jnp

    from flink_ml_tpu.parallel import iteration

    before = metrics.snapshot()
    iteration._wait_for({"w": jax.block_until_ready(jnp.zeros(4))})
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


# counters that no toy fit is sure to move, by what is sure to move them
ON_DEMAND = {"host.gc.ns": collection_inside_a_fit, "online.fence.dry": fence_of_a_ready_state}


@pytest.mark.parametrize("metric,counter", counters_read())
def test_counter_a_metric_reads_is_counted_by_a_fit(metric, counter):
    cells = METRICS.get(metric, {}).get("workloads") or sorted(CELLS)
    if counter in ON_DEMAND:
        assert ON_DEMAND[counter]()[counter] > 0, f"perf/metrics/{metric}.py reads the counter {counter}: {FOLLOW}"
        return
    if counter in OTHER_SIDE:
        assert not any(toy_fit_of_cell(cell)["counters"].get(counter, 0) for cell in cells)
        assert OTHER_SIDE[counter]()[counter] > 0, f"perf/metrics/{metric}.py reads the counter {counter}: {FOLLOW}"
        return
    assert any(toy_fit_of_cell(cell)["counters"].get(counter, 0) > 0 for cell in cells), (
        f"perf/metrics/{metric}.py reads the counter {counter}, which a toy fit of none of {cells} moved: {FOLLOW}"
    )


@pytest.mark.parametrize(
    "config,name", [(c["name"], p) for c in CONFIGS.values() for p in c.get("prep_programs", [])]
)
def test_prep_program_is_launched_under_the_name_the_configuration_gives(config, name):
    assert name in toy_fit(config, 1)["lowered"], (
        f"perf/configs/{config}.json names {name} under prep_programs, whose device time "
        f"perf/metrics/prep_roofline.py sums, but a toy fit ran {toy_fit(config, 1)['lowered']}: {FOLLOW}"
    )


@pytest.mark.parametrize("phase", documented_phases())
def test_documented_phase_is_emitted_once_a_fit(phase):
    """Once in every fit that goes through it (`fit.layout`: not on one
    shard and not in a walked fit, so in no cell's), and in one fit at least."""
    counted = {cell: toy_fit_of_cell(cell)["counters"].get(phase + ".n", 0) for cell in CELLS}
    counted["several passes over four shards"] = dense_fit_of_several_passes_over_four_shards().get(phase + ".n", 0)
    for cell in pipeline_cells():
        # `fit.total` is every estimator's, once a fitted stage: a pipeline's
        # fit counts one for itself and one for each estimator in front of
        # the trainer; the other phases are the trainer's alone
        if phase == "fit.total":
            counted[cell] -= 1 + estimators_of(CONFIGS[CELLS[cell]["config"]])
    assert set(counted.values()) in ({1}, {0, 1}), (
        f"docs/observability.md lists the phase {phase}; toy fits counted {counted} of it: {FOLLOW}"
    )


@pytest.mark.parametrize(
    "cell, ticks",
    [("lr-dense-100.pass", 1), ("lr-sparse-1m.partitions", 0), ("criteo-onehot-pipeline.day-partitions", 0)],
)
def test_the_one_shard_cells_start_goes_up_with_the_launch_where_it_is_on_the_host(cell, ticks):
    """`fit.stage.launch_inputs` (docs/observability.md "Fit phases"): a dense
    fit's start goes up with its launch; a sparse fit's zeros are made on the
    device and stay there."""
    counters = toy_fit_of_cell(cell)["counters"]
    assert counters["fit.launch.n"] == 1 and counters.get("fit.stage.launch_inputs", 0) == ticks
    fit_phases = (ROOT / "docs" / "observability.md").read_text().split("## Fit phases", 1)[1].split("\n## ", 1)[0]
    assert "`fit.stage.launch_inputs`" in fit_phases


def pipeline_cells() -> list:
    return sorted(name for name, cell in CELLS.items() if "pipeline" in CONFIGS[cell["config"]])


def estimators_of(config: dict) -> int:
    """The estimators among a pipeline configuration's feature stages."""
    from flink_ml_tpu.api import Estimator

    classes = [spec["class"].rpartition(".") for spec in config["pipeline"]]
    return sum(issubclass(getattr(importlib.import_module(m), c), Estimator) for m, _, c in classes)


def test_the_fleet_cells_toy_fit_is_one_fleet_trained_in_place():
    """`lr-regpath-100.path` is a `FitFleet` over a one-device table of whole
    batches: it copies no table, it is one fit with one readback whatever its
    members, its program is the one the configuration names, and the two
    readers the cell brought say so."""
    cell = "lr-regpath-100.path"
    fit, config = toy_fit_of_cell(cell), CONFIGS[CELLS[cell]["config"]]
    counters = fit["counters"]
    assert config["stage"]["fleet"]["members"] == len(config["stage"]["params"]["reg"]) == 100
    assert counters["fleet.fits"] == counters["fleet.in_place"] == 1 and counters["fleet.modelsTrained"] == K
    assert counters["fleet.examplesTrained"] == K * MAX_ITER * BATCH
    assert not any(name in counters for name in ("layout.exchange", "layout.general", "fit.layout.n"))
    assert counters["iteration.host_sync"] == counters["fit.outer.n"] == counters["fit.total.n"] == 1
    assert "jit__sgd_fleet_whole_fit_impl" in fit["lowered"] and not {"jit__sgd_train_flat", "jit__sgd_train"} & set(fit["lowered"])
    fit_phases = (ROOT / "docs" / "observability.md").read_text().split("## Fit phases", 1)[1].split("\n## ", 1)[0]
    assert "`fleet.in_place`" in fit_phases, FOLLOW
    run = {"counters": counters, "window": {"attempted": 1}, "trace": None}
    assert perf_module("metrics", "fleet_members_per_fit").read(run) == K
    assert perf_module("metrics", "fleet_in_place_share").read(run) == 100.0
    # on several shards the same job lays its table out, as it did
    assert not toy_fit(CELLS[cell]["config"], 4)["counters"].get("fleet.in_place")


def test_the_sparse_path_cells_toy_fit_is_one_fleet_in_place_in_the_row_form():
    """`lr-regpath-criteo-1m.resident-path` is a `FitFleet` over a one-device
    padded-CSR table of whole batches: it copies no table, plans its columns
    once, takes the member-row form (on the chip's word, as every toy fit
    here is told), syncs twice (the plan, the result), runs the program the
    configuration names, and the readers the cell lists say so."""
    cell = "lr-regpath-criteo-1m.resident-path"
    fit, config = toy_fit_of_cell(cell), CONFIGS[CELLS[cell]["config"]]
    counters = fit["counters"]
    assert config["stage"]["fleet"]["members"] == len(config["stage"]["params"]["reg"]) == 100
    assert counters["fleet.fits"] == counters["fleet.in_place"] == counters["fleet.product.rows"] == 1
    assert not counters.get("fleet.product.reduce") and not counters.get("fleet.product.matrix")
    assert counters["sparse_epoch.planned"] == 1 and counters["sync.plan.n"] == 1
    assert counters["sparse_epoch.entries"] == BATCH * DIM and 0 < counters["sparse_epoch.entries_gathered"] < BATCH * DIM
    assert not any(name in counters for name in ("layout.exchange", "layout.general", "fit.layout.n", "dense_epoch.reduce"))
    assert counters["iteration.host_sync"] == 2 and counters["fit.outer.n"] == counters["fit.total.n"] == 1
    assert config["train_programs"] == ["jit__sgd_fleet_rows_whole_fit_impl"]
    assert "jit__sgd_fleet_rows_whole_fit_impl" in fit["lowered"]
    assert not {"jit__sgd_fleet_whole_fit_impl", "jit__sgd_train_flat", "jit__sgd_train"} & set(fit["lowered"])
    fit_phases = (ROOT / "docs" / "observability.md").read_text().split("## Fit phases", 1)[1].split("\n## ", 1)[0]
    assert "`fleet.product.rows`" in fit_phases and "`fleet.in_place`" in fit_phases, FOLLOW
    run = {"counters": counters, "window": {"attempted": 1}, "trace": None}
    read = lambda metric: perf_module("metrics", metric).read(run)  # noqa: E731
    assert read("fleet_row_form_share") == read("fleet_in_place_share") == 100.0
    assert read("fleet_members_per_fit") == K and read("host_syncs_per_fit") == 2.0
    assert read("sparse_gather_share") == 100.0 * counters["sparse_epoch.entries_gathered"] / (BATCH * DIM)
    assert METRICS["fleet_row_form_share"]["workloads"] == [cell]
    # on several shards the same job lays its table out, in the row form, unplanned
    laid_out = toy_fit(CELLS[cell]["config"], 4)["counters"]
    assert not laid_out.get("fleet.in_place") and laid_out["fleet.product.rows"] == laid_out["sparse_epoch.general"] == 1


def test_the_sparse_path_counter_counts_the_batch_once_and_every_members_coefficient():
    counter = perf_module("counters", "fleet_sparse_lr_epoch")
    config = CONFIGS["lr-regpath-criteo-1m"]
    epoch = counter.fleet_sparse_lr_epoch(config["data"], config["stage"]["params"])
    assert epoch == {"bytes": 100_000 * 39 * 8 + 4 * 100_000 + 2 * 1_000_000 * 100 * 4, "flops": 4 * 100_000 * 39 * 100}


@pytest.mark.parametrize("phase", documented_phases("Pipeline phases", "pipeline"))
def test_documented_pipeline_phase_is_emitted_once_a_pipeline_fit(phase):
    assert pipeline_cells()
    for cell in CELLS:
        counted = toy_fit_of_cell(cell)["counters"].get(phase + ".n", 0)
        assert counted == (1 if cell in pipeline_cells() else 0), (
            f"docs/observability.md lists the pipeline phase {phase}; a toy fit of {cell} counted {counted}: {FOLLOW}"
        )


def test_a_toy_pipeline_fit_keeps_its_columns_on_the_device():
    for cell in pipeline_cells():
        counters = toy_fit_of_cell(cell)["counters"]
        fields = len(CONFIGS[CELLS[cell]["config"]]["data"]["cardinalities"])
        assert counters["onehot.fit.device"] == fields and not counters.get("onehot.fit.host")
        assert counters["assembler.sparse_out"] == 1
        assert counters["pipeline.prep.readback_bytes"] < 4096
        # moments, sizes, guards, the plan's counts, the packed result: whatever the fields
        assert counters["iteration.host_sync"] == 5


@pytest.mark.parametrize("phase", documented_phases("Online phases", "online"))
def test_documented_online_phase_is_emitted_once_a_batch(phase):
    """Once for every global batch the loop folds, and not for the wait that
    finds the stream at its end."""
    assert stream_cells()
    for cell in stream_cells():
        counters = toy_fit_of_cell(cell)["counters"]
        assert counters.get(phase + ".n") == counters["ftrl.batches"] == 3, (
            f"docs/observability.md lists the online phase {phase}; a toy stream of {cell} "
            f"counted {counters.get(phase + '.n')} of it over {counters['ftrl.batches']} batches: {FOLLOW}"
        )


def test_a_toy_stream_syncs_with_the_host_for_no_batch():
    for cell in stream_cells():
        counters = toy_fit_of_cell(cell)["counters"]
        assert counters["online.versions"] == 3
        assert not any(name.startswith(("iteration.host_sync", "readback.")) for name in counters), counters
        # its one wait is the fence's, through the funnel: a wait and no copy
        # (three batches, two in flight: the third fences the first)
        assert counters["sync.fence.n"] == counters["online.fence.n"] == 1
        assert 0 < counters["sync.fence.wait.ns"] <= counters["online.fence.ns"]
        assert not any(name.startswith("sync.") and not name.startswith("sync.fence.") for name in counters)
        assert counters.get("online.fence.dry", 0) <= 1


def test_the_four_chip_cells_toy_fit_walks_under_a_train_program_the_configuration_names():
    """`lr-dense-100.pass-x4` is one pass over a row-sharded table of whole
    batches: its fit walks, a leg is `jit__sgd_train_flat` (so
    `epoch_roofline` finds its program in a trace and does not raise), and
    the counters say so by the names the docs give them."""
    cell = "lr-dense-100.pass-x4"
    fit, config = toy_fit_of_cell(cell), CONFIGS[CELLS[cell]["config"]]
    counters = fit["counters"]
    assert counters["layout.walk"] == 1 and counters["layout.walk.legs"] == 4
    assert not any(name in counters for name in ("layout.exchange", "layout.general", "fit.layout.n"))
    assert not any(name.startswith("collective.") for name in counters)
    assert counters["iteration.host_sync"] == 1 and counters["fit.launch.n"] == 1
    assert "jit__sgd_train_flat" in fit["lowered"] and "jit__sgd_train_flat" in config["train_programs"]
    assert not {"jit__sgd_train", "jit__exchange_batches_impl", "jit__layout_batches_impl"} & set(fit["lowered"])
    fit_phases = (ROOT / "docs" / "observability.md").read_text().split("## Fit phases", 1)[1].split("\n## ", 1)[0]
    assert "`layout.walk`" in fit_phases and "`layout.walk.legs`" in fit_phases, FOLLOW
    # what the cell's readers make of it: a trace that holds the lowered
    # programs and no other, one fit of MAX_ITER epochs in a window of a second
    run = {
        "counters": counters,
        "config": config,
        "window": {"units": [MAX_ITER * BATCH], "attempted": 1, "begin": 0.0, "end": 1.0},
        "least_per_unit": {"seconds": 1e-9, "flops_seconds": 1e-12, "bound": "hbm"},
        "trace": {"modules_s": {name: 1e-3 for name in fit["lowered"]}, "window_s": 1.0},
    }
    read = lambda metric: perf_module("metrics", metric).read(run)  # noqa: E731
    assert read("dense_one_pass_share") == 100.0
    assert read("layout_exchange_share") is None  # nothing was laid out
    assert read("host_syncs_per_fit") == 1.0
    assert read("epoch_roofline") == pytest.approx(MAX_ITER * BATCH * 1e-9 / 1e-3 * 100.0)
    assert read("fit_mfu") == pytest.approx(MAX_ITER * BATCH * 1e-9 * 100.0)


FIT_CELLS = METRICS["fit_host_self_ms"]["workloads"]


@pytest.mark.parametrize("cell", FIT_CELLS)
def test_a_fits_wall_is_its_own_time_its_waits_and_its_copies(cell):
    """The identity of `fit_host_self_ms` + `fit_wait_ms` + `fit_d2h_ms`, in a
    toy fit of every fit cell: one outermost fit (a pipeline's holds its
    stages'), every blocking read of it through the funnel and inside it, and
    the three parts the fit's wall to the nanosecond."""
    assert METRICS["fit_wait_ms"]["workloads"] == METRICS["fit_d2h_ms"]["workloads"] == FIT_CELLS
    assert sorted(FIT_CELLS) == sorted(set(CELLS) - set(stream_cells()))
    counters = toy_fit_of_cell(cell)["counters"]
    assert counters["fit.outer.n"] == 1
    nested = 1 + estimators_of(CONFIGS[CELLS[cell]["config"]]) if cell in pipeline_cells() else 0
    assert counters["fit.total.n"] == 1 + nested
    if not nested:
        assert counters["fit.outer.ns"] == counters["fit.total.ns"]
    kinds = sorted({name.split(".")[1] for name in counters if name.startswith("sync.")})
    assert "fit" in kinds and "fence" not in kinds
    wait = sum(counters[f"sync.{kind}.wait.ns"] for kind in kinds)
    copy = sum(counters[f"sync.{kind}.copy.ns"] for kind in kinds)
    assert (counters["fit.sync.wait.ns"], counters["fit.sync.copy.ns"]) == (wait, copy)
    assert counters["fit.sync.bytes"] == counters["readback.bytes"] == sum(counters[f"sync.{kind}.bytes"] for kind in kinds)
    assert counters["iteration.host_sync"] == counters["readback.count"] == sum(counters[f"sync.{kind}.n"] for kind in kinds)
    own = counters["fit.outer.ns"] - wait - copy
    assert own > 0
    run = {"counters": counters, "window": {"begin": 0.0, "end": 1.0}}
    parts = [perf_module("metrics", name).read(run) for name in ("fit_host_self_ms", "fit_wait_ms", "fit_d2h_ms")]
    assert parts == [own / 1e6, wait / 1e6, copy / 1e6]
    assert round(sum(parts) * 1e6) == counters["fit.outer.ns"]
    assert perf_module("metrics", "host_gc_ms_per_s").read(run) == counters.get("host.gc.ns", 0) / 1e6


def test_a_toy_pipeline_fit_is_one_outermost_fit_of_four():
    """`fit.total` counts the pipeline and each of its estimators, `fit.outer`
    the pipeline alone, so the readers that divide by it report in the
    pipeline cell; its syncs are its stages' and its trainer's."""
    for cell in pipeline_cells():
        counters = toy_fit_of_cell(cell)["counters"]
        assert counters["fit.outer.n"] == 1 and counters["fit.total.n"] == 4
        assert counters["fit.outer.ns"] < counters["fit.total.ns"]
        assert counters["fit.outer.ns"] >= counters["pipeline.fit.ns"]
        # the scaler's moments, the encoder's sizes and the packed result, the guards, the plan's counts
        kinds = {"readback": 1, "fit": 2, "transform": 1, "plan": 1}
        assert {name.split(".")[1] for name in counters if name.startswith("sync.")} == set(kinds)
        assert {kind: counters[f"sync.{kind}.n"] for kind in kinds} == kinds


@pytest.mark.parametrize("cell", METRICS["fit_prelaunch_ms"]["workloads"])
def test_every_fit_has_an_extract_phase(cell):
    """The guard the three `fit_*_ms` readers share: they report nothing
    where not every fit of the window counted `fit.extract`. Held in the cells
    those readers list (a stream cell's fit has no such phases)."""
    assert METRICS["fit_launch_ms"]["workloads"] == METRICS["fit_finish_ms"]["workloads"] == METRICS["fit_prelaunch_ms"]["workloads"]
    counters = toy_fit_of_cell(cell)["counters"]
    assert counters.get("fit.extract.n") == counters.get("fit.total.n") == 1, cell
