"""What the benchmark finds BY NAME in the program (read-only on `perf/`).

`perf/` measures the package from outside and knows three kinds of its names:
the XLA modules of the training programs (`train_programs` in
`perf/configs/*.json`; `perf/metrics/epoch_roofline.py` raises on the chip
when a trace holds none of them), the counters of `utils.metrics` that the
readers under `perf/metrics/` take from `run["counters"]`, and the phases
behind the `fit_*_ms` and `stream_*_ms` readers (`docs/observability.md`
"Fit phases", "Online phases"). A
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
BATCH, DIM, MAX_ITER, K = 4 * mesh_lib.LANES, mesh_lib.SUBLANES, 3, 4
ROWS = 4 * BATCH * mesh_lib.SUBLANES


@cache
def toy_fit(config_name: str, shards: int) -> dict:
    """One cold fit of the configuration's estimator on a toy table over
    `shards` CPU devices: the XLA modules jax lowered for it, by the names it
    gave them, and the counters the fit moved."""
    config = CONFIGS[config_name]
    module, _, cls = config["stage"]["class"].rpartition(".")
    stage = getattr(importlib.import_module(module), cls)()
    online = hasattr(stage, "set_initial_model_data")
    if not online:
        stage.set_max_iter(MAX_ITER)
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
    # float32 table is not: `lloyd.table_copy` is held by a tick, not an absence
    values = rng.random((ROWS, DIM)).astype(np.float16 if lloyd else np.float32)
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
    fits = [toy_fit(config, shards) for shards in (1, 4)]
    assert any(name in fit["lowered"] for fit in fits), (
        f"perf/configs/{config}.json names {name} under train_programs, and on the chip "
        f"perf/metrics/epoch_roofline.py raises when a trace holds none of them, but toy fits over "
        f"one and four shards ran {[fit['lowered'] for fit in fits]}: {FOLLOW}"
    )


@pytest.mark.parametrize("metric,counter", counters_read())
def test_counter_a_metric_reads_is_counted_by_a_fit(metric, counter):
    cells = METRICS.get(metric, {}).get("workloads") or sorted(CELLS)
    assert any(toy_fit_of_cell(cell)["counters"].get(counter, 0) > 0 for cell in cells), (
        f"perf/metrics/{metric}.py reads the counter {counter}, which a toy fit of none of {cells} moved: {FOLLOW}"
    )


@pytest.mark.parametrize("phase", documented_phases())
def test_documented_phase_is_emitted_once_a_fit(phase):
    """Once in every fit that goes through it (`fit.layout`: not on one
    shard), and in one cell's fit at least."""
    counted = {cell: toy_fit_of_cell(cell)["counters"].get(phase + ".n", 0) for cell in CELLS}
    assert set(counted.values()) in ({1}, {0, 1}), (
        f"docs/observability.md lists the phase {phase}; toy fits counted {counted} of it: {FOLLOW}"
    )


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


@pytest.mark.parametrize("cell", METRICS["fit_prelaunch_ms"]["workloads"])
def test_every_fit_has_an_extract_phase(cell):
    """The guard the three `fit_*_ms` readers share: they report nothing
    where not every fit of the window counted `fit.extract`. Held in the cells
    those readers list (a stream cell's fit has no such phases)."""
    assert METRICS["fit_launch_ms"]["workloads"] == METRICS["fit_finish_ms"]["workloads"] == METRICS["fit_prelaunch_ms"]["workloads"]
    counters = toy_fit_of_cell(cell)["counters"]
    assert counters.get("fit.extract.n") == counters.get("fit.total.n") == 1, cell
