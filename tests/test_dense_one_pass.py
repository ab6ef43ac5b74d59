"""The dense epoch that reads its batch once (`ops/dense_epoch.py`), run here
by its own code, interpreted, through the real entry points.

Pinned on the suite's CPU devices:

1. `dense_epoch.one_pass` gives `losses._dense(pointwise)`'s loss sum,
   gradient and weight sum for the same rows, to float32 rounding, for the
   three pointwise losses: batches that start on a tile, batches that start
   off the lanes (a batch size that is a multiple of 32 and not of 128),
   padding rows past n, a weight column, a width of 100 and one that is no
   multiple of 8, and the table's last tile, where the interpreter (as the
   chip may) leaves NaN past the table's end;
2. a whole fit through `LogisticRegression` / `LinearSVC` /
   `LinearRegression` on either form: the same coefficient to 1e-5, the same
   epochs and criteria, a stop by `tol` at the same epoch;
3. `optimizer._can_one_pass` turns away, one by one, everything the kernel
   is not written for, and an unpatched fit on the CPU keeps the reduce form
   (`dense_epoch.reduce`), so that the bit-parity contracts between solo,
   fleet, chunked and whole-fit programs stand as they are.

A CPU array is on no TPU and keeps its rows major, so `_can_one_pass` never
admits one here: the tests that need the kernel taken tell `mesh_lib.on_tpu`
and `mesh_lib.rows_minor` to say what a narrow table on the chip says, and
the kernel's call is interpreted because the array's true platform says so.
(Compiled for a described v5e at the benchmark's size: the last test of
tests/test_layout_exchange.py, beside the other compiles for the chip.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.ops import dense_epoch, losses, optimizer
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics

LOSSES = {loss.name: loss for loss in (losses.BINARY_LOGISTIC_LOSS, losses.HINGE_LOSS, losses.LEAST_SQUARE_LOSS)}
ESTIMATORS = {"binary_logistic": LogisticRegression, "hinge": LinearSVC, "least_square": LinearRegression}

# (batch, batches, width, epoch's batch k, rows short of whole batches, weight column)
CASES = {
    "batch_of_whole_lanes": (256, 8, 100, 3, 0, False),
    "first_batch": (256, 8, 100, 0, 0, False),
    # 96 = 3 * 32: batch k starts at lane 96 k mod 128 of its tile
    "batch_starts_off_the_lanes": (96, 22, 100, 5, 0, False),
    "batch_across_two_tiles": (96, 22, 100, 10, 0, False),  # rows 960 .. 1056 of tiles of 1024
    # 22 * 96 = 2112 rows: the table's third tile holds 64 of them
    "the_tables_last_tile": (96, 22, 100, 21, 0, False),
    "padding_rows_weigh_nothing": (96, 22, 100, 21, 40, False),
    "weight_column": (96, 22, 100, 13, 40, True),
    "width_no_multiple_of_8": (96, 22, 5, 21, 40, True),
}


def table(batch, batches, width, short, weighted, seed=30):
    """(X, y, w or None, n): `batches` whole batches of rows, the last `short`
    of them padding as `_stage_flat` pads (zeros)."""
    rng = np.random.default_rng(seed)
    rows, n = batch * batches, batch * batches - short
    X = rng.random((rows, width)).astype(np.float32)
    y = (rng.random(rows) > 0.5).astype(np.float32)
    w = rng.random(rows).astype(np.float32) if weighted else None
    for column in (X, y, w):
        if column is not None:
            column[n:] = 0
    return X, y, w, n


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("loss", LOSSES)
def test_one_read_gives_the_two_reductions_sums(loss, case):
    batch, batches, width, k, short, weighted = CASES[case]
    X, y, w, n = table(batch, batches, width, short, weighted)
    coeff = (np.random.default_rng(k).standard_normal(width) * 0.2).astype(np.float32)
    rows = np.arange(k * batch, (k + 1) * batch)
    weights = (w[rows] if weighted else np.ones(batch, np.float32)) * (rows < n)
    want = LOSSES[loss](X[rows], y[rows], weights.astype(np.float32), coeff)
    pointwise = LOSSES[loss].pointwise
    got = jax.jit(
        lambda Xt, y, w, coeff, start, n: dense_epoch.one_pass(pointwise, Xt, y, w, coeff, start, n, batch, True)
    )(jnp.asarray(X).T, y, w, coeff, jnp.int32(k * batch), jnp.int32(n))
    for name, a, b in zip(("loss sum", "gradient", "weight sum"), want, got):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(b)), name
        assert np.max(np.abs(a - b)) <= 1e-6 * np.max(np.abs(a)), name


@pytest.mark.parametrize(
    "width, batch, rows, tile, steps",
    [
        (100, 100_000, 20_000_000, 2048, 50),  # the benchmark's: 49 or 50 tiles cover a batch
        (100, 100_000, 1_000_000, 2048, 50),
        (100, 1_000_000, 20_000_000, 6144, 164),
        (8, 100_000, 20_000_000, 7168, 15),
        (100, 2048, 20_000_000, 1024, 2),  # whole tiles make a batch up: it starts on one
        (127, 10_000_000, 100_000_000, 8192, 1222),  # two blocks of 4 MB are held
        (100, 96, 2112, 1024, 2),
    ],
)
def test_tile_is_chosen_from_the_batch_the_width_and_the_table(width, batch, rows, tile, steps):
    assert dense_epoch.tile_rows(width, batch, rows) == tile
    assert tile % dense_epoch.GROUP == 0 and tile <= rows
    assert dense_epoch._grid_steps(batch, tile) == steps
    # every start a batch can have is covered
    for start in range(0, min(rows - batch, 40 * batch) + 1, batch):
        assert (start + batch - 1) // tile - start // tile + 1 <= steps


# --- through the estimators ------------------------------------------------------


@pytest.fixture
def narrow_table_on_the_chip(monkeypatch):
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)


def one_shard():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:1])


def counted(fit):
    before = metrics.snapshot()
    result = fit()
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    return result, {k: v for k, v in counters.items() if k.startswith("dense_epoch.")}


def separable(rows=22 * 96 - 40, width=10, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.random((rows, width)).astype(np.float32)
    y = (X.sum(axis=1) > width / 2).astype(np.float32)
    return jax.device_put(X), jax.device_put(y), jax.device_put(rng.random(rows).astype(np.float32))


def estimator_fit(loss, weighted=False):
    X, y, w = separable()
    columns = {"features": X, "label": y, **({"weight": w} if weighted else {})}
    stage = ESTIMATORS[loss]().set_max_iter(40).set_global_batch_size(96)
    if weighted:
        stage.set_weight_col("weight")
    with mesh_lib.use_mesh(one_shard()):
        return np.asarray(stage.fit(Table(columns)).coefficient)


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weight_column"])
@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_through_the_estimator_is_the_same_fit_on_either_form(loss, weighted, monkeypatch):
    reduce_form, ticked = counted(lambda: estimator_fit(loss, weighted))
    assert ticked == {"dense_epoch.reduce": 1}
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
    one_read, ticked = counted(lambda: estimator_fit(loss, weighted))
    assert ticked == {"dense_epoch.one_pass": 1}
    assert np.max(np.abs(one_read - reduce_form)) <= 1e-5 * np.max(np.abs(reduce_form))
    assert np.any(one_read != 0)


def sgd_fit(loss, tol=0.0, max_iter=30):
    X, y, _ = separable()
    sgd = SGD(max_iter=max_iter, learning_rate=0.1, global_batch_size=96, tol=tol)
    return sgd.optimize(np.zeros(X.shape[1]), X, y, None, LOSSES[loss], one_shard())


@pytest.mark.parametrize("loss", LOSSES)
def test_epochs_and_criteria_are_the_same_on_either_form(loss, monkeypatch):
    coeff, criteria, epochs = sgd_fit(loss)
    # a tol between an epoch's criteria and the least of the epochs before it
    # (a mini-batch's loss does not fall epoch by epoch) stops both forms there
    early = [sgd_fit(loss, max_iter=m)[1] for m in range(1, 13)]
    stop_at = max(m for m in range(2, 13) if early[m - 1] < 0.999 * min(early[: m - 1]))
    tol = (early[stop_at - 1] + min(early[: stop_at - 1])) / 2
    stopped = sgd_fit(loss, tol=tol)
    assert stopped[2] == stop_at > 2
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
    (coeff_1, criteria_1, epochs_1), ticked = counted(lambda: sgd_fit(loss))
    assert ticked == {"dense_epoch.one_pass": 1}
    assert epochs_1 == epochs == 30
    assert abs(criteria_1 - criteria) <= 1e-5 * abs(criteria)
    assert np.max(np.abs(coeff_1 - coeff)) <= 1e-5 * np.max(np.abs(coeff))
    stopped_1 = sgd_fit(loss, tol=tol)
    assert stopped_1[2] == stop_at and abs(stopped_1[1] - stopped[1]) <= 1e-5 * abs(stopped[1])


def test_the_flat_program_keeps_its_name_on_either_form(narrow_table_on_the_chip):
    """`perf/configs/lr-dense-100.json` finds the train program in a trace as
    `jit__sgd_train_flat`: the kernel is inside it, no program of its own."""
    lowered = []

    def on_lowering(event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(on_lowering)
    try:
        _, ticked = counted(lambda: estimator_fit("binary_logistic"))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_lowering)
    assert ticked == {"dense_epoch.one_pass": 1}
    assert "jit(_sgd_train_flat)" in lowered and not any("one_pass" in name for name in lowered)


# --- who takes it ----------------------------------------------------------------


def admitted_table():
    return jnp.zeros((2048, 16), jnp.float32)


def two_shards():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:2])


NO_POINTWISE = losses.LossFunc("no_pointwise", losses.BINARY_LOGISTIC_LOSS.fn)

TURNED_AWAY = {
    "two_shards": lambda: (admitted_table(), losses.BINARY_LOGISTIC_LOSS, two_shards()),
    "sparse_rows": lambda: (
        (jnp.zeros((2048, 4), jnp.int32), jnp.zeros((2048, 4), jnp.float32)),
        losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard(),
    ),
    "bfloat16": lambda: (admitted_table().astype(jnp.bfloat16), losses.BINARY_LOGISTIC_LOSS, one_shard()),
    "float64_on_the_host": lambda: (np.zeros((2048, 16)), losses.BINARY_LOGISTIC_LOSS, one_shard()),
    "a_loss_without_pointwise": lambda: (admitted_table(), NO_POINTWISE, one_shard()),
    "fewer_rows_than_a_columns_tile": lambda: (jnp.zeros((1023, 16), jnp.float32), losses.BINARY_LOGISTIC_LOSS, one_shard()),
}


def test_a_narrow_float32_table_on_one_shard_of_the_chip_is_admitted(narrow_table_on_the_chip):
    for loss in LOSSES.values():
        assert optimizer._can_one_pass(admitted_table(), loss, one_shard())


@pytest.mark.parametrize("name", TURNED_AWAY)
def test_everything_else_keeps_the_reduce_form(name, narrow_table_on_the_chip):
    assert not optimizer._can_one_pass(*TURNED_AWAY[name]())


def test_a_table_off_the_tpu_keeps_the_reduce_form(monkeypatch):
    assert not mesh_lib.on_tpu(admitted_table())
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
    assert not optimizer._can_one_pass(admitted_table(), losses.BINARY_LOGISTIC_LOSS, one_shard())


def test_a_table_kept_rows_major_keeps_the_reduce_form(monkeypatch):
    assert not mesh_lib.rows_minor(admitted_table())  # the CPU's layout, and a wide table's on the chip
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    assert not optimizer._can_one_pass(admitted_table(), losses.BINARY_LOGISTIC_LOSS, one_shard())


def test_an_unpatched_fit_on_the_cpu_ticks_the_reduce_form():
    """What `tests/test_fleet.py` and `TestWholeFitParity` stand on: on the
    CPU every dense fit is `dense_dot` / `dense_grad`, bit for bit as before."""
    _, ticked = counted(lambda: estimator_fit("binary_logistic"))
    assert ticked == {"dense_epoch.reduce": 1}


def test_a_sparse_fit_ticks_neither():
    from flink_ml_tpu.table import SparseBatch

    rng = np.random.default_rng(5)
    indices = np.sort(rng.integers(0, 40, (2048, 4)).astype(np.int32), axis=1)
    values = rng.random((2048, 4)).astype(np.float32)
    features = SparseBatch(40, jax.device_put(indices), jax.device_put(values))
    label = jax.device_put((values.sum(axis=1) > 2).astype(np.float32))
    stage = LogisticRegression().set_max_iter(3).set_global_batch_size(256)
    with mesh_lib.use_mesh(one_shard()):
        _, ticked = counted(lambda: stage.fit(Table({"features": features, "label": label})))
    assert ticked == {}


def test_the_kernel_language_keeps_its_bytecode_beside_the_compile_cache(tmp_path):
    """Where the installation writes no bytecode, every process compiles
    `jax.experimental.pallas` from source (over a second of a first fit); with
    a persistent compile cache configured its bytecode is kept there, and the
    interpreter's settings are as they were afterwards. A process of its own:
    this one has imported the language already."""
    import os
    import subprocess
    import sys

    script = (
        "import sys, jax\n"
        "from flink_ml_tpu.ops import dense_epoch\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "pl, pltpu = dense_epoch._kernel_language()\n"
        "assert pl.pallas_call and pltpu.PrefetchScalarGridSpec\n"
        "assert sys.dont_write_bytecode and sys.pycache_prefix is None\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1", PYTHONPATH=root)
    for cache in (str(tmp_path / "cache"), ""):  # with a compile cache configured, and with none
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        subprocess.run([sys.executable, "-c", script], env=env, check=True, cwd=str(tmp_path), timeout=300)
    kept = list(tmp_path.rglob("*.pyc"))
    assert kept and all(tmp_path / "cache" / "pyc" in p.parents for p in kept)
