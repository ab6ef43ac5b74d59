"""The one-shard flat route's small inputs go up with its launch
(ops/optimizer.py: `SGD._stage_flat`, `_sgd_train_flat`).

Pinned here, on the suite's virtual devices:

1. a dense one-shard fit over a device table runs no device program and makes
   no device array while it stages (`fit.stage`, between `fit.extract` and
   `fit.launch`): its row count, its absent weight column's placeholder and its
   start coefficient are host values of the launch, and
   `fit.stage.launch_inputs` ticks once; a sparse fit, whose zeros are made on
   the device, keeps its start there and does not tick it;
2. a weighted fit and a ragged one (padded to whole batches) still train as
   the plain schedule does, and to the bit as with their start on the device;
3. `_sgd_train_flat` handed those three inputs as host values is the program
   handed them as device arrays: the same packed bits, the same HLO.
"""

import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import dispatch as jax_dispatch

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.ops import losses, optimizer
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch
from flink_ml_tpu.utils import metrics

BATCH, DIM, BATCHES = 256, 8, 8
ROWS = BATCH * BATCHES
ESTIMATORS = {"logistic": LogisticRegression, "hinge": LinearSVC, "least_square": LinearRegression}


def one_shard():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:1])


def host_table(rows=ROWS, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, DIM)).astype(np.float32)
    y = (X @ rng.normal(size=DIM) > 0).astype(np.float32)
    return X, y, (rng.random(rows) + 0.5).astype(np.float32)


def on_device(*columns):
    sharding = mesh_lib.data_sharding(one_shard(), 1)
    return [None if c is None else jax.device_put(c, sharding) for c in columns]


@pytest.fixture
def staged(monkeypatch):
    """What each `SGD._stage_async` (the `fit.stage` phase) did on the device:
    a list of (eager primitives run, device arrays made) a fit."""
    record, primitives = [], []
    original_callable = jax_dispatch.xla_primitive_callable

    def primitive_callable(prim, **params):
        primitives.append(prim.name)
        return original_callable(prim, **params)

    monkeypatch.setattr(jax_dispatch, "xla_primitive_callable", primitive_callable)
    original_stage = SGD._stage_async

    def stage(self, *args, **kwargs):
        live, ran = {id(a) for a in jax.live_arrays()}, len(primitives)
        launch = original_stage(self, *args, **kwargs)
        made = [(a.shape, str(a.dtype)) for a in jax.live_arrays() if id(a) not in live]
        record.append((primitives[ran:], made))
        return launch

    monkeypatch.setattr(SGD, "_stage_async", stage)
    return record


def counted(fit):
    before = metrics.snapshot()
    result = fit()
    return result, metrics.snapshot_delta(before, metrics.snapshot())["counters"]


# --- 1. the stage runs nothing on the device --------------------------------------


@pytest.mark.parametrize("estimator", list(ESTIMATORS))
def test_a_dense_one_shard_fit_stages_nothing_on_the_device(estimator, staged):
    X, y, _ = host_table()
    X, y = on_device(X, y)
    stage = ESTIMATORS[estimator]().set_global_batch_size(BATCH).set_max_iter(BATCHES)
    with mesh_lib.use_mesh(one_shard()):
        first, cold = counted(lambda: np.asarray(stage.fit(Table({"features": X, "label": y})).coefficient))
        second, warm = counted(lambda: np.asarray(stage.fit(Table({"features": X, "label": y})).coefficient))
    assert staged == [([], []), ([], [])]  # no eager program, no device array, in either fit
    for counters in (cold, warm):
        assert counters["fit.stage.launch_inputs"] == counters["fit.stage.n"] == counters["fit.launch.n"] == 1
    # one lowering serves every fit: the warm-up's
    assert not warm.get("jit.traces") and not warm.get("jit.compiles")
    np.testing.assert_array_equal(first, second)


def test_a_sparse_fit_keeps_its_start_on_the_device_and_does_not_tick(monkeypatch):
    rng = np.random.default_rng(5)
    indices = np.sort(rng.integers(0, 40, (ROWS, 4)).astype(np.int32), axis=1)
    values = rng.random((ROWS, 4)).astype(np.float32)
    indices, values, label = on_device(indices, values, (values.sum(axis=1) > 2).astype(np.float32))
    handed = []
    original = optimizer._sgd_train_flat

    def spy(X, y, w, init_coeff, loss_func, batch, has_weights, n, *rest, **kwargs):
        handed.append((w, init_coeff, n))
        return original(X, y, w, init_coeff, loss_func, batch, has_weights, n, *rest, **kwargs)

    monkeypatch.setattr(optimizer, "_sgd_train_flat", spy)
    stage = LogisticRegression().set_max_iter(3).set_global_batch_size(BATCH)
    with mesh_lib.use_mesh(one_shard()):
        _, counters = counted(lambda: stage.fit(Table({"features": SparseBatch(40, indices, values), "label": label})))
    assert counters["fit.stage.n"] == 1 and "fit.stage.launch_inputs" not in counters
    [(w, init, n)] = handed
    assert isinstance(init, jax.Array) and init.dtype == np.float32 and init.shape == (40,)
    # the weight placeholder and the row count go up with the launch all the same
    assert isinstance(w, np.ndarray) and w.shape == (0,) and w.dtype == np.float32
    assert isinstance(n, np.int32) and n == ROWS


# --- 2. weighted and ragged fits train as they did --------------------------------


def plain_schedule(X, y, w, batch, max_iter, lr):
    """Logistic SGD as the engine schedules it, in float64: an epoch applies the
    gradient of the one before, then takes batch `epoch mod batches`' gradient;
    one update more after the last epoch. Rows past the table weigh nothing."""
    n = X.shape[0]
    batches = -(-n // batch)
    X, y, w = X.astype(np.float64), y.astype(np.float64), w.astype(np.float64)
    coeff, grad, wsum = np.zeros(X.shape[1]), np.zeros(X.shape[1]), 0.0
    for epoch in range(max_iter):
        if wsum > 0:
            coeff = coeff - lr / wsum * grad
        rows = slice((epoch % batches) * batch, min(n, (epoch % batches + 1) * batch))
        label = 2 * y[rows] - 1
        margin = (X[rows] @ coeff) * label
        grad = X[rows].T @ (w[rows] * -label / (np.exp(margin) + 1))
        wsum = w[rows].sum()
    return coeff - lr / wsum * grad


@pytest.mark.parametrize(
    "rows, weighted",
    [(ROWS, True), (ROWS - 100, False), (ROWS - 100, True)],
    ids=["weighted", "ragged", "ragged_weighted"],
)
def test_weighted_and_ragged_fits_train_as_the_schedule_does(rows, weighted):
    X, y, w = host_table(rows)
    sgd = SGD(max_iter=BATCHES + 3, global_batch_size=BATCH, learning_rate=0.5, tol=0.0)
    columns = on_device(X, y, w if weighted else None)
    mesh = one_shard()
    (coeff, _, epochs), counters = counted(
        lambda: sgd.optimize(np.zeros(DIM), *columns, losses.BINARY_LOGISTIC_LOSS, mesh)
    )
    assert epochs == BATCHES + 3 and counters["fit.stage.launch_inputs"] == 1
    want = plain_schedule(X, y, w if weighted else np.ones(rows, np.float32), BATCH, BATCHES + 3, 0.5)
    np.testing.assert_allclose(coeff, want, rtol=2e-5, atol=2e-6)
    # the start placed on the device first, as it was before, gives the same bits
    placed, placed_counters = counted(
        lambda: sgd.optimize(jnp.zeros(DIM, jnp.float32), *columns, losses.BINARY_LOGISTIC_LOSS, mesh)
    )
    assert "fit.stage.launch_inputs" not in placed_counters
    np.testing.assert_array_equal(placed[0], coeff)


# --- 3. the program is the same for either form -----------------------------------


def ops(compiled_text):
    """The optimized HLO's instructions with their names left out."""
    return [re.sub(r"%[\w.\-]+", "%", line.strip()) for line in compiled_text.splitlines() if " = " in line]


@pytest.mark.parametrize(
    "loss, has_weights, check_labels",
    [("BINARY_LOGISTIC_LOSS", False, True), ("HINGE_LOSS", True, True), ("LEAST_SQUARE_LOSS", False, False)],
)
def test_the_flat_program_is_the_same_with_its_small_inputs_from_the_host(loss, has_weights, check_labels):
    X, y, w = host_table()
    X, y = on_device(X, y)
    hyper = SGD(max_iter=BATCHES + 2, global_batch_size=BATCH, learning_rate=0.3, tol=0.0)._hyper()
    start = np.random.default_rng(2).normal(size=DIM).astype(np.float32)
    from_host = (w if has_weights else np.zeros((0,), np.float32), start, np.int32(ROWS))
    placed = tuple(jnp.asarray(v) for v in from_host)
    if has_weights:
        # a weight column is the caller's and lies on the device in either form
        from_host = (on_device(w)[0],) + from_host[1:]
        placed = (from_host[0],) + placed[1:]
    train = partial(
        optimizer._sgd_train_flat,
        loss_func=getattr(losses, loss), batch=BATCH, has_weights=has_weights, check_labels=check_labels,
    )
    results = [np.asarray(train(X, y, w_, c, n=n, hyper=hyper)) for w_, c, n in (from_host, placed)]
    np.testing.assert_array_equal(results[0], results[1])
    assert np.all(np.isfinite(results[0]))
    program = jax.jit(train)
    lowered = [program.lower(X, y, w_, c, n=n, hyper=hyper) for w_, c, n in (from_host, placed)]
    assert lowered[0].as_text() == lowered[1].as_text()
    host_ops, placed_ops = (ops(low.compile().as_text()) for low in lowered)
    assert host_ops == placed_ops and any("while(" in line for line in host_ops)
