"""The fleet trained in place (`optimizer._can_train_in_place`,
`optimizer.FlatBatches`, `SGD._in_place`): on ONE device a `FitFleet` over a
dense device table of whole batches hands its programs a view of the caller's
table and lays nothing out.

1. the in-place fleet is the laid-out fleet bit for bit, and every member is
   its solo fit, for `reg` on a path with elasticNet 0, 0.5 and 1;
2. it agrees with the benchmark's plain reference of a path
   (`perf/reference/lr-regpath-100.py`) on seeded data;
3. what the view turns away keeps the laid-out route (`fleet.in_place` does
   not tick): ragged rows (dense or sparse), several shards, a host table,
   another dtype, a `StreamTable`, a checkpointed fleet;
4. a fleet fit is one fit to the observability layer: the four phases once,
   one `tracing.sync`, one outermost fit;
5. the members find their batch ONCE (no gather in the fleet program), and
   the solo programs that share `_sgd_chunk_impl` lower to what they were.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import config
from flink_ml_tpu.fleet import FitFleet
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.ops import losses, optimizer
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch, StreamTable, Table
from flink_ml_tpu.utils import metrics

ROWS, WIDTH, BATCH = 1200, 12, 200
PATH = [1.0 * (1e-4) ** (i / 4) for i in range(5)]  # the configuration's grid, five values of it
WATCHED = (
    "fleet.in_place", "fleet.fits", "fleet.modelsTrained", "fleet.examplesTrained", "layout.general",
    "layout.exchange", "fit.layout.n", "fit.extract.n", "fit.stage.n", "fit.launch.n", "fit.readback.n",
    "fit.total.n", "fit.outer.n", "sync.fit.n", "iteration.host_sync", "dense_epoch.reduce",
)


@pytest.fixture
def one_device():
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    with mesh_lib.use_mesh(mesh):
        yield mesh


def columns(rows=ROWS, seed=0):
    key = jax.random.PRNGKey(seed)
    X = jax.random.uniform(key, (rows, WIDTH), jnp.float32)
    y = (jax.random.uniform(jax.random.fold_in(key, 1), (rows,)) > 0.5).astype(jnp.float32)
    return X, y


def device_table(rows=ROWS, seed=0, weights=False):
    X, y = columns(rows, seed)
    cols = {"features": X, "label": y}
    if weights:
        cols["weight"] = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(seed), 2), (rows,), jnp.float32) + 0.5
    return Table(cols)


def host_table(table):
    return Table({name: np.asarray(table.column(name)) for name in table.column_names})


def path(kind=LogisticRegression, elastic_net=0.0, max_iter=15, weights=False):
    members = [
        kind().set_reg(reg).set_elastic_net(elastic_net).set_max_iter(max_iter).set_global_batch_size(BATCH).set_tol(0.0)
        for reg in PATH
    ]
    return [m.set_weight_col("weight") for m in members] if weights else members


def counted(fit):
    before = metrics.snapshot()
    out = fit()
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    return out, {name: delta.get(name, 0) for name in WATCHED}


def coefficients(models):
    return np.stack([np.asarray(m.coefficient) for m in models])


# --- 1. the same bits ---------------------------------------------------------------


@pytest.mark.parametrize("elastic_net", [0.0, 0.5, 1.0], ids=["l2", "mixed", "l1"])
@pytest.mark.parametrize("weights", [False, True], ids=["unit_weights", "weight_column"])
def test_the_in_place_fleet_is_the_laid_out_fleet_and_every_member_its_solo_fit(one_device, elastic_net, weights):
    table = device_table(weights=weights)
    in_place, ticks = counted(lambda: FitFleet(path(elastic_net=elastic_net, weights=weights)).fit(table))
    assert ticks["fleet.in_place"] == 1 and ticks["layout.general"] == ticks["layout.exchange"] == 0
    laid_out, ticks = counted(lambda: FitFleet(path(elastic_net=elastic_net, weights=weights)).fit(host_table(table)))
    assert ticks["fleet.in_place"] == 0 and ticks["layout.general"] == 3 - (not weights)
    np.testing.assert_array_equal(coefficients(in_place), coefficients(laid_out))
    solo = [member.fit(table) for member in path(elastic_net=elastic_net, weights=weights)]
    np.testing.assert_array_equal(coefficients(in_place), coefficients(solo))
    # the members differ, and come back in the grid's order: under L2 the stronger penalty shrinks more
    # (an L1 step of learningRate * reg = 0.1 throws a small coefficient to and fro across zero)
    norms = np.linalg.norm(coefficients(in_place), axis=1)
    assert len(set(norms)) == len(PATH) and (elastic_net or np.all(np.diff(norms) > 0))


@pytest.mark.parametrize("kind", [LinearSVC, LinearRegression], ids=["hinge", "least_square"])
def test_the_other_linear_estimators_train_in_place_too(one_device, kind):
    table = device_table()
    in_place, ticks = counted(lambda: FitFleet(path(kind)).fit(table))
    assert ticks["fleet.in_place"] == 1
    np.testing.assert_array_equal(coefficients(in_place), coefficients([m.fit(table) for m in path(kind)]))


def test_members_of_unequal_length_and_one_that_tol_stops_keep_their_solo_epochs(one_device):
    """The batch is found once, at the fleet's furthest epoch: a member that
    stopped is no longer at it, and its frozen state has to stay its solo
    fit's."""
    table = device_table()

    def members():
        fleet = path(max_iter=15)
        fleet[1].set_max_iter(4)  # stops inside the first pass
        fleet[2].set_tol(0.69)  # the mean loss falls under it after a few epochs
        fleet[3].set_max_iter(9)  # stops inside the second pass
        return fleet

    in_place, ticks = counted(lambda: FitFleet(members()).fit(table))
    assert ticks["fleet.in_place"] == 1
    np.testing.assert_array_equal(coefficients(in_place), coefficients([m.fit(table) for m in members()]))
    np.testing.assert_array_equal(coefficients(in_place), coefficients(FitFleet(members()).fit(host_table(table))))


# --- 2. the benchmark's reference ---------------------------------------------------


def reference_of_a_path():
    path_ = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perf", "reference", "lr-regpath-100.py")
    spec = importlib.util.spec_from_file_location("perf_reference_lr_regpath_100", path_)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("seed", [3, 2147483999 % 1000])
def test_the_fleet_agrees_with_the_plain_reference_of_a_path(one_device, seed):
    """Float32 sums in another order, over 40 epochs: 1e-5 of a member's
    norm is an order above what the CPU reads (1e-6) and three orders under
    what a reference in bfloat16 reads."""
    reference = reference_of_a_path()
    X, y = columns(seed=seed)
    params = {"learningRate": 0.1, "reg": PATH, "elasticNet": 0.0, "globalBatchSize": BATCH, "tol": 0.0, "maxIter": 40}
    want, epochs, _ = reference.fit({"features": X, "label": y}, {"dim": WIDTH}, params)
    got = coefficients(FitFleet(path(max_iter=40)).fit(Table({"features": X, "label": y})))
    gaps = np.linalg.norm(got - np.asarray(want), axis=1) / np.linalg.norm(np.asarray(want), axis=1)
    assert epochs == 40 and gaps.max() < 1e-5
    lower, _, _ = reference.fit({"features": X, "label": y}, {"dim": WIDTH}, params, precision="bfloat16")
    assert (np.linalg.norm(np.asarray(lower - want), axis=1) / np.linalg.norm(np.asarray(want), axis=1)).max() > 1e-3
    one, _, _ = reference.fit({"features": X, "label": y}, {"dim": WIDTH}, dict(params, reg=PATH[2]))
    np.testing.assert_allclose(np.asarray(one), np.asarray(want)[2], rtol=1e-5, atol=1e-8)  # one number, one member


# --- 3. what keeps the laid-out route -----------------------------------------------


def sparse_table():
    """A padded-CSR device table of ragged rows: a sparse table of whole
    batches trains in place (tests/test_fleet_sparse_rows.py)."""
    X, y = columns(rows=ROWS - 50)
    indices = jnp.tile(jnp.arange(WIDTH, dtype=jnp.int32), (ROWS - 50, 1))
    return Table({"features": SparseBatch(WIDTH, indices, X), "label": y})


TURNED_AWAY = {
    "ragged_rows": lambda: device_table(rows=ROWS - 50),
    "host_table": lambda: host_table(device_table()),
    "sparse_table": sparse_table,
    "float64_labels_on_the_host": lambda: Table({"features": columns()[0], "label": np.asarray(columns()[1], np.float64)}),
}


@pytest.mark.parametrize("name", list(TURNED_AWAY))
def test_a_table_the_view_turns_away_is_laid_out_as_it_was(one_device, name):
    table = TURNED_AWAY[name]()
    models, ticks = counted(lambda: FitFleet(path()).fit(table))
    assert ticks["fleet.in_place"] == 0 and ticks["layout.general"] >= 2 and ticks["fit.layout.n"] == 1
    np.testing.assert_array_equal(coefficients(models), coefficients([m.fit(table) for m in path()]))


def test_several_shards_keep_the_laid_out_route(mesh8):
    table = device_table(rows=1600)
    models, ticks = counted(lambda: FitFleet(path()).fit(table))
    assert ticks["fleet.in_place"] == 0 and ticks["layout.general"] >= 2
    np.testing.assert_array_equal(coefficients(models), coefficients([m.fit(table) for m in path()]))


def test_a_table_on_another_device_than_the_meshs_keeps_the_laid_out_route(one_device):
    X, y = columns()
    elsewhere = jax.devices()[3]
    table = Table({"features": jax.device_put(X, elsewhere), "label": jax.device_put(y, elsewhere)})
    assert not optimizer._can_train_in_place(table.column("features"), table.column("label"), None, BATCH, np.float32, one_device)
    assert optimizer._can_train_in_place(X, y, None, BATCH, np.float32, one_device)


def test_the_fleet_sharded_regime_keeps_the_laid_out_route(mesh8):
    members = [LogisticRegression().set_reg(r).set_max_iter(6).set_global_batch_size(BATCH) for r in PATH + PATH[:3]]
    _, ticks = counted(lambda: FitFleet(members, shard_fleet_axis=True).fit(device_table(rows=1600)))
    assert ticks["fleet.in_place"] == 0 and ticks["fleet.fits"] == 1


def test_a_stream_table_keeps_its_route(one_device):
    X, y = (np.asarray(c) for c in columns())
    stream = StreamTable.from_batches(
        [Table({"features": X[i : i + BATCH], "label": y[i : i + BATCH]}) for i in range(0, ROWS, BATCH)]
    )
    models, ticks = counted(lambda: FitFleet(path()).fit(stream))
    assert ticks["fleet.in_place"] == 0 and ticks["fit.extract.n"] == 0 and ticks["fleet.fits"] == 1
    assert len(models) == len(PATH)


def test_a_checkpointed_fleet_keeps_the_laid_out_route(one_device, tmp_path, monkeypatch):
    monkeypatch.setattr(config, "iteration_checkpoint_dir", str(tmp_path))
    monkeypatch.setattr(config, "iteration_checkpoint_interval", 5)
    table = device_table()
    models, ticks = counted(lambda: FitFleet(path()).fit(table))
    assert ticks["fleet.in_place"] == 0 and ticks["layout.general"] == 2
    monkeypatch.setattr(config, "iteration_checkpoint_dir", None)
    np.testing.assert_array_equal(coefficients(models), coefficients(FitFleet(path()).fit(table)))


# --- 4. one fit to the observability layer ------------------------------------------


def test_a_fleet_fit_reports_the_four_phases_once_and_one_sync(one_device):
    table = device_table()
    FitFleet(path()).fit(table)  # compiled
    _, ticks = counted(lambda: FitFleet(path(max_iter=15)).fit(table))
    for phase in ("fit.extract.n", "fit.stage.n", "fit.launch.n", "fit.readback.n", "fit.total.n", "fit.outer.n"):
        assert ticks[phase] == 1, phase
    assert ticks["sync.fit.n"] == ticks["iteration.host_sync"] == 1
    assert ticks["fit.layout.n"] == 0 and ticks["dense_epoch.reduce"] == 1
    assert ticks["fleet.fits"] == 1 and ticks["fleet.modelsTrained"] == len(PATH)
    # rows by epochs: 15 epochs of whole batches, every member
    assert ticks["fleet.examplesTrained"] == len(PATH) * 15 * BATCH


def test_examples_trained_counts_a_short_last_batch_by_its_rows(one_device):
    table = device_table(rows=ROWS - 50)  # six batches, the last of 150 rows
    _, ticks = counted(lambda: FitFleet(path(max_iter=8)).fit(table))
    one_member = (ROWS - 50) + 2 * BATCH  # a pass and two batches of the next
    assert ticks["fleet.examplesTrained"] == len(PATH) * one_member


def test_a_fleet_inside_a_fit_is_not_the_outermost(one_device):
    from flink_ml_tpu.obs import tracing

    table = device_table()
    assert getattr(FitFleet.fit, "_obs_instrumented", False)
    before = metrics.snapshot()
    tracing._fits.depth += 1  # as inside a pipeline's fit
    try:
        FitFleet(path()).fit(table)
    finally:
        tracing._fits.depth -= 1
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert delta.get("fit.total.n") == 1 and not delta.get("fit.outer.n")


# --- 5. the programs -----------------------------------------------------------------


def fleet_program(X_b, members=5):
    shape = jax.ShapeDtypeStruct
    carry = (
        shape((members, WIDTH), np.float32), shape((members, WIDTH), np.float32),
        shape((members,), np.float32), shape((members,), np.int32),
    )

    def fit(X, y_b, w_b, carry, criteria, hyper):
        return optimizer._sgd_fleet_whole_fit_impl(
            X_b(X), y_b, w_b, carry, criteria, losses.BINARY_LOGISTIC_LOSS, hyper, True, None
        )

    batches = ROWS // BATCH
    return jax.jit(fit).lower(
        shape((ROWS, WIDTH), np.float32), shape((batches, BATCH), np.float32), shape((batches, BATCH), np.float32),
        carry, shape((members,), np.float32), shape((members, 5), np.float32),
    ).as_text()


@pytest.mark.parametrize(
    "X_b",
    [lambda X: optimizer.FlatBatches(X, BATCH), lambda X: X.reshape(ROWS // BATCH, BATCH, WIDTH)],
    ids=["in_place", "laid_out"],
)
def test_the_members_find_their_batch_once(X_b):
    """Indexed by each member's own epoch the batch is a gather that makes N
    copies of it, and on the TPU the table is copied to feed the gather
    (22 GB asked of 15.75 for the benchmark's cell); by the fleet's furthest
    epoch it is one slice."""
    text = fleet_program(X_b)
    assert "gather" not in text
    assert f"tensor<{len(PATH)}x{BATCH}x{WIDTH}xf32>" in text  # the members' products of ONE batch
    assert re.search(r"stablehlo\.reduce\(.*maximum", text) or "stablehlo.maximum" in text  # the furthest epoch


def test_the_view_serves_the_general_forms_batches():
    X, _ = columns()
    view = optimizer.FlatBatches(X, BATCH)
    assert view.dtype == np.float32
    for k in (0, 3, ROWS // BATCH - 1):
        got = jax.jit(optimizer._index_batch)(view, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(X)[k * BATCH : (k + 1) * BATCH])
    leaves, tree = jax.tree_util.tree_flatten(view)
    assert len(leaves) == 1 and leaves[0] is X and jax.tree_util.tree_unflatten(tree, leaves).size == BATCH


# (arguments, lines) of the StableHLO jax lowers the solo chunk program to
# (`_sgd_chunk_impl`, the checkpointed loop's and the whole-fit program's
# body) for six laid-out batches of 200 x 12, as read at the commit before
# the loop could be told a fleet's axis (PR 37; the whole texts were compared
# then and were the same). The flat one-shard program, which three cells
# share, is held by tests/test_layout_walk.py.
PARENTS_CHUNK = (
    ["tensor<6x200x12xf32>", "tensor<6x200xf32>", "tensor<6x200xf32>", "tensor<12xf32>", "tensor<12xf32>",
     "tensor<f32>", "tensor<i32>", "tensor<f32>", "tensor<5xf32>", "tensor<i32>"],
    208,
)


def lowered_chunk(**fleet_axis):
    shape = jax.ShapeDtypeStruct
    batches = ROWS // BATCH
    carry = (shape((WIDTH,), np.float32), shape((WIDTH,), np.float32), shape((), np.float32), shape((), np.int32))

    def chunk(X_b, y_b, w_b, carry, criteria, hyper, end):
        return optimizer._sgd_chunk_impl(X_b, y_b, w_b, carry, criteria, losses.BINARY_LOGISTIC_LOSS, hyper, end, **fleet_axis)

    return jax.jit(chunk).lower(
        shape((batches, BATCH, WIDTH), np.float32), shape((batches, BATCH), np.float32),
        shape((batches, BATCH), np.float32), carry, shape((), np.float32), shape((5,), np.float32), shape((), np.int32),
    ).as_text()


def test_a_solo_chunk_lowers_to_the_program_it_was():
    text = lowered_chunk()
    main = text.split("func.func public @main(", 1)[1].split("->", 1)[0]
    assert (re.findall(r"%arg\d+: (tensor<[^>]*>)", main), len(text.splitlines())) == PARENTS_CHUNK
    assert text == lowered_chunk(fleet_axis=None)
