"""The walk: on several data shards a fit that reads no batch twice trains
each batch on the shard that holds it (ops/optimizer.py: `_can_walk`,
`SGD._stage_walk`, `_sgd_train_flat` as one leg).

Pinned here, on the suite's virtual devices:

1. a walked four-shard fit returns the one-shard fit's coefficient to the bit
   (the same rows summed in the same order on one device, by the reduce form
   and by the one-read kernel interpreted) and the batched route's within the
   four-chip cell's limits (there a batch's sum is four partial sums), for the
   three pointwise losses, with and without a weight column;
2. who walks: whole batches and at most one pass over a row-sharded device
   table; everything else takes exactly the route it took before;
3. what a walked fit counts, and that a `tol` that stops it in its second leg
   stops it where the one-shard fit stops;
4. a one-shard fit's program is the one it was before a leg could be asked for.

`tests/test_layout_exchange.py` compiles a leg for a described v5e.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_ml_tpu import Table, config
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.ops import losses, optimizer
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch
from flink_ml_tpu.utils import metrics

SHARDS = 4
BATCH = 2048  # two tiles of the kernel's 1-D columns
DIM = 8
PER_SHARE = 3  # batches a shard holds
ROWS = SHARDS * PER_SHARE * BATCH
NUM_BATCHES = ROWS // BATCH
# the four-chip cell's limits (perf/traffic/pass-x4.json)
COEF_GAP = COEF_MAX_GAP = 2e-4

ESTIMATORS = {"logistic": LogisticRegression, "hinge": LinearSVC, "least_square": LinearRegression}


def data_mesh(shards):
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:shards])


def host_table(rows=ROWS, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, DIM)).astype(np.float32)
    y = (X @ rng.normal(size=DIM) > 0).astype(np.float32)
    return {"features": X, "label": y, "weight": rng.random(rows).astype(np.float32) + 0.5}


def by_rows(mesh, columns):
    return {name: jax.device_put(col, mesh_lib.data_sharding(mesh, col.ndim)) for name, col in columns.items()}


def fit(estimator, table, mesh, max_iter=NUM_BATCHES, weighted=False, batch=BATCH):
    """(coefficient, the counters the fit moved) of one fit on `mesh`."""
    stage = ESTIMATORS[estimator]().set_global_batch_size(batch).set_max_iter(max_iter)
    if weighted:
        stage.set_weight_col("weight")
    with mesh_lib.use_mesh(mesh):
        before = metrics.snapshot()
        model = stage.fit(Table(table))
        counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    return np.asarray(model.coefficient), counters


def gaps(coeff, ref):
    """The cell's two numbers (perf/compare.py)."""
    return (
        np.linalg.norm(coeff - ref) / np.linalg.norm(ref),
        np.abs(coeff - ref).max() / np.abs(ref).max(),
    )


@pytest.fixture
def narrow_table_on_the_chip(monkeypatch):
    """The device says of every table what the TPU says of a narrow one, so
    a share takes the one-read kernel, interpreted."""
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)


# --- 1. the same model -----------------------------------------------------------


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("estimator", list(ESTIMATORS))
def test_a_walked_fit_is_the_one_shard_fit_to_the_bit_and_the_batched_fit_within_the_cells_limits(
    estimator, weighted, monkeypatch
):
    host = host_table()
    one, counters = fit(estimator, by_rows(data_mesh(1), host), data_mesh(1), weighted=weighted)
    assert "layout.walk" not in counters
    mesh = data_mesh(SHARDS)
    walked, counters = fit(estimator, by_rows(mesh, host), mesh, weighted=weighted)
    assert counters["layout.walk"] == 1
    assert np.all(np.isfinite(walked)) and np.any(walked != 0)
    np.testing.assert_array_equal(walked, one)
    monkeypatch.setattr(optimizer, "_can_walk", lambda *args: False)
    batched, counters = fit(estimator, by_rows(mesh, host), mesh, weighted=weighted)
    assert "layout.walk" not in counters and counters["layout.general"] >= 2
    gap, max_gap = gaps(walked, batched)
    assert gap < COEF_GAP and max_gap < COEF_MAX_GAP


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("estimator", list(ESTIMATORS))
def test_a_walked_fit_by_the_one_read_kernel_is_the_one_shard_fit_to_the_bit(
    estimator, weighted, narrow_table_on_the_chip
):
    host = host_table()
    one, counters = fit(estimator, by_rows(data_mesh(1), host), data_mesh(1), weighted=weighted)
    assert counters["dense_epoch.one_pass"] == 1
    mesh = data_mesh(SHARDS)
    walked, counters = fit(estimator, by_rows(mesh, host), mesh, weighted=weighted)
    assert counters["layout.walk"] == 1 and counters["dense_epoch.one_pass"] == 1
    np.testing.assert_array_equal(walked, one)


def test_a_part_of_a_pass_walks_only_the_shares_its_epochs_reach():
    """LinearRegression checks no label, so a fit of 4 epochs over shares of
    3 batches has two legs; LogisticRegression's labels are checked in the
    program, on every share, the epochs' or not."""
    host, mesh = host_table(), data_mesh(SHARDS)
    for estimator, legs in (("least_square", 2), ("logistic", SHARDS)):
        one, _ = fit(estimator, by_rows(data_mesh(1), host), data_mesh(1), max_iter=PER_SHARE + 1)
        walked, counters = fit(estimator, by_rows(mesh, host), mesh, max_iter=PER_SHARE + 1)
        assert counters["layout.walk"] == 1 and counters["layout.walk.legs"] == legs
        np.testing.assert_array_equal(walked, one)


def test_a_label_the_epochs_never_reach_is_still_refused():
    host, mesh = host_table(), data_mesh(SHARDS)
    host["label"][-1] = 2.0  # in the last share; one epoch reads the first batch
    with pytest.raises(ValueError, match="Multinomial"):
        fit("logistic", by_rows(mesh, host), mesh, max_iter=1)


# --- 2. who walks ------------------------------------------------------------------


def sparse(mesh, host):
    rng = np.random.default_rng(3)
    indices = np.sort(rng.integers(0, 40, (ROWS, DIM)).astype(np.int32), axis=1)
    put = lambda a: jax.device_put(a, mesh_lib.data_sharding(mesh, a.ndim))  # noqa: E731
    return {"features": SparseBatch(40, put(indices), put(np.abs(host["features"]))), "label": put(host["label"])}


def replicated(mesh, host):
    return {name: jax.device_put(col, NamedSharding(mesh, P())) for name, col in host.items()}


def label_on_the_host(mesh, host):
    return dict(by_rows(mesh, host), label=host["label"])


def bfloat16_table(mesh, host):
    return dict(by_rows(mesh, host), features=by_rows(mesh, {"f": host["features"].astype(jnp.bfloat16)})["f"])


# name: (does it walk?, the fit's keyword arguments, the table from (mesh, host) or None for sharded by rows,
#        config settings for the fit)
ROUTES = {
    "whole_batches_one_pass": (True, {}, None, {}),
    "a_fifth_of_a_pass": (True, {"max_iter": max(1, NUM_BATCHES // 5)}, None, {}),
    "one_epoch_more_than_a_pass": (False, {"max_iter": NUM_BATCHES + 1}, None, {}),
    "a_batch_straddles_two_shares": (False, {"batch": 2 * BATCH, "max_iter": 2}, None, {}),  # 1.5 batches a share
    "ragged_rows": (False, {"batch": BATCH - 8, "max_iter": 2}, None, {}),
    "host_table": (False, {}, lambda mesh, host: host, {}),
    "label_on_the_host": (False, {}, label_on_the_host, {}),
    "sparse_table": (False, {}, sparse, {}),
    "table_of_another_dtype": (False, {}, bfloat16_table, {}),
    "table_sharded_another_way": (False, {}, replicated, {}),
    "checkpoint_directory": (False, {}, None, {"iteration_checkpoint_dir": "<tmp>"}),
    "overlap_schedule": (False, {}, None, {"collective_overlap": True}),
}


@pytest.mark.parametrize("name", list(ROUTES))
def test_who_walks_and_who_keeps_the_route_it_had(name, tmp_path, monkeypatch):
    """Each case is the admitted fit but for the one thing its name says. What
    is turned away takes the route it took before the walk existed: the same
    counters and the same bits as with the predicate answering no."""
    walks, fit_args, make, settings = ROUTES[name]
    mesh, host = data_mesh(SHARDS), host_table()
    table = by_rows(mesh, host) if make is None else make(mesh, host)
    for key, value in settings.items():
        monkeypatch.setattr(config, key, str(tmp_path) if value == "<tmp>" else value)
    coeff, counters = fit("logistic", table, mesh, **fit_args)
    assert counters.get("layout.walk", 0) == int(walks)
    if walks:
        assert not any(k in counters for k in ("layout.exchange", "layout.general", "fit.layout.n"))
        return
    assert "layout.walk.legs" not in counters
    monkeypatch.setattr(optimizer, "_can_walk", lambda *args: False)
    if "iteration_checkpoint_dir" in settings:  # a directory of its own: the second fit is not to resume the first
        monkeypatch.setattr(config, "iteration_checkpoint_dir", str(tmp_path / "again"))
    before, routed = fit("logistic", table, mesh, **fit_args)
    np.testing.assert_array_equal(coeff, before)
    names = lambda c: {k for k in c if k.startswith(("layout.", "dense_epoch.", "fit.layout", "dispatch."))}  # noqa: E731
    assert names(counters) == names(routed)


def test_a_2d_mesh_and_the_fleets_replicated_data_never_walk(mesh_2d):
    host = host_table()
    columns = {
        name: jax.device_put(col, mesh_lib.data_sharding(mesh_2d, col.ndim)) for name, col in host.items()
    }
    X, y = columns["features"], columns["label"]
    assert not optimizer._can_walk(X, y, None, BATCH, NUM_BATCHES, jnp.float32, mesh_2d)
    _, counters = fit("logistic", columns, mesh_2d)
    assert "layout.walk" not in counters
    # the fleet lays its shared table out itself (`replicate_data`) and asks no predicate
    from flink_ml_tpu.fleet import FitFleet

    mesh = data_mesh(SHARDS)
    with mesh_lib.use_mesh(mesh):
        before = metrics.snapshot()
        members = [LogisticRegression().set_global_batch_size(BATCH).set_max_iter(3).set_learning_rate(lr) for lr in (0.1, 0.2, 0.3, 0.4)]
        FitFleet(members).fit(Table(by_rows(mesh, host)))
        counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert "layout.walk" not in counters and counters["layout.general"] >= 2


def test_the_predicate_reads_shapes_sharding_the_mesh_and_max_iter():
    mesh = data_mesh(SHARDS)
    columns = by_rows(mesh, host_table())
    X, y, w = columns["features"], columns["label"], columns["weight"]
    can = lambda **kw: optimizer._can_walk(  # noqa: E731
        kw.get("X", X), kw.get("y", y), kw.get("w", None), kw.get("batch", BATCH),
        kw.get("max_iter", NUM_BATCHES), kw.get("dtype", jnp.float32), kw.get("mesh", mesh),
    )
    assert can() and can(w=w) and can(max_iter=1)
    assert not can(max_iter=NUM_BATCHES + 1)
    assert not can(batch=2 * BATCH)  # 1.5 batches a share: one straddles
    assert not can(y=None) and not can(y=np.asarray(y)) and not can(w=np.asarray(w))
    assert not can(dtype=jnp.bfloat16)
    assert not can(mesh=data_mesh(1)) and not can(mesh=data_mesh(2))  # another mesh than the table's
    assert not can(X=jnp.zeros((0, DIM), jnp.float32))


# --- 3. what it counts, and where tol stops it -------------------------------------


def test_a_walked_fit_counts_one_launch_one_sync_and_no_layout():
    mesh = data_mesh(SHARDS)
    _, counters = fit("logistic", by_rows(mesh, host_table()), mesh)
    assert counters["layout.walk"] == 1 and counters["layout.walk.legs"] == SHARDS
    assert not any(name.startswith(("layout.exchange", "layout.general", "collective.", "fit.layout")) for name in counters)
    assert counters["iteration.host_sync"] == counters["sync.fit.n"] == 1
    assert counters["fit.launch.n"] == counters["fit.stage.n"] == counters["fit.readback.n"] == 1
    assert counters["dense_epoch.reduce"] == 1 and "dense_epoch.one_pass" not in counters  # once a fit, not a leg
    assert counters["dispatch.whole_fit.sgd"] == 1


def test_tol_that_stops_in_the_second_leg_stops_where_the_one_shard_fit_stops():
    """Labels the features decide, so the loss falls below a tol within a
    pass; the legs behind the one it stops in run for no epoch."""
    host, mesh = host_table(), data_mesh(SHARDS)
    sgd = SGD(max_iter=NUM_BATCHES, global_batch_size=BATCH, learning_rate=0.5, tol=0.5)
    loss, init = losses.BINARY_LOGISTIC_LOSS, np.zeros(DIM)
    columns = by_rows(data_mesh(1), host)
    one = sgd.optimize(init, columns["features"], columns["label"], None, loss, data_mesh(1))
    assert PER_SHARE < one[2] <= 2 * PER_SHARE, one[2]  # stopped by tol, in the second share
    columns = by_rows(mesh, host)
    before = metrics.snapshot()
    walked = sgd.optimize(init, columns["features"], columns["label"], None, loss, mesh)
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert counters["layout.walk"] == 1 and counters["layout.walk.legs"] == SHARDS
    assert walked[2] == one[2] and walked[1] == one[1]
    np.testing.assert_array_equal(walked[0], one[0])


# --- 4. the one-shard program is the parent's ----------------------------------------

# (arguments, lines) of the StableHLO jax lowers `_sgd_train_flat` to for a
# 4096 x 16 float32 table in batches of 256, by (loss, weight column?, labels
# checked?), as read at the commit before a leg could be asked for (PR 36; the
# whole texts were compared then, these and eight more, and were the same; jax
# leaves an argument the program never reads, the absent weights or n, out of
# the list). The three one-chip fit cells share this program, and a
# recompilation alone has moved a cell (PERF.md §6, PR 31): what changes these
# numbers changes their program.
WIDE, NARROW, COLUMN = "tensor<4096x16xf32>", "tensor<16xf32>", "tensor<4096xf32>"
PARENTS_PROGRAM = {
    ("BINARY_LOGISTIC_LOSS", False, True): ([WIDE, COLUMN, NARROW, "tensor<i32>", "tensor<5xf32>"], 261),
    ("BINARY_LOGISTIC_LOSS", True, True): ([WIDE, COLUMN, COLUMN, NARROW, "tensor<5xf32>"], 261),
    ("HINGE_LOSS", False, True): ([WIDE, COLUMN, NARROW, "tensor<i32>", "tensor<5xf32>"], 258),
    ("LEAST_SQUARE_LOSS", True, False): ([WIDE, COLUMN, COLUMN, NARROW, "tensor<5xf32>"], 227),
}


def lowered_flat(loss, has_weights, check_labels, carry=None):
    """`_sgd_train_flat` for that table, lowered; `carry` False says the
    absent leg aloud, True hands it a leg's end state in the start
    coefficient's place."""
    shape = jax.ShapeDtypeStruct
    rows, width, batch = 4096, 16, 256

    def train(X, y, w, init, n, hyper, carry_in):
        return optimizer._sgd_train_flat(
            X, y, w, init, getattr(losses, loss), batch, has_weights, n, hyper, check_labels, False, False,
            **({} if carry is None else {"carry": carry_in}),
        )

    return jax.jit(train).lower(
        shape((rows, width), np.float32), shape((rows,), np.float32), shape((rows if has_weights else 0,), np.float32),
        None if carry else shape((width,), np.float32), shape((), np.int32), shape((5,), np.float32),
        shape((2 * width + 4,), np.float32) if carry else None,
    ).as_text()


def arguments_and_lines(text):
    import re

    main = text.split("func.func public @main(", 1)[1].split("->", 1)[0]
    return re.findall(r"%arg\d+: (tensor<[^>]*>)", main), len(text.splitlines())


@pytest.mark.parametrize("loss, has_weights, check_labels", list(PARENTS_PROGRAM))
def test_a_one_shard_fit_lowers_to_the_program_it_was(loss, has_weights, check_labels):
    text = lowered_flat(loss, has_weights, check_labels)
    assert arguments_and_lines(text) == PARENTS_PROGRAM[(loss, has_weights, check_labels)]
    assert text == lowered_flat(loss, has_weights, check_labels, carry=False)
    # and a leg is another program: the state comes in one vector and goes out as one
    leg = lowered_flat(loss, has_weights, check_labels, carry=True)
    arguments, _ = arguments_and_lines(leg)
    assert "tensor<36xf32>" in arguments and NARROW not in arguments
    assert "-> (tensor<36xf32>" in leg.split("func.func public @main(", 1)[1].split("{", 1)[0]


def test_the_first_legs_carry_is_the_one_shard_programs_start():
    """What the first leg is handed in the start coefficient's place, made on
    the host for a host coefficient and on the device for a device one."""
    init = np.arange(3, dtype=np.float64)
    expected = np.array([0, 1, 2, 0, 0, 0, 0, 0, np.inf, 1], np.float32)
    for given in (init, jnp.asarray(init, jnp.float32)):
        carry = optimizer._first_leg_carry(given, np.dtype(np.float32))
        assert isinstance(carry, jax.Array) == isinstance(given, jax.Array) and carry.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(carry), expected)
    state, ok = optimizer._unpack_leg_carry(jnp.asarray(expected), jnp.float32)
    assert [np.asarray(part).tolist() for part in state] == [[0, 1, 2], [0, 0, 0], 0, 0, np.inf] and ok == 1
    np.testing.assert_array_equal(np.asarray(optimizer._pack_leg_carry(state, ok)), expected)
