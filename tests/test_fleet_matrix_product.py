"""The fleet's matrix form (`optimizer._fleet_multiplies`,
`losses.product_variant`): on a TPU a fleet's epochs form the N members'
row-dots as ONE [B, d] x [d, N] product and their gradients as ONE [N, B] x
[B, d], float32 at `Precision.HIGHEST`, where the reduce form's two vector-unit
reductions took 94% of the path cell's epoch (PERF.md §5, PR 39). A test that
wants the form tells `mesh_lib.on_tpu` to say yes.

1. the loss alone: the product form is the reduce form's sums to rounding, and
   under the member `vmap` each of its two contractions is ONE product;
2. on every fleet route (in place, laid out, several shards, fleet-sharded,
   checkpointed chunks, a stream) the matrix form's members are the reduce
   form's within 1e-5 of a member's norm, at the same stop epochs, for `reg`
   on a path, mixed elasticNet, unequal maxIter and a member that tol stops;
3. each fleet fit ticks the form it took once, `fleet.product.matrix` or
   `fleet.product.reduce`, and the programs are handed that form's loss: on
   the CPU, `on_tpu` untouched, the reduce form on every route of 2. (the
   bit-parity tests of tests/test_fleet.py and tests/test_fleet_in_place.py
   stand as they were), and a sparse table takes no product form on the chip
   either (its own form is tests/test_fleet_sparse_rows.py's);
4. the solo programs lower to the same text whatever `on_tpu` and
   `_fleet_multiplies` say, so the product form cannot reach them; the CPU's
   fleet program forms the members' products by broadcast and holds no
   `dot_general`; the TPU form's fleet program holds two `dot_general` at
   HIGHEST and no product of the members with the batch.
"""

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
from flink_ml_tpu.parallel import dispatch
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch, StreamTable, Table
from flink_ml_tpu.utils import metrics

ROWS, WIDTH, BATCH = 1600, 12, 200
PATH = [1.0 * (1e-4) ** (i / 7) for i in range(8)]  # eight values of the configuration's grid
DENSE = {
    "binary_logistic": losses.BINARY_LOGISTIC_LOSS,
    "hinge": losses.HINGE_LOSS,
    "least_square": losses.LEAST_SQUARE_LOSS,
}
KINDS = {"binary_logistic": LogisticRegression, "hinge": LinearSVC, "least_square": LinearRegression}


@pytest.fixture
def one_device():
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    with mesh_lib.use_mesh(mesh):
        yield mesh


@pytest.fixture
def on_the_chip(monkeypatch):
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)


def columns(rows=ROWS, seed=0):
    key = jax.random.PRNGKey(seed)
    X = jax.random.uniform(key, (rows, WIDTH), jnp.float32)
    y = (jax.random.uniform(jax.random.fold_in(key, 1), (rows,)) > 0.5).astype(jnp.float32)
    return X, y


def device_table():
    X, y = columns()
    return Table({"features": X, "label": y})


def host_table():
    return Table({name: np.asarray(col) for name, col in zip(("features", "label"), columns())})


def stream_table():
    X, y = (np.asarray(c) for c in columns())
    return StreamTable.from_batches(
        [Table({"features": X[i : i + BATCH], "label": y[i : i + BATCH]}) for i in range(0, ROWS, BATCH)]
    )


def members(kind=LogisticRegression, max_iter=15):
    """`reg` on a path; elasticNet 0, 0.5 and 1 in turn; two members that
    stop early by maxIter, inside the first pass and inside the second; one
    that tol stops (the mean loss under 0.6928 at its sixth epoch, 0.6924
    there: no rounding moves it)."""
    fleet = [
        kind().set_reg(reg).set_elastic_net((0.0, 0.5, 1.0)[i % 3]).set_max_iter(max_iter)
        .set_global_batch_size(BATCH).set_tol(0.0)
        for i, reg in enumerate(PATH)
    ]
    fleet[1].set_max_iter(4)
    fleet[3].set_max_iter(11)
    if kind is LogisticRegression:
        fleet[2].set_tol(0.6928)
    return fleet


EPOCHS = [15, 4, 6, 11, 15, 15, 15, 15]  # the members' stop epochs, whatever the form


class Fits:
    """What a fleet fit hands back and what it counts: the members'
    coefficients, their stop epochs, the losses its programs were handed and
    the ticks of the two forms."""

    def __init__(self, monkeypatch):
        self.epochs, self.losses = [], []
        unpack, timed = optimizer.unpack_fleet_train_result, dispatch.timed_dispatch

        def unpack_and_keep(*args, **kwargs):
            out = unpack(*args, **kwargs)
            self.epochs.append(np.asarray(out[3]))
            return out

        def timed_and_keep(fn, *args, **kwargs):
            self.losses += [arg for arg in args if isinstance(arg, losses.LossFunc)]
            return timed(fn, *args, **kwargs)

        monkeypatch.setattr(optimizer, "unpack_fleet_train_result", unpack_and_keep)
        monkeypatch.setattr(dispatch, "timed_dispatch", timed_and_keep)

    def __call__(self, fleet, table, **options):
        self.losses.clear()
        before = metrics.snapshot()
        models = FitFleet(fleet, **options).fit(table)
        delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        ticks = {form: delta.get(f"fleet.product.{form}", 0) for form in ("matrix", "reduce")}
        coefficients = np.stack([np.asarray(m.coefficient) for m in models])
        return coefficients, self.epochs[-1], ticks, list(self.losses)


@pytest.fixture
def fits(monkeypatch):
    return Fits(monkeypatch)


def member_gaps(got, want):
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)


# --- 1. the loss alone ----------------------------------------------------------------


@pytest.mark.parametrize("name", list(DENSE))
def test_the_product_form_gives_the_reduce_forms_sums(name):
    X, y = columns(rows=BATCH)
    w = jnp.linspace(0.5, 1.5, BATCH, dtype=jnp.float32)
    coeff = jnp.linspace(-1.0, 1.0, WIDTH, dtype=jnp.float32)
    product = losses.product_variant(DENSE[name])
    assert product is not DENSE[name] and product.pointwise is DENSE[name].pointwise and not product.sparse
    for got, want in zip(product(X, y, w, coeff), DENSE[name](X, y, w, coeff)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", list(DENSE))
def test_under_the_member_vmap_each_contraction_is_one_product(name):
    """The batched coefficient becomes a free dimension of the product: the
    members' row-dots [B, N] and gradients [N, d], the batch unbatched."""
    X, y = columns(rows=BATCH)
    w = jnp.ones((BATCH,), jnp.float32)
    coeffs = jnp.zeros((len(PATH), WIDTH), jnp.float32)
    fleet_loss = jax.vmap(losses.product_variant(DENSE[name]), in_axes=(None, None, None, 0))
    jaxpr = jax.make_jaxpr(fleet_loss)(X, y, w, coeffs).jaxpr
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    assert {tuple(e.outvars[0].aval.shape) for e in dots} == {(BATCH, len(PATH)), (len(PATH), WIDTH)}
    assert all(e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2 for e in dots)
    assert not any(e.primitive.name == "reduce_sum" and e.invars[0].aval.ndim == 3 for e in jaxpr.eqns)


def test_every_dense_loss_has_one_product_form_and_a_sparse_loss_none():
    assert {losses.product_variant(loss).name for loss in DENSE.values()} == {
        "binary_logistic_product", "hinge_product", "least_square_product"
    }
    assert losses.product_variant(losses.BINARY_LOGISTIC_LOSS) is losses.product_variant(losses.BINARY_LOGISTIC_LOSS)
    with pytest.raises(KeyError):
        losses.product_variant(losses.SPARSE_BINARY_LOGISTIC_LOSS)


# --- 2. every fleet route, the matrix form against the reduce form --------------------


def checkpointed(monkeypatch, tmp_path):
    monkeypatch.setattr(config, "iteration_checkpoint_dir", str(tmp_path))
    monkeypatch.setattr(config, "iteration_checkpoint_interval", 5)


ROUTES = {
    # route: (mesh fixture, table, FitFleet options, set-up)
    "in_place": ("one_device", device_table, {}, None),
    "laid_out": ("one_device", host_table, {}, None),
    "several_shards": ("mesh8", device_table, {}, None),
    "fleet_sharded": ("mesh8", device_table, {"shard_fleet_axis": True}, None),
    "checkpointed_chunks": ("one_device", device_table, {}, checkpointed),
    "stream": ("one_device", stream_table, {}, None),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_matrix_forms_members_are_the_reduce_forms_at_the_same_epochs(route, fits, request, monkeypatch, tmp_path):
    mesh_fixture, table, options, set_up = ROUTES[route]
    request.getfixturevalue(mesh_fixture)
    if set_up is not None:
        set_up(monkeypatch, tmp_path / "reduce")
    want, want_epochs, ticks, handed = fits(members(), table(), **options)
    assert ticks == {"matrix": 0, "reduce": 1} and set(handed) == {losses.BINARY_LOGISTIC_LOSS}  # the CPU's form
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    if set_up is not None:
        set_up(monkeypatch, tmp_path / "matrix")
    got, got_epochs, ticks, handed = fits(members(), table(), **options)
    assert ticks == {"matrix": 1, "reduce": 0}
    assert handed and {loss.name for loss in handed} == {"binary_logistic_product"}
    assert got_epochs.tolist() == want_epochs.tolist() == EPOCHS
    assert member_gaps(got, want).max() < 1e-5


@pytest.mark.parametrize("kind", ["hinge", "least_square"])
def test_the_other_linear_estimators_take_the_matrix_form_too(one_device, fits, monkeypatch, kind):
    want, want_epochs, _, _ = fits(members(KINDS[kind]), device_table())
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    got, got_epochs, ticks, handed = fits(members(KINDS[kind]), device_table())
    assert ticks == {"matrix": 1, "reduce": 0} and {loss.name for loss in handed} == {f"{kind}_product"}
    np.testing.assert_array_equal(got_epochs, want_epochs)
    assert member_gaps(got, want).max() < 1e-5


# --- 3. one tick a fleet fit, and the CPU keeps the reduce form -----------------------


@pytest.mark.parametrize("checkpoints", [False, True], ids=["whole_fit", "checkpointed_chunks"])
def test_each_fleet_fit_ticks_the_matrix_form_once(one_device, fits, on_the_chip, monkeypatch, tmp_path, checkpoints):
    for fit in range(2):
        if checkpoints:  # a directory a fit: none resumes the other's snapshot
            checkpointed(monkeypatch, tmp_path / str(fit))
        _, _, ticks, handed = fits(members(), device_table())
        assert ticks == {"matrix": 1, "reduce": 0}
        assert {loss.name for loss in handed} == {"binary_logistic_product"}
        # the whole fit is one program; the checkpointed fit is chunks of two
        # epochs cut at the snapshots, every fifth epoch: nine
        assert len(handed) == (9 if checkpoints else 1)


def test_a_sparse_table_keeps_the_reduce_form_on_the_chip_too(one_device, fits, on_the_chip, monkeypatch, tmp_path):
    """No product form for a sparse table. On the whole-fit route of one
    fleet it takes the member-row form (`fleet.product.rows`,
    tests/test_fleet_sparse_rows.py); the checkpointed chunks keep the
    reduce form, as they did."""
    checkpointed(monkeypatch, tmp_path)
    X, y = columns()
    indices = jnp.tile(jnp.arange(WIDTH, dtype=jnp.int32), (ROWS, 1))
    _, _, ticks, handed = fits(members(), Table({"features": SparseBatch(WIDTH, indices, X), "label": y}))
    assert ticks == {"matrix": 0, "reduce": 1} and set(handed) == {losses.SPARSE_BINARY_LOGISTIC_LOSS}


def test_the_decision_reads_the_table_and_the_loss(on_the_chip):
    X, _ = columns()
    assert optimizer._fleet_multiplies(X, losses.HINGE_LOSS)
    assert optimizer._fleet_multiplies(optimizer.FlatBatches(X, BATCH), losses.BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_multiplies(X.astype(jnp.bfloat16), losses.BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_multiplies((X, X), losses.SPARSE_BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_multiplies(np.asarray(X), losses.BINARY_LOGISTIC_LOSS)


def test_a_kmeans_fleet_counts_no_product_form(one_device):
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    before = metrics.snapshot()
    FitFleet([KMeans().set_k(3).set_seed(s).set_max_iter(3) for s in range(2)]).fit(Table({"features": columns()[0]}))
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert not delta.get("fleet.product.matrix") and not delta.get("fleet.product.reduce")


# --- 4. the programs -----------------------------------------------------------------

SHAPE = jax.ShapeDtypeStruct
NB = ROWS // BATCH
MEMBERS = 5


def lowered_solo(program, loss=losses.BINARY_LOGISTIC_LOSS):
    carry = (SHAPE((WIDTH,), np.float32), SHAPE((WIDTH,), np.float32), SHAPE((), np.float32), SHAPE((), np.int32))
    batched = (SHAPE((NB, BATCH, WIDTH), np.float32), SHAPE((NB, BATCH), np.float32), SHAPE((NB, BATCH), np.float32))
    if program == "_sgd_train_flat":
        fn = lambda X, y, w, init, n, hyper: optimizer._sgd_train_flat(X, y, w, init, loss, BATCH, True, n, hyper, True)
        args = (SHAPE((ROWS, WIDTH), np.float32), SHAPE((ROWS,), np.float32), SHAPE((ROWS,), np.float32),
                SHAPE((WIDTH,), np.float32), SHAPE((), np.int32), SHAPE((5,), np.float32))
    elif program == "_sgd_whole_fit":
        fn = lambda X, y, w, c, crit, hyper: optimizer._sgd_whole_fit_impl(X, y, w, c, crit, loss, hyper, None)
        args = batched + (carry, SHAPE((), np.float32), SHAPE((5,), np.float32))
    else:
        fn = lambda X, y, w, init, hyper: optimizer._sgd_train(X, y, w, init, loss, hyper, True, None)
        args = batched + (SHAPE((WIDTH,), np.float32), SHAPE((5,), np.float32))
    return jax.jit(fn).lower(*args).as_text()


def lowered_fleet(program, loss=losses.BINARY_LOGISTIC_LOSS):
    carry = (SHAPE((MEMBERS, WIDTH), np.float32), SHAPE((MEMBERS, WIDTH), np.float32),
             SHAPE((MEMBERS,), np.float32), SHAPE((MEMBERS,), np.int32))
    columns_ = (SHAPE((NB, BATCH), np.float32), SHAPE((NB, BATCH), np.float32))
    rest = (carry, SHAPE((MEMBERS,), np.float32), SHAPE((MEMBERS, 5), np.float32))
    if program == "_sgd_fleet_whole_fit":
        def fn(X, y, w, c, crit, hyper):
            return optimizer._sgd_fleet_whole_fit_impl(
                optimizer.FlatBatches(X, BATCH), y, w, c, crit, loss, hyper, True, None
            )

        return jax.jit(fn).lower(SHAPE((ROWS, WIDTH), np.float32), *columns_, *rest).as_text()

    def fn(X, y, w, c, crit, hyper, end):
        return optimizer._sgd_fleet_chunk_impl(X, y, w, c, crit, loss, hyper, end)

    return jax.jit(fn).lower(SHAPE((NB, BATCH, WIDTH), np.float32), *columns_, *rest, SHAPE((), np.int32)).as_text()


def lowered_solo_told(program, says, monkeypatch):
    """`lowered_solo(program)` traced afresh, with the chip's two predicates
    answering `says`."""
    jax.clear_caches()  # nothing traced before is found again
    with monkeypatch.context() as patched:
        patched.setattr(mesh_lib, "on_tpu", lambda arr: says)
        patched.setattr(optimizer, "_fleet_multiplies", lambda X_b, loss_func: says)
        return lowered_solo(program)


@pytest.mark.parametrize("program", ["_sgd_train_flat", "_sgd_whole_fit", "_sgd_train"])
def test_the_solo_programs_never_take_the_product_form(program, monkeypatch):
    text = lowered_solo_told(program, False, monkeypatch)
    assert lowered_solo_told(program, True, monkeypatch) == text
    assert "HIGHEST" not in text


@pytest.mark.parametrize("program", ["_sgd_fleet_whole_fit", "_sgd_fleet_chunk"])
def test_the_cpus_fleet_program_forms_the_products_by_broadcast(program):
    text = lowered_fleet(program)
    assert "dot_general" not in text and f"tensor<{MEMBERS}x{BATCH}x{WIDTH}xf32>" in text  # the members' products


@pytest.mark.parametrize("program", ["_sgd_fleet_whole_fit", "_sgd_fleet_chunk"])
def test_the_tpu_forms_fleet_program_multiplies(program):
    text = lowered_fleet(program, losses.product_variant(losses.BINARY_LOGISTIC_LOSS))
    dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    assert len(dots) == 2 and all("precision = [HIGHEST, HIGHEST]" in line for line in dots)
    assert f"tensor<{BATCH}x{MEMBERS}xf32>" in text or f"tensor<{MEMBERS}x{BATCH}xf32>" in text
    # no product of the members with the batch is formed, nor reduced
    assert f"tensor<{MEMBERS}x{BATCH}x{WIDTH}xf32>" not in text
    assert "gather" not in text
