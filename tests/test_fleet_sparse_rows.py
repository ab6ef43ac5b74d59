"""The sparse fleet in place and in the member-row form
(`optimizer._can_train_in_place` for a padded-CSR pair,
`optimizer._fleet_rows`, `_sgd_fleet_rows_whole_fit`,
`losses.rows_variant`, `sparse_epoch.planned_rows_loss`): on ONE device a
`FitFleet` of linear members over a padded-CSR table of whole batches reads
the table where it lies, and on a TPU holds its members' coefficients [d, N]
so that an entry's N coefficients are one gathered row and its N gradients
one row segment-summed, over the column plan made once a fleet fit. A test
that wants the form tells `mesh_lib.on_tpu` to say yes.

1. the loss alone: the row form is N solo losses' sums to rounding, planned
   or not, for the three pointwise losses;
2. the row form's members are the reduce form's and their solo fits' to
   rounding, at the same stop epochs, in place and laid out, with a weight
   column, for `reg` on a path, mixed elasticNet, unequal maxIter and a tol
   stop; on the CPU a sparse fleet in place is its solo fits bit for bit;
3. the plan is made once a fleet fit, over the rows the longest member's
   epochs reach: ids in rows no member reads (one more id than the widest
   dictionary, a second id in a column constant where it is read) change
   neither the plan nor the members; `fleet.in_place`, `fleet.product.rows`
   and `sparse_epoch.*` tick as docs/observability.md says;
4. the programs: the row program holds its state [d, N] and gathers rows;
   the solo sparse programs and the CPU's sparse fleet program lower to the
   same text whatever `_fleet_rows` says, and hold no [d, N] state.
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
from flink_ml_tpu.ops import losses, optimizer, sparse_epoch
from flink_ml_tpu.parallel import dispatch
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch, Table
from flink_ml_tpu.utils import metrics

# a toy click log: 13 integer fields at fixed ids, categorical fields of 3, 24
# and 300 categories (dictionaries of the plan) and two of ids spread over the
# dimension (5,000 rows, more distinct ids than a dictionary holds: gathered)
ROWS, BATCH, DIM = 5000, 500, 20000
CARDS = (3, 24, 300, None, 3, 24, 300, None)
NNZ = 13 + len(CARDS)
GATHERED = CARDS.count(None)
PATH = [1.0 * (1e-4) ** (i / 5) for i in range(6)]  # six values of the configuration's grid, its ends among them
SPARSE = {
    "binary_logistic": losses.SPARSE_BINARY_LOGISTIC_LOSS,
    "hinge": losses.SPARSE_HINGE_LOSS,
    "least_square": losses.SPARSE_LEAST_SQUARE_LOSS,
}
KINDS = {"binary_logistic": LogisticRegression, "hinge": LinearSVC, "least_square": LinearRegression}


def click_log(rows=ROWS, seed=0):
    """(ids i32[rows, NNZ], values f32[rows, NNZ], labels f32[rows]); two
    fields of a row may hold one id, and one entry is padding."""
    rng = np.random.default_rng(seed)
    ids = np.empty((rows, NNZ), np.int32)
    ids[:, :13] = np.arange(13)
    for j, card in enumerate(CARDS):
        draw = rng.permutation(DIM - 13)[:rows] if card is None else rng.integers(0, card, rows) * 97
        ids[:, 13 + j] = 13 + draw % (DIM - 13)
    values = np.ones((rows, NNZ), np.float32)
    values[:, :13] = rng.random((rows, 13))
    ids[7, 14], values[7, 14] = -1, 0.0
    labels = (rng.random(rows) > 0.5).astype(np.float32)
    return ids, values, labels


def device_table(rows=ROWS, seed=0, weights=False):
    ids, values, labels = click_log(rows, seed)
    columns = {"features": SparseBatch(DIM, jnp.asarray(ids), jnp.asarray(values)), "label": jnp.asarray(labels)}
    if weights:
        columns["weight"] = jnp.asarray(np.random.default_rng(seed + 1).random(rows).astype(np.float32) + 0.5)
    return Table(columns)


def host_table(rows=ROWS, seed=0):
    ids, values, labels = click_log(rows, seed)
    return Table({"features": SparseBatch(DIM, ids, values), "label": labels})


def members(kind=LogisticRegression, max_iter=14, weights=False):
    """`reg` on a path; elasticNet 0, 0.5 and 1 in turn; members that stop
    early by maxIter inside the first pass and inside the second; for the
    logistic loss one that tol stops."""
    fleet = [
        kind().set_reg(reg).set_elastic_net((0.0, 0.5, 1.0)[i % 3]).set_max_iter(max_iter)
        .set_global_batch_size(BATCH).set_tol(0.0)
        for i, reg in enumerate(PATH)
    ]
    fleet[1].set_max_iter(4)
    fleet[3].set_max_iter(12)
    if kind is LogisticRegression:
        fleet[2].set_tol(0.69295)  # its mean loss falls to 0.692929 at its seventh epoch
    return [m.set_weight_col("weight") for m in fleet] if weights else fleet


@pytest.fixture
def one_device():
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    with mesh_lib.use_mesh(mesh):
        yield mesh


@pytest.fixture
def on_the_chip(monkeypatch):
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)


WATCHED = (
    "fleet.in_place", "fleet.product.rows", "fleet.product.reduce", "fleet.product.matrix", "fleet.fits",
    "sparse_epoch.planned", "sparse_epoch.general", "sparse_epoch.entries", "sparse_epoch.entries_gathered",
    "sparse_epoch.plan_rows", "sparse_epoch.table_rows",
    "sync.plan.n", "sync.fit.n", "iteration.host_sync", "fit.layout.n", "layout.general", "dense_epoch.reduce",
)


class Fits:
    """A fleet fit's coefficients, its members' stop epochs, the losses its
    programs were handed and the counters it moved."""

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

    def __call__(self, fleet, table):
        self.losses.clear()
        before = metrics.snapshot()
        models = FitFleet(fleet).fit(table)
        delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        coefficients = np.stack([np.asarray(m.coefficient) for m in models])
        return coefficients, self.epochs[-1], {name: delta.get(name, 0) for name in WATCHED}, list(self.losses)


@pytest.fixture
def fits(monkeypatch):
    return Fits(monkeypatch)


def member_gaps(got, want):
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)


def batch_of(table_rows=BATCH, seed=1):
    ids, values, labels = click_log(table_rows, seed)
    w = jnp.linspace(0.5, 1.5, table_rows, dtype=jnp.float32)
    return (jnp.asarray(ids), jnp.asarray(values)), jnp.asarray(labels), w


# --- 1. the loss alone ----------------------------------------------------------------


@pytest.mark.parametrize("name", list(SPARSE))
def test_the_row_form_gives_each_members_solo_sums(name):
    X, y, w = batch_of()
    coeffs = jax.random.normal(jax.random.PRNGKey(3), (len(PATH), DIM), jnp.float32) * 0.1
    rows = losses.rows_variant(SPARSE[name])
    assert rows.sparse and rows.pointwise is SPARSE[name].pointwise and rows.name == SPARSE[name].name + "_rows"
    loss, grad, wsum = rows(X, y, w, coeffs.T)
    assert grad.shape == (DIM, len(PATH))
    for i, coeff in enumerate(coeffs):
        want = SPARSE[name](X, y, w, coeff)
        np.testing.assert_allclose(np.asarray(loss[i]), np.asarray(want[0]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad[:, i]), np.asarray(want[1]), rtol=1e-5, atol=1e-5)
        assert float(wsum) == float(want[2])


@pytest.mark.parametrize("name", list(SPARSE))
def test_the_planned_row_form_gives_the_row_forms_sums(name):
    """Constant columns, dictionaries and gathered columns, as the plan of
    the batch's own ids finds them (a batch of the table's size, whose wide
    columns hold more ids than a dictionary): the sums of the plain row form."""
    X, y, w = batch_of(ROWS)
    counts, dictionaries = sparse_epoch._column_dictionaries(X[0])
    widths = tuple(sparse_epoch.width_of(int(c)) for c in counts)
    assert {1, sparse_epoch.GATHER} <= set(widths) and max(widths) >= sparse_epoch.BUCKET
    coeffs = jax.random.normal(jax.random.PRNGKey(4), (DIM, len(PATH)), jnp.float32) * 0.1
    planned = sparse_epoch.planned_rows_loss(SPARSE[name], widths)(X, y, w, coeffs, dictionaries)
    for got, want in zip(planned, losses.rows_variant(SPARSE[name])(X, y, w, coeffs)):
        # sums of a dictionary id's 1,700 rows in another order: to rounding of the largest sum
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6 * float(jnp.abs(want).max()))


def test_every_sparse_loss_has_one_row_form_and_a_dense_loss_none():
    assert {losses.rows_variant(loss).name for loss in SPARSE.values()} == {
        "sparse_binary_logistic_rows", "sparse_hinge_rows", "sparse_least_square_rows"
    }
    assert losses.rows_variant(losses.SPARSE_HINGE_LOSS) is losses.rows_variant(losses.SPARSE_HINGE_LOSS)
    with pytest.raises(KeyError):
        losses.rows_variant(losses.BINARY_LOGISTIC_LOSS)


# --- 2. the fleet's routes --------------------------------------------------------------


EPOCHS = [14, 4, None, 12, 14, 14]  # the members' stop epochs; the tol stop's is the reduce form's


@pytest.mark.parametrize("route", ["in_place", "laid_out", "weight_column"])
def test_the_row_forms_members_are_the_reduce_forms_and_their_solo_fits(route, one_device, fits, monkeypatch):
    table = {"in_place": device_table, "laid_out": host_table, "weight_column": lambda: device_table(weights=True)}[route]()
    fleet = lambda: members(weights=route == "weight_column")  # noqa: E731
    want, want_epochs, ticks, handed = fits(fleet(), table)
    assert ticks["fleet.product.reduce"] == 1 and handed == [losses.SPARSE_BINARY_LOGISTIC_LOSS]  # the CPU's form
    solo = np.stack([np.asarray(m.fit(table).coefficient) for m in fleet()])
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    got, got_epochs, ticks, handed = fits(fleet(), table)
    assert ticks["fleet.product.rows"] == 1 and ticks["fleet.product.reduce"] == 0
    assert [loss.name for loss in handed] == ["sparse_binary_logistic_rows"]
    assert ticks["fleet.in_place"] == (route != "laid_out") and ticks["fit.layout.n"] == (route == "laid_out")
    assert got_epochs.tolist() == want_epochs.tolist()
    assert [e for e, want_e in zip(got_epochs.tolist(), EPOCHS) if want_e is not None] == [14, 4, 12, 14, 14]
    assert 0 < got_epochs[2] < 14  # the member tol stops
    assert member_gaps(got, want).max() < 1e-5
    assert member_gaps(got, solo).max() < 1e-5


def test_the_in_place_row_form_is_the_laid_out_row_form(one_device, fits, on_the_chip):
    """In place the plan is made (on the chip's word) and the epochs take the
    planned row form; over a host table the laid-out batches take the plain
    row form: the same members to rounding."""
    in_place, _, ticks, _ = fits(members(), device_table())
    assert ticks["fleet.in_place"] == 1 and ticks["sparse_epoch.planned"] == 1
    laid_out, _, ticks, _ = fits(members(), host_table())
    assert ticks["fleet.in_place"] == 0 and ticks["sparse_epoch.general"] == 1 and ticks["layout.general"] == 3
    assert member_gaps(in_place, laid_out).max() < 1e-5


@pytest.mark.parametrize("kind", ["hinge", "least_square"])
def test_the_other_linear_estimators_take_the_row_form_too(one_device, fits, monkeypatch, kind):
    want, want_epochs, _, _ = fits(members(KINDS[kind]), device_table())
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    got, got_epochs, ticks, handed = fits(members(KINDS[kind]), device_table())
    assert ticks["fleet.product.rows"] == 1 and [loss.name for loss in handed] == [f"sparse_{kind}_rows"]
    np.testing.assert_array_equal(got_epochs, want_epochs)
    assert member_gaps(got, want).max() < 1e-5


def test_on_the_cpu_a_sparse_fleet_in_place_is_the_laid_out_fleet_bit_for_bit(one_device, fits):
    """The reduce form, as it was: the same program over a view of the table
    and over its laid-out copy. Its members are their solo fits to a unit in
    the last place (the vmapped gather-sum and scatter-add of a table whose
    ids collide sum in another order than one member's; the parent's
    laid-out fleet reads the same: tests/test_fleet.py's sparse table is
    exact)."""
    table = device_table()
    got, _, ticks, _ = fits(members(), table)
    assert ticks["fleet.in_place"] == 1 and ticks["fleet.product.reduce"] == 1 and ticks["fit.layout.n"] == 0
    np.testing.assert_array_equal(got, fits(members(), host_table())[0])
    solo = np.stack([np.asarray(m.fit(table).coefficient) for m in members()])
    assert member_gaps(got, solo).max() < 1e-6


def test_several_shards_lay_out_and_take_the_row_form_and_the_fleet_sharded_regime_does_not(mesh8, fits, on_the_chip):
    _, _, ticks, _ = fits(members()[:4] + members()[:4], device_table(rows=4000))
    assert ticks["fleet.product.rows"] == 1 and ticks["fleet.in_place"] == 0 and ticks["sparse_epoch.general"] == 1
    sharded = FitFleet(members()[:4] + members()[:4], shard_fleet_axis=True)
    before = metrics.snapshot()
    sharded.fit(device_table(rows=4000))
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert delta.get("fleet.product.reduce") == 1 and not delta.get("fleet.product.rows")


# --- 3. the plan and the counters -------------------------------------------------------


def test_the_plan_is_made_once_a_fleet_fit(one_device, fits, on_the_chip, monkeypatch):
    made = []
    plan = sparse_epoch.column_plan

    def counted_plan(indices, rows):
        made.append((indices.shape, rows))
        return plan(indices, rows)

    monkeypatch.setattr(sparse_epoch, "column_plan", counted_plan)
    for _ in range(2):
        _, _, ticks, _ = fits(members(), device_table())
        assert ticks["sync.plan.n"] == 1 and ticks["sync.fit.n"] == 1 and ticks["iteration.host_sync"] == 2
    assert made == [((ROWS, NNZ), ROWS)] * 2  # the longest member's 14 epochs wrap past the 10 batches


T = sparse_epoch.DICTIONARY_MAX
LONG_ROWS = 12 * BATCH
READ_EPOCHS = 9  # the longest member's: 4,500 of the 6,000 rows
WIDENED = 13 + 2  # the 300-category field, made to hold T ids in the read rows
MAX_ITERS = {"longest_first": (9, 3, 6), "longest_last": (2, 5, 9), "longest_in_the_middle": (4, 9, 1)}


def partly_read_log():
    """12 batches whose first 9 hold `T` ids in one field and one id in each
    integer field; the 3 after them repeat those rows but for one id more in
    the widened field and a second id in the first integer field."""
    ids, values, labels = click_log(LONG_ROWS, seed=2)
    read = READ_EPOCHS * BATCH
    ids[:, WIDENED] = 13 + (np.arange(LONG_ROWS) % T) * 3
    ids[read:, WIDENED] = ids[: LONG_ROWS - read, WIDENED]
    ids[read + 100, WIDENED] = 13 + T * 3
    ids[read + 400, 0] = 7
    return ids, values, labels


@pytest.mark.parametrize("max_iters", list(MAX_ITERS))
def test_the_fleets_plan_reads_the_rows_its_longest_member_reaches(one_device, fits, monkeypatch, max_iters):
    """Whichever member it is, the longest decides: the plan is `column_plan`
    of the 4,500 rows its 9 epochs read, where the whole table's would gather
    the widened field and hold the first integer field as a dictionary; the
    members are the reduce form's and their solo fits' to rounding."""
    ids, values, labels = partly_read_log()
    table = Table({"features": SparseBatch(DIM, jnp.asarray(ids), jnp.asarray(values)), "label": jnp.asarray(labels)})
    fleet = lambda: [  # noqa: E731
        LogisticRegression().set_reg(reg).set_max_iter(max_iter).set_global_batch_size(BATCH).set_tol(0.0)
        for reg, max_iter in zip(PATH, MAX_ITERS[max_iters])
    ]
    want, want_epochs, _, _ = fits(fleet(), table)
    solo = np.stack([np.asarray(m.fit(table).coefficient) for m in fleet()])
    made, plan = [], sparse_epoch.column_plan

    def kept_plan(indices, rows):
        made.append((rows, plan(indices, rows)))
        return made[-1][1]

    monkeypatch.setattr(sparse_epoch, "column_plan", kept_plan)
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    got, got_epochs, ticks, _ = fits(fleet(), table)
    read = READ_EPOCHS * BATCH
    ((rows, (widths, dictionaries)),) = made
    want_widths, want_dictionaries = plan(jnp.asarray(ids[:read]))
    assert rows == read and widths == want_widths
    np.testing.assert_array_equal(np.asarray(dictionaries), np.asarray(want_dictionaries))
    whole = plan(jnp.asarray(ids))[0]
    assert (widths[0], widths[WIDENED]) == (1, T) and (whole[0], whole[WIDENED]) == (sparse_epoch.BUCKET, sparse_epoch.GATHER)
    assert ticks["sparse_epoch.planned"] == 1 and ticks["fleet.product.rows"] == 1
    assert ticks["sparse_epoch.plan_rows"] == read and ticks["sparse_epoch.table_rows"] == LONG_ROWS
    assert got_epochs.tolist() == want_epochs.tolist() == list(MAX_ITERS[max_iters])
    assert member_gaps(got, want).max() < 1e-5
    assert member_gaps(got, solo).max() < 1e-5


def test_the_counters_say_the_route_and_the_form(one_device, fits, on_the_chip):
    _, _, ticks, _ = fits(members(), device_table())
    assert ticks == {
        "fleet.in_place": 1, "fleet.product.rows": 1, "fleet.product.reduce": 0, "fleet.product.matrix": 0,
        "fleet.fits": 1, "sparse_epoch.planned": 1, "sparse_epoch.general": 0,
        # an epoch's batch: its entries, and those of the columns the plan leaves to the gather
        "sparse_epoch.entries": BATCH * NNZ, "sparse_epoch.entries_gathered": BATCH * GATHERED,
        "sparse_epoch.plan_rows": ROWS, "sparse_epoch.table_rows": ROWS, "sync.plan.n": 1, "sync.fit.n": 1, "iteration.host_sync": 2, "fit.layout.n": 0, "layout.general": 0,
        "dense_epoch.reduce": 0,
    }


def test_on_the_cpu_a_sparse_fleet_counts_the_reduce_form_and_no_plan(one_device, fits):
    _, _, ticks, _ = fits(members(), device_table())
    assert ticks["fleet.in_place"] == 1 and ticks["fleet.product.reduce"] == 1 and not ticks["fleet.product.rows"]
    assert not any(ticks[name] for name in ticks if name.startswith("sparse_epoch.")) and ticks["sync.plan.n"] == 0


def test_what_the_view_admits(one_device):
    ids, values, labels = (jnp.asarray(a) for a in click_log())
    y = labels
    assert optimizer._can_train_in_place((ids, values), y, None, BATCH, np.float32, one_device)
    assert not optimizer._can_train_in_place((ids, values), y, None, 300, np.float32, one_device)  # ragged
    assert not optimizer._can_train_in_place((ids.astype(jnp.float32), values), y, None, BATCH, np.float32, one_device)
    assert not optimizer._can_train_in_place((ids, values[:, :5]), y, None, BATCH, np.float32, one_device)
    assert not optimizer._can_train_in_place((np.asarray(ids), values), y, None, BATCH, np.float32, one_device)
    assert not optimizer._can_train_in_place((ids, values.astype(jnp.bfloat16)), y, None, BATCH, np.float32, one_device)


def test_the_decision_reads_the_table_and_the_loss(on_the_chip):
    ids, values, _ = (jnp.asarray(a) for a in click_log())
    flat = (optimizer.FlatBatches(ids, BATCH), optimizer.FlatBatches(values, BATCH))
    assert optimizer._fleet_rows(flat, losses.SPARSE_HINGE_LOSS)
    assert optimizer._fleet_rows((ids.reshape(10, BATCH, NNZ), values.reshape(10, BATCH, NNZ)), losses.SPARSE_BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_rows((ids, values.astype(jnp.bfloat16)), losses.SPARSE_BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_rows((ids, values), losses.BINARY_LOGISTIC_LOSS)
    assert not optimizer._fleet_rows(values, losses.SPARSE_BINARY_LOGISTIC_LOSS)


def test_the_cpu_decides_the_reduce_form():
    ids, values, _ = (jnp.asarray(a) for a in click_log())
    assert not optimizer._fleet_rows((ids, values), losses.SPARSE_BINARY_LOGISTIC_LOSS)


# --- 4. the programs --------------------------------------------------------------------

SHAPE = jax.ShapeDtypeStruct
NB = ROWS // BATCH
MEMBERS = 5
WIDTHS = (1, 128, 0, 0, 256, 1)  # a constant column, dictionaries, gathered columns


def lowered_rows(plan=WIDTHS):
    loss = losses.rows_variant(losses.SPARSE_BINARY_LOGISTIC_LOSS)

    def fn(ids, values, y_b, w_b, hyper, dictionaries):
        X_b = (optimizer.FlatBatches(ids, BATCH), optimizer.FlatBatches(values, BATCH))
        return optimizer._sgd_fleet_rows_whole_fit_impl(
            X_b, y_b, w_b, loss, hyper, DIM, True, None, plan, dictionaries if plan else None
        )

    nnz = len(WIDTHS)
    return jax.jit(fn).lower(
        SHAPE((ROWS, nnz), np.int32), SHAPE((ROWS, nnz), np.float32), SHAPE((NB, BATCH), np.float32),
        SHAPE((NB, BATCH), np.float32), SHAPE((MEMBERS, 5), np.float32), SHAPE((nnz, 4096), np.int32),
    ).as_text()


@pytest.mark.parametrize("plan", [WIDTHS, None], ids=["planned", "every_column_gathered"])
def test_the_row_program_holds_its_members_minor_and_gathers_rows(plan):
    text = lowered_rows(plan)
    assert f"tensor<{DIM}x{MEMBERS}xf32>" in text and f"tensor<{MEMBERS}x{DIM}xf32>" in text  # the state; the pack's transpose
    assert "slice_sizes = array<i64: 1, 5>" in text.replace(str(MEMBERS), "5")  # an entry's N coefficients: one row
    assert f"tensor<{MEMBERS}x{BATCH}" not in text  # no member-major batch: nothing is vmapped over the members


def lowered_solo(program, loss=losses.SPARSE_BINARY_LOGISTIC_LOSS):
    """The sparse programs a fleet fit of this PR's row form leaves as they
    were: the solo flat fit, general and planned, and the fleet's reduce form
    over laid-out batches (the CPU's)."""
    rows, nnz, batch, dim, members_ = 1200, 6, 200, 5000, 5
    if program in ("_sgd_train_flat", "_sgd_train_flat_planned"):
        plan = WIDTHS if program.endswith("planned") else None

        def fn(ids, values, y, w, init, n, hyper, dictionaries):
            return optimizer._sgd_train_flat(
                (ids, values), y, w, init, loss, batch, True, n, hyper, True,
                plan=plan, dictionaries=dictionaries if plan else None,
            )

        args = (SHAPE((rows, nnz), np.int32), SHAPE((rows, nnz), np.float32), SHAPE((rows,), np.float32),
                SHAPE((rows,), np.float32), SHAPE((dim,), np.float32), SHAPE((), np.int32), SHAPE((5,), np.float32),
                SHAPE((nnz, 4096), np.int32))
        return jax.jit(fn).lower(*args).as_text()
    nb = rows // batch
    carry = (SHAPE((members_, dim), np.float32), SHAPE((members_, dim), np.float32),
             SHAPE((members_,), np.float32), SHAPE((members_,), np.int32))

    def fn(ids, values, y, w, c, crit, hyper):
        return optimizer._sgd_fleet_whole_fit_impl((ids, values), y, w, c, crit, loss, hyper, True, None)

    return jax.jit(fn).lower(
        SHAPE((nb, batch, nnz), np.int32), SHAPE((nb, batch, nnz), np.float32), SHAPE((nb, batch), np.float32),
        SHAPE((nb, batch), np.float32), carry, SHAPE((members_,), np.float32), SHAPE((members_, 5), np.float32),
    ).as_text()


def lowered_solo_told(program, says, monkeypatch):
    """`lowered_solo(program)` traced afresh, with `_fleet_rows` answering
    `says`."""
    jax.clear_caches()  # nothing traced before is found again
    with monkeypatch.context() as patched:
        patched.setattr(optimizer, "_fleet_rows", lambda X_b, loss_func: says)
        return lowered_solo(program)


@pytest.mark.parametrize("program", ["_sgd_train_flat", "_sgd_train_flat_planned", "_sgd_fleet_whole_fit"])
def test_the_sparse_programs_never_take_the_row_form(program, monkeypatch):
    text = lowered_solo_told(program, False, monkeypatch)
    assert lowered_solo_told(program, True, monkeypatch) == text
    assert "tensor<5000x5xf32>" not in text  # no [dim, N] state of lowered_solo's dim and members


def test_a_fleet_under_a_checkpoint_directory_keeps_the_reduce_form(one_device, fits, on_the_chip, monkeypatch, tmp_path):
    monkeypatch.setattr(config, "iteration_checkpoint_dir", str(tmp_path))
    monkeypatch.setattr(config, "iteration_checkpoint_interval", 5)
    _, _, ticks, handed = fits(members(), device_table())
    assert ticks["fleet.product.reduce"] == 1 and set(handed) == {losses.SPARSE_BINARY_LOGISTIC_LOSS}


def test_a_members_coefficient_is_a_row_of_the_one_readback_as_a_solo_fits_is_of_its_own(one_device):
    """A fleet of 100 members of a million coefficients reads back 400 MB:
    a float64 copy of every member was twice that for the host to write, a
    float32 copy as much again. What a promoted version publishes is its own."""
    from flink_ml_tpu.fleet import fleet_model_arrays

    table = device_table()
    models = FitFleet(members()).fit(table)
    solo = members()[0].fit(table)
    assert {m.coefficient.dtype for m in models} == {solo.coefficient.dtype} == {np.dtype(np.float32)}
    assert all(m.coefficient.shape == (DIM,) and np.shares_memory(m.coefficient, models[0].coefficient.base) for m in models)
    (published,) = fleet_model_arrays(models[3])
    assert not np.shares_memory(published, models[3].coefficient)
    np.testing.assert_array_equal(published, models[3].coefficient)
