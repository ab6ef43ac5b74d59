"""The sparse epoch that gathers only what is sparse (`ops/sparse_epoch.py`),
run here on the suite's CPU devices through the real entry points.

Pinned:

1. `sparse_epoch.planned_loss` gives `losses._sparse(pointwise)`'s loss sum,
   gradient and weight sum for the same rows, to float32 rounding, for the
   three pointwise losses: field-structured rows with constant, small and
   wide columns; rows with no structure (every column keeps the gather, no
   plan); two columns that hash to one id; -1 padding entries and padding
   rows;
2. the plan holds every row of the table it was made from: a column with an
   id the sample missed, and a column the sample saw constant that is not,
   are counted over all their rows, and the sums and the fit are the
   general ones;
3. a whole fit through `LogisticRegression` / `LinearSVC` /
   `LinearRegression` on either program: the same coefficient to 1e-5, a
   ragged last batch, the counters;
3a. the plan reads the rows the fit's epochs reach: a fit that reads part of
   its table plans what `column_plan` of those rows plans, and ids in rows
   no epoch reads (one more id than the widest dictionary, a second id in a
   column that is constant where it is read) change neither its plan nor its
   coefficient; a fit whose epochs wrap plans every row; the counters
   `sparse_epoch.plan_rows` / `.table_rows`; the rows are sliced column by
   column inside the program (`tests/test_layout_exchange.py` compiles it
   for a described v5e: no copy of them);
4. `sparse_epoch.can_plan` turns away, one by one, everything the plan is
   not made for, and an unpatched fit on the CPU keeps `_sparse`
   (`sparse_epoch.general`) to the bit;
5. a dictionary's width is a power of two, so tables whose distinct counts
   differ share a compiled train program, and a column has eight classes at
   most.

A CPU array is on no TPU, so `can_plan` never admits one here: the tests that
need the plan taken tell `mesh_lib.on_tpu` to say what an array on the chip
says (as `tests/test_dense_one_pass.py` does for its kernel). The planned
loss is plain jax.numpy and runs anywhere.
"""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification.linearsvc import LinearSVC
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.models.regression.linearregression import LinearRegression
from flink_ml_tpu.ops import losses, optimizer, sparse_epoch
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch
from flink_ml_tpu.utils import metrics

LOSSES = {
    loss.name: loss
    for loss in (losses.SPARSE_BINARY_LOGISTIC_LOSS, losses.SPARSE_HINGE_LOSS, losses.SPARSE_LEAST_SQUARE_LOSS)
}
ESTIMATORS = {
    "sparse_binary_logistic": LogisticRegression, "sparse_hinge": LinearSVC, "sparse_least_square": LinearRegression,
}
DIM = 50_000
BATCH = 256
WIDE = sparse_epoch.DICTIONARY_MAX + 1200  # ids a column must hold to keep the gather


def fields(rows, seed=32):
    """Rows written field by field: two constant columns, a column of three
    ids, one of 300, and two that spread over the dimension."""
    rng = np.random.default_rng(seed)
    return np.stack(
        [
            np.full(rows, 0), np.full(rows, 1), rng.integers(2, 5, rows), rng.integers(5, 305, rows),
            305 + rng.permutation(max(rows, WIDE))[:rows] % (DIM - 305), rng.integers(305, DIM, rows),
        ],
        axis=1,
    ).astype(np.int32)


def bag(rows, seed=33):
    """Rows with no structure: every column holds whatever id the row drew."""
    return np.sort(np.random.default_rng(seed).integers(0, DIM, (rows, 6)), axis=1).astype(np.int32)


def one_id_in_two_columns(rows):
    """The small column's ids are the constant columns' too, and the wide
    columns share ids with it and with each other."""
    indices = fields(rows)
    indices[:, 2] = np.random.default_rng(1).integers(0, 3, rows)
    indices[::7, 4] = indices[::7, 2]
    indices[::5, 5] = indices[::5, 4]
    return indices


def padded(rows):
    """-1 entries scattered over every class of column, and a tail of rows
    that are padding alone, as `_stage_flat` pads a ragged table."""
    indices = fields(rows)
    holes = np.random.default_rng(2).random(indices.shape) < 0.1
    indices[holes] = -1
    indices[-40:] = -1
    return indices


ROWS = WIDE
CASES = {"fields": fields, "one_id_in_two_columns": one_id_in_two_columns, "padding_entries_and_rows": padded}


def batch_of(indices, seed=4):
    """(X, y, w, coeff) of the table's LAST batch: values that are no ones,
    NaN under the padding (masked, as `sparse_dot` masks it), weight 0 for
    rows of padding alone."""
    rng = np.random.default_rng(seed)
    rows = slice(len(indices) - BATCH, len(indices))
    ids = indices[rows]
    values = np.where(ids >= 0, rng.random(ids.shape) + 0.5, np.nan).astype(np.float32)
    y = (rng.random(BATCH) > 0.5).astype(np.float32)
    w = (rng.random(BATCH) * (ids >= 0).any(axis=1)).astype(np.float32)
    coeff = (rng.standard_normal(DIM) * 0.3).astype(np.float32)
    return (jnp.asarray(ids), jnp.asarray(values)), jnp.asarray(y), jnp.asarray(w), jnp.asarray(coeff)


def assert_same_sums(want, got):
    for name, a, b in zip(("loss sum", "gradient", "weight sum"), want, got):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(b)), name
        assert np.max(np.abs(a - b)) <= 2e-6 * np.max(np.abs(a)), name


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("loss", LOSSES)
def test_planned_loss_gives_the_general_sums(loss, case):
    indices = CASES[case](ROWS)
    widths, dictionaries = sparse_epoch.column_plan(jnp.asarray(indices))
    assert widths == (1, 1, sparse_epoch.BUCKET, 512, 0, 0)
    X, y, w, coeff = batch_of(indices)
    want = LOSSES[loss](X, y, w, coeff)
    got = jax.jit(sparse_epoch.planned_loss(LOSSES[loss], widths))(X, y, w, coeff, dictionaries)
    assert_same_sums(want, got)


def test_rows_with_no_structure_have_no_plan():
    assert sparse_epoch.column_plan(jnp.asarray(bag(ROWS))) == (None, None)
    assert sparse_epoch.column_plan(jnp.full((64, 3), -1, jnp.int32)) == (None, None)  # padding alone


STRAYS = {
    # a category the sample missed: the 300-id column holds an id its candidates lack
    "an_id_the_sample_missed": (3, 4711),
    # a column the sample saw constant is not
    "a_constant_column_that_is_not": (1, 7),
}
UNSAMPLED = (BATCH + 100, 3 * BATCH + 17)  # rows past a sample of 64


@pytest.fixture
def a_sample_of_64_rows(monkeypatch):
    monkeypatch.setattr(sparse_epoch, "SAMPLE_ROWS", 64)
    jax.clear_caches()  # `_column_dictionaries` may have been traced at this shape with the full sample
    yield
    jax.clear_caches()


def strayed(stray):
    """Five batches whose columns 64 rows hold in full, but for one id in
    two rows the sample does not see."""
    indices = fields(5 * BATCH)
    indices[:, 3] = 5 + indices[:, 3] % 4
    indices[:, 4:] = indices[:, 2:3] + 10
    column, stranger = STRAYS[stray]
    indices[UNSAMPLED, column] = stranger
    return indices, column


@pytest.mark.parametrize("stray", STRAYS)
@pytest.mark.parametrize("loss", LOSSES)
def test_a_column_the_sample_does_not_hold_is_counted_over_all_its_rows(loss, stray, a_sample_of_64_rows):
    indices, column = strayed(stray)
    seen, _ = sparse_epoch._distinct(jnp.asarray(indices[:64].T))
    assert np.asarray(seen).tolist() == [1, 1, 3, 4, 3, 3]  # what the sample saw
    counts, dictionaries = sparse_epoch._column_dictionaries(jnp.asarray(indices))
    assert np.asarray(counts).tolist() == [n + (j == column) for j, n in enumerate((1, 1, 3, 4, 3, 3))]
    assert STRAYS[stray][1] in np.asarray(dictionaries[column]).tolist()
    widths, dictionaries = sparse_epoch.column_plan(jnp.asarray(indices))
    assert widths == (1, 128 if column == 1 else 1, 128, 128, 128, 128)
    X, y, w, coeff = batch_of(indices[: UNSAMPLED[0] + 1 + BATCH // 2])  # a batch that holds the stranger
    assert np.any(np.asarray(X[0][:, column]) == STRAYS[stray][1])
    want = LOSSES[loss](X, y, w, coeff)
    got = jax.jit(sparse_epoch.planned_loss(LOSSES[loss], widths))(X, y, w, coeff, dictionaries)
    assert_same_sums(want, got)


T = sparse_epoch.DICTIONARY_MAX


@pytest.mark.parametrize(
    "count, width",
    [(0, 0), (1, 1), (2, 128), (128, 128), (129, 256), (290, 512), (300, 512), (583, 1024), (1460, 2048),
     (2173, T), (3194, T), (T, T), (T + 1, 0), (5652, 0), (65536, 0)],
)
def test_a_dictionarys_width_is_a_power_of_two(count, width):
    assert sparse_epoch.width_of(count) == width


def test_a_column_has_eight_classes_at_most():
    """What bounds the train programs a log's partitions can ask for: a
    column's width is one of gather, constant and six powers of two, whatever
    its count."""
    widths = {sparse_epoch.width_of(count) for count in range(2 * T)}
    assert widths == {0, 1, 128, 256, 512, 1024, 2048, 4096}
    assert T == 4096 and T % sparse_epoch.BUCKET == 0


def test_the_plan_samples_a_large_table_and_counts_all_its_rows():
    rows = 3 * sparse_epoch.SAMPLE_ROWS + 5
    every_row = jnp.arange(rows, dtype=jnp.int32)
    # a constant column, one id a row, padding alone, four ids, four ids but for the LAST row's,
    # and a column whose sample holds T ids and whose last row holds one more
    late = (every_row % 4).at[-1].set(9)
    wide = ((every_row // 3) % T).at[-1].set(T + 7)
    table = jnp.stack(
        [jnp.zeros(rows, jnp.int32), every_row, jnp.full(rows, -1, jnp.int32), every_row % 4, late, wide], axis=1
    )
    counts, dictionaries = sparse_epoch._column_dictionaries(table)
    assert np.asarray(counts).tolist() == [1, sparse_epoch.SAMPLE_ROWS, 0, 4, 5, T + 1]
    assert dictionaries.shape == (6, T)
    assert np.asarray(dictionaries[0, :2]).tolist() == [0, sparse_epoch.NO_ID]
    assert np.asarray(dictionaries[1, :3]).tolist() == [0, 1, 2]
    assert np.all(np.asarray(dictionaries[2]) == sparse_epoch.NO_ID)
    assert np.asarray(dictionaries[3, :5]).tolist() == [0, 1, 2, 3, sparse_epoch.NO_ID]
    assert np.asarray(dictionaries[4, :6]).tolist() == [0, 1, 2, 3, 9, sparse_epoch.NO_ID]
    assert sparse_epoch.column_plan(table)[0] == (1, 0, 0, 128, 128, 0)


# --- through the estimators ------------------------------------------------------


def one_shard():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:1])


def counted(fit):
    before = metrics.snapshot()
    result = fit()
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    keep = ("sparse_epoch.", "iteration.host_sync", "jit.compiles")
    return result, {k: v for k, v in counters.items() if k.startswith(keep)}


def table_of(indices, seed=6):
    rng = np.random.default_rng(seed)
    values = (rng.random(indices.shape) + 0.5).astype(np.float32)
    label = (rng.random(len(indices)) > 0.5).astype(np.float32)
    features = SparseBatch(DIM, jax.device_put(indices), jax.device_put(values))
    return Table({"features": features, "label": jax.device_put(label)})


RAGGED = ROWS - 100  # 5,196 rows: the last batch is 76 rows and 180 of padding
ONE_PASS = -(-RAGGED // BATCH)  # 21 epochs: every batch once, the ragged last one too, so the plan reads every row


def estimator_fit(loss, indices, max_iter=ONE_PASS):
    stage = ESTIMATORS[loss]().set_max_iter(max_iter).set_global_batch_size(BATCH).set_learning_rate(0.5).set_tol(0.0)
    with mesh_lib.use_mesh(one_shard()):
        return np.asarray(stage.fit(table_of(indices)).coefficient)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_through_the_estimator_is_the_same_fit_on_either_program(loss, case, monkeypatch):
    indices = CASES[case](RAGGED)
    general, ticked = counted(lambda: estimator_fit(loss, indices))
    entries = BATCH * 6  # of one epoch's batch
    assert ticked["sparse_epoch.general"] == 1 and ticked["iteration.host_sync"] == 1
    assert ticked["sparse_epoch.entries"] == ticked["sparse_epoch.entries_gathered"] == entries
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    planned, ticked = counted(lambda: estimator_fit(loss, indices))
    assert ticked["sparse_epoch.planned"] == 1 and "sparse_epoch.general" not in ticked
    assert ticked["iteration.host_sync"] == 2 and ticked["iteration.host_sync.plan"] == 1
    assert ticked["sparse_epoch.entries"] == entries
    assert ticked["sparse_epoch.entries_gathered"] == entries // 3
    assert np.any(planned != 0)
    assert np.max(np.abs(planned - general)) <= 1e-5 * np.max(np.abs(general))


@pytest.mark.parametrize("loss", LOSSES)
def test_rows_with_no_structure_run_the_general_program_to_the_bit(loss, monkeypatch):
    indices = bag(RAGGED)
    general, _ = counted(lambda: estimator_fit(loss, indices))
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    asked, ticked = counted(lambda: estimator_fit(loss, indices))
    # the plan was asked for (a sync) and found nothing to take off the gather
    assert ticked["sparse_epoch.general"] == 1 and ticked["iteration.host_sync.plan"] == 1
    assert ticked["sparse_epoch.entries"] == ticked["sparse_epoch.entries_gathered"]
    np.testing.assert_array_equal(asked, general)


@pytest.mark.parametrize("stray", STRAYS)
@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_over_a_table_the_sample_did_not_foresee_is_the_general_fit(loss, stray, monkeypatch, a_sample_of_64_rows):
    """Two unsampled batches hold a category the 64 sampled rows lack: its
    column's dictionary holds it all the same, and the coefficient is the
    general program's."""
    indices, _ = strayed(stray)
    general, _ = counted(lambda: estimator_fit(loss, indices, max_iter=10))
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    planned, ticked = counted(lambda: estimator_fit(loss, indices, max_iter=10))
    assert ticked["sparse_epoch.planned"] == 1
    assert ticked["sparse_epoch.entries"] == BATCH * 6 and "sparse_epoch.entries_gathered" not in ticked
    assert np.any(planned != general)
    assert np.max(np.abs(planned - general)) <= 1e-5 * np.max(np.abs(general))


def test_tables_whose_counts_differ_share_one_program(monkeypatch):
    """Partitions of one log, or another seed or day: 300, 290 and 400
    distinct ids in a column are all a dictionary of 512, and the second and
    third fit compile nothing; 600 are one of 1,024, a second program."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    tables = []
    for seed, distinct in ((40, 300), (41, 290), (42, 400), (43, 600)):
        indices = fields(RAGGED, seed=seed)
        indices[:, 3] = 5 + np.random.default_rng(seed).integers(0, distinct, RAGGED)
        tables.append(indices)
    plans = [sparse_epoch.column_plan(jnp.asarray(indices))[0] for indices in tables]
    assert plans[0] == plans[1] == plans[2] == (1, 1, 128, 512, 0, 0) and plans[3] == (1, 1, 128, 1024, 0, 0)
    jax.clear_caches()
    _, cold = counted(lambda: estimator_fit("sparse_binary_logistic", tables[0]))
    assert cold["jit.compiles"] >= 1
    for indices in tables[1:3]:
        _, warm = counted(lambda: estimator_fit("sparse_binary_logistic", indices))
        assert "jit.compiles" not in warm and warm["sparse_epoch.planned"] == 1
    _, wider = counted(lambda: estimator_fit("sparse_binary_logistic", tables[3]))
    assert wider["jit.compiles"] == 1  # the train program alone: the plan's own is shaped by the table


def test_the_flat_program_keeps_its_name_with_a_plan(monkeypatch):
    """`perf/configs/lr-sparse-1m.json` finds the train program in a trace as
    `jit__sgd_train_flat`: the planned loss is inside it."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    lowered = []

    def on_lowering(event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(on_lowering)
    try:
        _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", fields(RAGGED)))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_lowering)
    assert ticked["sparse_epoch.planned"] == 1
    assert "jit(_sgd_train_flat)" in lowered and "jit(_column_dictionaries)" in lowered


# --- the rows the fit reaches ----------------------------------------------------

READ_EPOCHS = 16
READ = READ_EPOCHS * BATCH  # 4,096 rows the fit's epochs read of a table of 24 batches
BOUNDARY = (3, 4711)  # an id of the 300-id column in the LAST read row alone
UNREAD = {
    # column 4 holds T ids in the read rows, and one more in an unread row
    "an_id_past_the_widest_dictionary": (4, DIM - 1, (1, 1, 128, 512, T, T), (1, 1, 128, 512, 0, T)),
    # column 0 holds id 0 in the read rows, and id 7 in an unread row
    "a_second_id_in_a_constant_column": (0, 7, (1, 1, 128, 512, T, T), (128, 1, 128, 512, T, T)),
}


def partly_read(unread):
    """24 batches whose first 16 hold, but for one id in the last of their
    rows, what the 8 after them hold, and one id more in an unread row."""
    indices = fields(24 * BATCH)
    indices[:, 5] = 305 + np.random.default_rng(5).permutation(DIM - 305)[: 24 * BATCH] % T
    indices[READ:, 4:] = indices[: 24 * BATCH - READ, 4:]
    indices[READ - 1, BOUNDARY[0]] = BOUNDARY[1]
    indices[READ:, BOUNDARY[0]] = indices[: 24 * BATCH - READ, BOUNDARY[0]]
    column, stranger, *_ = UNREAD[unread]
    indices[READ + 300, column] = stranger
    return indices


def planned_fit(loss, indices, max_iter):
    """(the coefficient, the plan the flat program was handed, the counters)
    of a planned fit through the estimator."""
    handed = []
    original = optimizer._sgd_train_flat

    def spy(*args, **kwargs):
        handed.append(args[12:14])
        return original(*args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mesh_lib, "on_tpu", lambda arr: True)
        patch.setattr(optimizer, "_sgd_train_flat", spy)
        coefficient, ticked = counted(lambda: estimator_fit(loss, indices, max_iter=max_iter))
    (plan,) = handed
    return coefficient, plan, ticked


def assert_same_plan(got, want):
    assert got[0] == want[0]
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("unread", UNREAD)
@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_that_reads_part_of_its_table_plans_the_rows_it_reads(loss, unread):
    """A fit of 16 epochs over 24 batches reads the first 16: its plan is
    `column_plan` of those 4,096 rows (the id in the last of them included),
    the ids after them change nothing, and the coefficient is the general
    program's."""
    indices = partly_read(unread)
    _, _, read_widths, table_widths = UNREAD[unread]
    general = estimator_fit(loss, indices, max_iter=READ_EPOCHS)
    planned, plan, ticked = planned_fit(loss, indices, READ_EPOCHS)
    want = sparse_epoch.column_plan(jnp.asarray(indices[:READ]))
    assert_same_plan(plan, want)
    assert plan[0] == read_widths and BOUNDARY[1] in np.asarray(plan[1][BOUNDARY[0]]).tolist()
    assert sparse_epoch.column_plan(jnp.asarray(indices))[0] == table_widths  # what the whole table would plan
    assert ticked["sparse_epoch.planned"] == 1
    assert ticked["sparse_epoch.plan_rows"] == READ and ticked["sparse_epoch.table_rows"] == 24 * BATCH
    assert np.max(np.abs(planned - general)) <= 1e-5 * np.max(np.abs(general))


@pytest.mark.parametrize("loss", LOSSES)
def test_a_fit_whose_epochs_wrap_plans_every_row(loss):
    """30 epochs over 24 batches read every batch, the 8 last once: the plan
    is the whole table's, its widest column a gather again."""
    indices = partly_read("an_id_past_the_widest_dictionary")
    general = estimator_fit(loss, indices, max_iter=30)
    planned, plan, ticked = planned_fit(loss, indices, 30)
    assert_same_plan(plan, sparse_epoch.column_plan(jnp.asarray(indices)))
    assert plan[0] == UNREAD["an_id_past_the_widest_dictionary"][3]
    assert ticked["sparse_epoch.plan_rows"] == ticked["sparse_epoch.table_rows"] == 24 * BATCH
    assert np.max(np.abs(planned - general)) <= 1e-5 * np.max(np.abs(general))


@pytest.mark.parametrize(
    "max_iter, plan_rows",
    [(1, BATCH), (4, 4 * BATCH), (READ_EPOCHS, READ), (24, 24 * BATCH), (25, 24 * BATCH), (60, 24 * BATCH)],
)
def test_the_plan_counts_the_rows_it_read_and_the_tables(max_iter, plan_rows, monkeypatch):
    """One tick of each a planned fit: the rows the plan read, the staged
    table's; a ragged table's are its padded rows, which its last batch
    reaches."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", fields(24 * BATCH), max_iter=max_iter))
    assert ticked["sparse_epoch.plan_rows"] == plan_rows and ticked["sparse_epoch.table_rows"] == 24 * BATCH
    _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", fields(RAGGED), max_iter=max_iter))
    assert ticked["sparse_epoch.plan_rows"] == min(max_iter, ONE_PASS) * BATCH
    assert ticked["sparse_epoch.table_rows"] == ONE_PASS * BATCH


def test_a_plan_that_finds_nothing_counts_the_rows_it_read(monkeypatch):
    """20 batches of rows with no structure hold more than `T` ids in every
    column: the plan read them and left every column to the gather."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", bag(RAGGED), max_iter=20))
    assert ticked["sparse_epoch.general"] == 1 and "sparse_epoch.planned" not in ticked
    assert ticked["sparse_epoch.plan_rows"] == 20 * BATCH and ticked["sparse_epoch.table_rows"] == ONE_PASS * BATCH


def test_an_unplanned_fit_counts_no_rows():
    _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", fields(RAGGED), max_iter=4))
    assert ticked["sparse_epoch.general"] == 1
    assert "sparse_epoch.plan_rows" not in ticked and "sparse_epoch.table_rows" not in ticked


def lowered_plan(table, nnz, **rows):
    return jax.jit(sparse_epoch._column_dictionaries, static_argnames=("rows",)).lower(
        jax.ShapeDtypeStruct((table, nnz), jnp.int32), **rows
    ).as_text()


def test_the_plan_cuts_each_columns_read_rows_inside_its_loop():
    """The rows a fit reads (more than the sample) are cut from each column
    inside the loop over the columns, where the compiler folds the cut into
    the column's slice (`tests/test_layout_exchange.py` compiles it for a
    described v5e: no copy); the table's rows of several columns are its
    transpose alone (on the chip a view of the rows-minor table), and no op
    takes the read rows of all columns as one array."""
    table, nnz, rows = 3 * sparse_epoch.SAMPLE_ROWS, 6, 2 * sparse_epoch.SAMPLE_ROWS
    text = lowered_plan(table, nnz, rows=rows)
    assert f"tensor<{rows}x{nnz}xi32>" not in text and f"tensor<{nnz}x{rows}xi32>" not in text
    made = re.findall(rf"= stablehlo\.(\w+) .*-> tensor<(\d+)x{table}xi32>", text)
    assert sorted(made) == [("dynamic_slice", "1"), ("transpose", str(nnz))]
    assert f"stablehlo.slice %arg0 [0:{rows}] : (tensor<{table}xi32>) -> tensor<{rows}xi32>" in text


# sha256 of the StableHLO text of the plan over a 196,608 x 6 table before the
# plan read only the rows a fit reaches (read with `lowered_plan` over that package)
WHOLE_TABLE_PLAN = "5e7602a4ac4549b7c247b532b26f2f5bbc4ae37e277fadea3319800660ee7363"


@pytest.mark.parametrize("rows", [{}, {"rows": 3 * sparse_epoch.SAMPLE_ROWS}], ids=["by_default", "every_row_reached"])
def test_a_plan_over_every_row_lowers_to_the_text_it_always_had(rows):
    """A fit whose epochs reach every row (the two solo sparse cells' fits
    make one whole pass) runs the plan program it ran before."""
    text = lowered_plan(3 * sparse_epoch.SAMPLE_ROWS, 6, **rows)
    assert hashlib.sha256(text.encode()).hexdigest() == WHOLE_TABLE_PLAN


# --- who takes it ----------------------------------------------------------------


def admitted_table(columns=6, dtype=jnp.float32):
    return jnp.zeros((2048, columns), jnp.int32), jnp.zeros((2048, columns), dtype)


def two_shards():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:2])


NO_POINTWISE = losses.LossFunc("no_pointwise", losses.SPARSE_BINARY_LOGISTIC_LOSS.fn, None, True)

TURNED_AWAY = {
    "two_shards": lambda: (admitted_table(), losses.SPARSE_BINARY_LOGISTIC_LOSS, two_shards()),
    "dense_rows": lambda: (jnp.zeros((2048, 16), jnp.float32), losses.BINARY_LOGISTIC_LOSS, one_shard()),
    "bfloat16_values": lambda: (admitted_table(dtype=jnp.bfloat16), losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard()),
    "ids_on_the_host": lambda: (
        (np.zeros((2048, 6), np.int32), jnp.zeros((2048, 6), jnp.float32)), losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard(),
    ),
    "a_loss_without_pointwise": lambda: (admitted_table(), NO_POINTWISE, one_shard()),
    "a_dense_loss": lambda: (admitted_table(), losses.BINARY_LOGISTIC_LOSS, one_shard()),
    "float64_values": lambda: (
        (jnp.zeros((2048, 6), jnp.int32), np.zeros((2048, 6), np.float64)), losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard(),
    ),
}


def test_a_padded_csr_table_on_one_shard_of_the_chip_is_admitted(monkeypatch):
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    for loss in LOSSES.values():
        assert sparse_epoch.can_plan(admitted_table(), loss, one_shard())
        assert sparse_epoch.can_plan(admitted_table(1000), loss, one_shard())  # a row of any width


@pytest.mark.parametrize("name", TURNED_AWAY)
def test_everything_else_keeps_the_general_program(name, monkeypatch):
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    assert not sparse_epoch.can_plan(*TURNED_AWAY[name]())


def test_a_table_off_the_tpu_keeps_the_general_program():
    assert not mesh_lib.on_tpu(admitted_table()[0])
    assert not sparse_epoch.can_plan(admitted_table(), losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard())


def test_an_unpatched_fit_on_the_cpu_is_the_general_program_without_a_plan():
    """What the bit-parity contracts between solo, fleet, chunked, stream and
    whole-fit programs stand on: on the CPU every sparse fit is `sparse_dot`
    and the scatter-add, no plan is asked for, and the flat program is given
    neither widths nor dictionaries."""
    indices = fields(RAGGED)
    handed = []
    original = optimizer._sgd_train_flat

    def spy(*args, **kwargs):
        handed.append(args[12:])
        return original(*args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(optimizer, "_sgd_train_flat", spy)
        _, ticked = counted(lambda: estimator_fit("sparse_binary_logistic", indices))
    assert handed == [(None, None)]
    assert ticked["sparse_epoch.general"] == 1 and ticked["iteration.host_sync"] == 1
    assert "iteration.host_sync.plan" not in ticked
    sgd = SGD(max_iter=ONE_PASS, learning_rate=0.5, global_batch_size=BATCH, tol=0.0)
    table = table_of(indices)
    features = table.column("features")
    coeff, _, epochs = sgd.optimize(
        np.zeros(DIM), (features.indices, features.values), table.column("label"), None,
        losses.SPARSE_BINARY_LOGISTIC_LOSS, one_shard(),
    )
    assert epochs == ONE_PASS
    np.testing.assert_array_equal(coeff, estimator_fit("sparse_binary_logistic", indices))
