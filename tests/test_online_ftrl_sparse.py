"""OnlineLogisticRegression over sparse batches: the touched-coordinate FTRL
step against the plain reference of `perf/reference/ftrl-criteo-1tb.py` (the
dense all-coordinate form), the state on the device, the published record,
the online loop's phases and counters, and the stager's pass-through."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import config
from flink_ml_tpu.linalg import DenseVector
from flink_ml_tpu.models.classification import onlinelogisticregression as olr
from flink_ml_tpu.models.classification.onlinelogisticregression import (
    OnlineLogisticRegression,
    OnlineLogisticRegressionModel,
)
from flink_ml_tpu.models.clustering.onlinekmeans import OnlineKMeans, generate_random_model_data
from flink_ml_tpu.obs import tracing
from flink_ml_tpu.parallel import prefetch
from flink_ml_tpu.parallel.iteration import iterate_unbounded
from flink_ml_tpu.table import SparseBatch, StreamTable, Table
from flink_ml_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM, NNZ = 60, 6
EVERY, NEVER = 0, DIM - 1  # a coordinate every row holds, and one no row holds


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "ftrl-criteo-1tb.py")
    spec = importlib.util.spec_from_file_location("ftrl_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _reference()


def rows_of(rng, rows, few=False):
    """Seeded padded-CSR rows: id EVERY in every row, distinct ids inside a
    row, some padding, never NEVER; `few` draws the ids from six coordinates,
    so that a batch is nearly all repeats. Nor ever DIM - 2."""
    pool = np.arange(1, 7) if few else np.arange(1, DIM - 2)
    idx = np.stack([rng.permutation(pool)[: NNZ - 1] for _ in range(rows)]).astype(np.int32)
    idx = np.concatenate([np.full((rows, 1), EVERY, np.int32), idx], axis=1)
    idx[:, 2:][rng.rand(rows, NNZ - 2) < 0.2] = -1
    val = (rng.rand(rows, NNZ) + 0.1).astype(np.float32)
    y = rng.randint(0, 2, rows).astype(np.float32)
    return idx, val, y


def stream_of(chunks, dense=False, device=False):
    tables = []
    for idx, val, y in chunks:
        if device:
            idx, val, y = jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y)
        features = SparseBatch(DIM, idx, val)
        tables.append(Table({"features": features.to_dense() if dense else features, "label": y}))
    return StreamTable.from_batches(tables)


def estimator(batch, w0=None, reg=0.0, elastic_net=0.0):
    w0 = np.zeros(DIM) if w0 is None else w0
    return (
        OnlineLogisticRegression()
        .set_global_batch_size(batch)
        .set_reg(reg)
        .set_elastic_net(elastic_net)
        .set_initial_model_data(Table({"coefficient": [DenseVector(w0)]}))
    )


def reference_states(chunks, batch, w0, reg, elastic_net):
    """The reference's state after every whole global batch of the rows."""
    idx, val, y = (np.concatenate(parts) for parts in zip(*chunks))
    params = {"alpha": 0.1, "beta": 0.1, "reg": reg, "elasticNet": elastic_net}
    hyper = REFERENCE.hyperparameters(params)
    state = (jnp.asarray(w0, jnp.float32), jnp.zeros(DIM, jnp.float32), jnp.zeros(DIM, jnp.float32))
    out = []
    for k in range(len(y) // batch):
        rows = slice(k * batch, (k + 1) * batch)
        state = REFERENCE.batch_step(state, (jnp.asarray(idx[rows]), jnp.asarray(val[rows]), jnp.asarray(y[rows])), hyper)
        out.append(tuple(np.asarray(a) for a in state))
    return out


def counters_over(fn):
    before = metrics.snapshot()
    fn()
    return metrics.snapshot_delta(before, metrics.snapshot())["counters"]


@pytest.mark.parametrize("few", [False, True], ids=["spread", "duplicate-heavy"])
@pytest.mark.parametrize("chunk, batch", [(16, 16), (10, 16), (48, 16)], ids=["whole", "cut-and-joined", "three-a-chunk"])
@pytest.mark.parametrize("reg, elastic_net", [(0.0, 0.0), (0.4, 0.5)], ids=["l1=0", "l1>0,l2>0"])
def test_state_and_coefficient_after_every_batch_are_the_references(reg, elastic_net, chunk, batch, few):
    rng = np.random.RandomState(3)
    chunks = [rows_of(rng, chunk, few) for _ in range(96 // chunk)]
    w0 = np.linspace(-0.5, 0.5, DIM)
    want = reference_states(chunks, batch, w0, reg, elastic_net)
    model = estimator(batch, w0, reg, elastic_net).fit(stream_of(chunks))
    assert model.model_version == 0 and model.training_state() is None
    for version, (w, z, n) in enumerate(want, start=1):
        assert model.process_updates(max_batches=1) == version
        at, state = model.training_state()
        assert at == version
        for got, ref in zip(state, (w, z, n)):
            assert isinstance(got, jax.Array) and got.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-7)
        np.testing.assert_allclose(model.coefficient, w, rtol=2e-5, atol=2e-7)
    assert model.process_updates() == len(want)  # rows short of a batch are not folded
    if reg:
        assert np.sum(want[-1][0] == 0.0) >= 1  # the |z| <= l1 branch was taken
        np.testing.assert_array_equal(model.coefficient == 0.0, want[-1][0] == 0.0)


@pytest.mark.parametrize("device", [False, True], ids=["host-batches", "device-batches"])
def test_a_coordinate_no_row_holds_keeps_w_z_n_to_the_bit(device):
    rng = np.random.RandomState(5)
    chunks = [rows_of(rng, 16) for _ in range(5)]
    only_first = DIM - 2  # held by the first batch alone
    chunks[0][0][:, 1] = only_first
    w0 = np.linspace(-0.5, 0.5, DIM)
    model = estimator(16, w0).fit(stream_of(chunks, device=device))
    model.process_updates(max_batches=1)
    after_first = [np.asarray(a) for a in model.training_state()[1]]
    assert after_first[1][only_first] != 0.0 and after_first[2][only_first] > 0.0
    model.process_updates()
    assert model.model_version == 5
    w, z, n = (np.asarray(a) for a in model.training_state()[1])
    for now, then in zip((w, z, n), after_first):
        assert now[only_first].tobytes() == then[only_first].tobytes()
        assert now[EVERY] != then[EVERY]
    assert w[NEVER].tobytes() == np.float32(w0[NEVER]).tobytes()
    assert z[NEVER] == 0.0 and n[NEVER] == 0.0


@pytest.mark.parametrize("reg, elastic_net", [(0.0, 0.0), (0.4, 0.5)], ids=["l1=0", "l1>0,l2>0"])
def test_sparse_and_densified_rows_give_the_same_model(reg, elastic_net):
    rng = np.random.RandomState(7)
    chunks = [rows_of(rng, 16) for _ in range(6)]
    models = []
    for dense in (False, True):
        model = estimator(16, reg=reg, elastic_net=elastic_net).fit(stream_of(chunks, dense=dense))
        model.process_updates()
        models.append(model.coefficient)
    np.testing.assert_allclose(models[0], models[1], rtol=2e-5, atol=2e-7)


def test_a_dense_stream_gives_the_model_of_the_sweep_to_the_bit():
    """The dense column keeps `_ftrl_step`: float32 state from the same
    float64 start, batch by batch."""
    rng = np.random.RandomState(9)
    X = rng.randn(64, 5)
    y = (X[:, 0] > 0).astype(np.float64)
    stream = StreamTable.from_batches([Table({"features": X[i : i + 16], "label": y[i : i + 16]}) for i in range(0, 64, 16)])
    est = OnlineLogisticRegression().set_global_batch_size(16).set_reg(0.2).set_elastic_net(0.5)
    model = est.set_initial_model_data(Table({"coefficient": [DenseVector(np.full(5, 0.25))]})).fit(stream)
    model.process_updates()
    state = (jnp.asarray(np.full(5, 0.25)), jnp.asarray(np.zeros(5)), jnp.asarray(np.zeros(5)))
    for i in range(0, 64, 16):
        state = olr._ftrl_step(*state, jnp.asarray(X[i : i + 16]), jnp.asarray(y[i : i + 16]), 0.1, 0.1, 0.1, 0.1)
    assert np.asarray(state[0], np.float64).tobytes() == model.coefficient.tobytes()


def test_version_v_is_published_after_exactly_v_batches_and_a_held_record_does_not_change():
    rng = np.random.RandomState(11)
    chunks = [rows_of(rng, 16) for _ in range(6)]
    model = estimator(16).fit(stream_of(chunks))
    records = []
    for version in range(1, 7):
        counted = counters_over(lambda: model.process_updates(max_batches=1))
        assert counted["online.versions"] == 1 and counted["ftrl.batches"] == 1
        record = model._published
        assert record.version == version == model.model_version
        assert isinstance(record.coefficient, jax.Array)
        assert model.model_arrays()[0] is record.coefficient
        records.append((record, np.array(record.coefficient)))
    for record, then in records:  # five more batches were folded since the first was held
        np.testing.assert_array_equal(np.asarray(record.coefficient), then)
    assert not np.array_equal(records[0][1], records[-1][1])
    want = reference_states(chunks, 16, np.zeros(DIM), 0.0, 0.0)
    for (record, then), (w, _, _) in zip(records, want):
        np.testing.assert_allclose(then, w, rtol=2e-5, atol=2e-7)


def test_a_training_state_asked_for_is_the_callers_to_keep():
    """`training_state()` hands out copies: none of them is a buffer of the
    loop's (the next step gives its z and n up), and later batches leave
    them as they were."""
    rng = np.random.RandomState(13)
    model = estimator(16).fit(stream_of([rows_of(rng, 16) for _ in range(4)]))
    model.process_updates(max_batches=2)
    at, kept = model.training_state()
    assert at == 2 and not any(a is b for a in kept for b in model._trained[1])
    then = [np.array(a) for a in kept]
    model.process_updates()
    assert model.training_state()[0] == 4
    for a, b in zip(kept, then):
        assert not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a), b)
    assert not np.array_equal(then[0], np.asarray(model.training_state()[1][0]))


@pytest.mark.parametrize("device", [False, True], ids=["host-batches", "device-batches"])
def test_no_readback_and_no_host_sync_a_batch(device):
    rng = np.random.RandomState(13)
    chunks = [rows_of(rng, 16) for _ in range(8)]
    model = estimator(16).fit(stream_of(chunks, device=device))
    model.process_updates(max_batches=2)  # compiled, the stager's worker running
    counted = counters_over(lambda: model.process_updates(max_batches=5))
    assert model.model_version == 7
    assert not any(name.startswith(("iteration.host_sync", "readback.")) for name in counted)
    assert counted["ftrl.batches"] == 5 and counted["ftrl.rows"] == 80
    assert counted["ftrl.slots_updated"] == 5 * 16 * NNZ  # the touched form, not 5 * DIM
    for phase in ("online.batch", "online.ingest", "online.launch", "online.publish"):
        assert counted[phase + ".n"] == 5 and counted[phase + ".ns"] > 0
    assert counted["online.versions"] == 5 and "flow.shed" not in counted
    assert counted.get("jit.compiles", 0) == 0
    if device:  # a batch that is on the device passes the stager untouched
        assert not any(name.startswith("h2d.") for name in counted)
    else:
        assert counted["h2d.bytes"] > 0
    before = metrics.snapshot()
    assert model.coefficient.dtype == np.float64  # the host asks: one accounted readback
    asked = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert asked["readback.count"] == 1 and asked["iteration.host_sync.model"] == 1


def test_a_dense_batch_counts_a_sweep():
    X = np.random.RandomState(1).randn(32, 4)
    stream = StreamTable.from_batches([Table({"features": X, "label": (X[:, 0] > 0) * 1.0})])
    est = OnlineLogisticRegression().set_global_batch_size(16)
    model = est.set_initial_model_data(Table({"coefficient": [DenseVector(np.zeros(4))]})).fit(stream)
    counted = counters_over(model.process_updates)
    assert counted["ftrl.batches"] == 2 and counted["ftrl.slots_updated"] == 2 * 4


def test_a_resident_batch_passes_the_stager_as_it_is():
    batch = (jnp.arange(6).reshape(3, 2), jnp.ones((3, 2)), jnp.zeros(3))
    counted = counters_over(lambda: prefetch.stage_to_device(batch))
    staged = prefetch.stage_to_device(batch)
    assert staged is batch and not any(name.startswith("h2d.") for name in counted)
    host = (np.arange(6).reshape(3, 2), np.ones(3))
    counted = counters_over(lambda: prefetch.stage_to_device(host))
    assert counted["h2d.bytes"] == sum(a.nbytes for a in host)


def test_a_batch_of_the_global_size_reaches_the_step_as_it_is(monkeypatch):
    rng = np.random.RandomState(17)
    chunks = [tuple(jnp.asarray(a) for a in rows_of(rng, 16)) for _ in range(3)]
    seen = []
    step = olr._ftrl_touched_step
    monkeypatch.setattr(olr, "_ftrl_touched_step", lambda w, z, n, idx, val, y, hyper: seen.append((idx, val, y)) or step(w, z, n, idx, val, y, hyper))
    model = estimator(16).fit(stream_of(chunks))
    model.process_updates()
    assert len(seen) == 3
    for got, given in zip(seen, chunks):
        assert all(a is b for a, b in zip(got, given))


@pytest.mark.parametrize("distinct", [4, 24, 25, 96], ids=["few", "a-quarter", "one-over", "all"])
def test_the_update_is_sized_by_the_batchs_distinct_coordinates(distinct):
    """On either side of a quarter of the batch's entries, and at the
    boundary itself, the state is the reference's."""
    rng = np.random.RandomState(19)
    dim, rows, nnz = 128, 16, 6
    idx = np.resize(rng.permutation(dim)[:distinct], rows * nnz)
    idx = rng.permutation(idx).reshape(rows, nnz).astype(np.int32)
    val = (rng.rand(rows, nnz) + 0.1).astype(np.float32)
    y = rng.randint(0, 2, rows).astype(np.float32)
    assert len(np.unique(idx)) == distinct and rows * nnz // 4 == 24
    state = tuple(jnp.asarray(a, jnp.float32) for a in (rng.randn(dim) * 0.1, rng.randn(dim) * 0.1, rng.rand(dim)))
    hyper = jnp.asarray([0.1, 0.1, 0.05, 0.05], jnp.float32)
    z, n, w = jax.jit(olr._ftrl_touched)(state[1], state[2], state[0], jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y), hyper)
    got = (w, z, n)
    want = REFERENCE.batch_step(state, (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y)), (0.1, 0.1, 0.05, 0.05))
    untouched = np.setdiff1d(np.arange(dim), idx)
    for g, r, before in zip(got, want, state):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-5, atol=2e-7)
        assert np.asarray(g)[untouched].tobytes() == np.asarray(before)[untouched].tobytes()


def test_sparse_chunks_of_unequal_width_are_joined_with_padding():
    rng = np.random.RandomState(23)
    wide, narrow = rows_of(rng, 10), rows_of(rng, 22)
    narrow = (narrow[0][:, :4], narrow[1][:, :4], narrow[2])
    model = estimator(16).fit(stream_of([wide, narrow]))
    assert model.process_updates() == 2
    padded = (np.pad(narrow[0], ((0, 0), (0, 2)), constant_values=-1), np.pad(narrow[1], ((0, 0), (0, 2))), narrow[2])
    want = reference_states([wide, padded], 16, np.zeros(DIM), 0.0, 0.0)
    np.testing.assert_allclose(model.coefficient, want[-1][0], rtol=2e-5, atol=2e-7)


def test_a_stream_that_mixes_kinds_or_sizes_is_refused():
    rng = np.random.RandomState(29)
    idx, val, y = rows_of(rng, 10)
    mixed = StreamTable.from_batches([
        Table({"features": SparseBatch(DIM, idx, val), "label": y}),
        Table({"features": SparseBatch(DIM, idx, val).to_dense(), "label": y}),
    ])
    with pytest.raises(TypeError, match="mixes dense and sparse"):
        estimator(16).fit(mixed).process_updates()
    other = StreamTable.from_batches([Table({"features": SparseBatch(DIM + 1, idx, val), "label": y})])
    with pytest.raises(ValueError, match="sparse batch of size"):
        estimator(16).fit(other).process_updates()


def test_a_device_record_is_saved_loaded_and_served(tmp_path):
    rng = np.random.RandomState(31)
    chunks = [rows_of(rng, 16) for _ in range(3)]
    model = estimator(16).fit(stream_of(chunks))
    model.process_updates()
    consts = model.device_constants()
    assert consts["coefficient"] is model._published.coefficient  # served without a round trip
    data = model.get_model_data()[0].collect()[0]
    assert data["modelVersion"] == 3
    np.testing.assert_array_equal(data["coefficient"].to_array(), model.coefficient)
    model.save(str(tmp_path / "olr"))
    loaded = OnlineLogisticRegressionModel.load(str(tmp_path / "olr"))
    assert loaded.model_version == 3 and isinstance(loaded._published.coefficient, np.ndarray)
    np.testing.assert_array_equal(loaded.coefficient, model.coefficient)
    test = Table({"features": SparseBatch(DIM, *chunks[0][:2]).to_dense()})
    np.testing.assert_array_equal(
        np.asarray(model.transform(test)[0].column("prediction")),
        np.asarray(loaded.transform(test)[0].column("prediction")),
    )


def test_a_sparse_stream_resumes_from_its_checkpoint_to_the_bit(tmp_path):
    rng = np.random.RandomState(37)
    chunks = [rows_of(rng, 16) for _ in range(6)]
    full = estimator(16, reg=0.2, elastic_net=0.5).fit(stream_of(chunks))
    full.process_updates()
    with config.iteration_checkpointing(str(tmp_path / "ckpt")):
        part = estimator(16, reg=0.2, elastic_net=0.5).fit(stream_of(chunks))
        part.process_updates(max_batches=3)
        resumed = estimator(16, reg=0.2, elastic_net=0.5).fit(stream_of(chunks))
        assert resumed.process_updates(max_batches=1) == 3  # the checkpoint, republished
        resumed.process_updates()
    assert resumed.model_version == 6
    assert resumed.coefficient.tobytes() == full.coefficient.tobytes()


def test_the_state_is_ledgered_as_online_state():
    from flink_ml_tpu.obs import memledger

    rng = np.random.RandomState(41)
    model = estimator(16).fit(stream_of([rows_of(rng, 16) for _ in range(2)]))
    model.process_updates()
    state = model._trained[1]  # the loop's own buffers: what training_state() copies
    assert memledger.tracked_nbytes(state) == 3 * DIM * 4
    assert memledger.live_bytes("online.state") >= 3 * DIM * 4


def test_online_kmeans_publishes_a_device_record_of_the_same_form():
    rng = np.random.RandomState(43)
    X = rng.randn(64, 3)
    est = OnlineKMeans().set_global_batch_size(16).set_initial_model_data(generate_random_model_data(2, 3, 1.0, seed=1))
    model = est.fit(StreamTable.from_batches([Table({"features": X[i : i + 16]}) for i in range(0, 64, 16)]))
    counted = counters_over(lambda: model.process_updates(max_batches=3))
    assert counted["online.versions"] == 3 and counted["online.batch.n"] == 3
    assert not any(name.startswith(("iteration.host_sync", "readback.")) for name in counted)
    record = model._published
    assert record.version == 3 and isinstance(record.centroids, jax.Array) and isinstance(record.weights, jax.Array)
    then = np.array(record.centroids)
    model.process_updates()
    np.testing.assert_array_equal(np.asarray(record.centroids), then)  # a held record does not change
    assert model.centroids.dtype == np.float64 and model.centroids.shape == (2, 3)
    assert model.device_constants()["centroids"] is model._published.centroids


def test_a_phase_taken_back_leaves_the_counters_as_they_were():
    def run():
        with tracing.phase("online.test_void") as phase:
            phase.void()
        with tracing.phase("online.test_kept"):
            pass

    counted = counters_over(run)
    assert "online.test_void.n" not in counted and counted["online.test_kept.n"] == 1


@pytest.mark.parametrize("publish", [False, True], ids=["yields", "publishes"])
def test_the_loop_counts_a_batch_phase_for_every_batch_and_none_for_the_streams_end(publish):
    published = []
    hook = (lambda version, state: published.append((version, state))) if publish else None
    counted = counters_over(lambda: list(iterate_unbounded(iter(range(4)), lambda s, b: s + b, 0, publish=hook)))
    for phase in ("online.batch", "online.ingest", "online.launch"):
        assert counted[phase + ".n"] == 4
    if publish:
        assert published == [(1, 0), (2, 1), (3, 3), (4, 6)]
        assert counted["online.publish.n"] == 4 and counted["online.versions"] == 4
    else:
        assert "online.publish.n" not in counted and "online.versions" not in counted
