"""NaiveBayes over indexed categorical fields: the device routes (scatter and
compare) and the host route against a plain float64 reference, the model's
arrays, its round trips and the route counters."""

import jax
import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification import naivebayes as nb
from flink_ml_tpu.models.classification.naivebayes import NaiveBayes, NaiveBayesModel
from flink_ml_tpu.utils import javacodec, metrics

CARDINALITIES = (3, 300, 1000, 100_000, 1)
SMOOTHING = 1.0


def zipf_fields(rows, cardinalities=CARDINALITIES, seed=0, labels=2):
    """Zipf(1)-skewed indices (rank r drawn with p(r) ~ 1/r, spread over
    the field's range) and uniform labels, float32."""
    rng = np.random.RandomState(seed)
    columns = []
    for card in cardinalities:
        rank = np.floor((card + 1.0) ** rng.rand(rows)).astype(np.int64)
        index = (np.clip(rank, 1, card) - 1) * 7919 % card
        columns.append(index)
    X = np.stack(columns, axis=1).astype(np.float32)
    y = rng.randint(0, labels, rows).astype(np.float32)
    return X, y


def reference(X, y, smoothing=SMOOTHING):
    """(labels, counts per field [labels, values], values per field, theta
    per field, pi), all in float64 from np.bincount: shares nothing with the
    program."""
    labels, label_idx = np.unique(y, return_inverse=True)
    L = labels.size
    label_rows = np.bincount(label_idx, minlength=L).astype(np.float64)
    counts, values, thetas = [], [], []
    for j in range(X.shape[1]):
        v, inv = np.unique(X[:, j].astype(np.float64), return_inverse=True)
        c = np.bincount(label_idx * v.size + inv, minlength=L * v.size).reshape(L, v.size)
        counts.append(c)
        values.append(v)
        thetas.append(np.log(c + smoothing) - np.log(label_rows[:, None] + smoothing * v.size))
    n, d = X.shape
    pi = np.log(label_rows * d + smoothing) - np.log(n * d + L * smoothing)
    return labels, counts, values, thetas, pi


def device_table(X, y):
    return Table({"features": jax.device_put(X), "label": jax.device_put(y)})


def route_counts(fit):
    names = ("naivebayes.fields.indexed", "naivebayes.fields.compare", "naivebayes.fields.host")
    before = {name: metrics.get_counter(name) for name in names}
    model = fit()
    return model, {name.rsplit(".", 1)[1]: metrics.get_counter(name) - before[name] for name in names}


@pytest.fixture(scope="module")
def data():
    return zipf_fields(6000, seed=1)


def test_bucket_sizes():
    assert [nb.bucket(n) for n in (1, 3, 16, 17, 18, 19, 305, 1460)] == [1, 3, 16, 18, 18, 20, 320, 1536]
    sizes = {nb.bucket(n) for n in range(1025, 2049)}
    assert len(sizes) == 8 and max(sizes) == 2048
    for n in (17, 1000, 10_131_227, (1 << 24) - 1):
        assert n <= nb.bucket(n) <= n * 1.125
    assert nb.bucket(1 << 24) == 1 << 24


@pytest.mark.parametrize("block_rows", [nb.COUNT_BLOCK_ROWS, 1000])
def test_counts_match_the_reference_exactly(data, block_rows):
    X, y = data
    staged = NaiveBayes._stage(jax.device_put(X), jax.device_put(y))
    assert [f.form for f in staged.plan] == ["range", "range", "scatter", "scatter", "range"]
    out = np.asarray(nb._nb_count(staged.X, staged.y, staged.labels, staged.sorted_cats, staged.plan, block_rows))
    bins = out[2:].reshape(2, -1)
    _, counts, values, _, _ = reference(X, y)
    for f, c, v in zip(staged.plan, counts, values):
        field = bins[:, f.offset : f.offset + f.width]
        np.testing.assert_array_equal(field[:, v.astype(np.int64)], c)
        assert field.sum() == c.sum()  # nothing counted outside the seen values


def test_device_fit_matches_the_reference(data):
    X, y = data
    model, routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert routes == {"indexed": 2, "compare": 3, "host": 0}
    labels, _, values, thetas, pi = reference(X, y)
    np.testing.assert_array_equal(model.labels, labels)
    np.testing.assert_allclose(model.pi, pi, rtol=1e-14)
    assert model.theta_array.dtype == np.float32
    assert model.theta_array.shape == (2, sum(v.size for v in values))
    for j, (v, t) in enumerate(zip(values, thetas)):
        got_values, got_theta = model._feature(j)
        np.testing.assert_array_equal(got_values, v)
        # float32 theta, rounded once from the float64 value
        np.testing.assert_array_equal(got_theta, t.astype(np.float32))
    assert np.diff(model.offsets).tolist() == [v.size for v in values]
    assert values[-1].tolist() == [0.0]  # the one-valued field
    pred = np.asarray(model.transform(Table({"features": X}))[0].column("prediction"))
    scores = pi[None, :] + sum(
        t[:, np.searchsorted(v, X[:, j])].T for j, (v, t) in enumerate(zip(values, thetas))
    )
    np.testing.assert_array_equal(pred, labels[np.argmax(scores, axis=1)])


def test_the_three_routes_give_the_same_arrays(data, monkeypatch):
    X, y = data[0][:3000], data[1][:3000]
    host, host_routes = route_counts(lambda: NaiveBayes().fit(Table({"features": X, "label": y})))
    indexed, _ = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    monkeypatch.setattr(nb, "MATCH_MAX_CATEGORIES", 0)
    scattered, scatter_routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    monkeypatch.setattr(nb, "MATCH_MAX_CATEGORIES", 1 << 17)
    compared, compare_routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert host_routes == {"indexed": 0, "compare": 0, "host": 5}
    assert scatter_routes == {"indexed": 5, "compare": 0, "host": 0}
    assert compare_routes == {"indexed": 0, "compare": 5, "host": 0}
    for model in (indexed, scattered, compared):
        np.testing.assert_array_equal(model.categories, host.categories)
        np.testing.assert_array_equal(model.offsets, host.offsets)
        np.testing.assert_array_equal(model.labels, host.labels)
        np.testing.assert_array_equal(model.pi, host.pi)
        np.testing.assert_array_equal(model.theta_array, host.theta_array)


@pytest.mark.parametrize("value", [-1.0, 2.5, float(1 << 24), float(1 << 25)])
def test_a_value_that_is_no_index_leaves_the_indexed_route(value):
    X, y = zipf_fields(2000, cardinalities=(50, 2000), seed=3)
    X[17, 0] = value
    model, routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert routes == {"indexed": 1, "compare": 1, "host": 0}
    assert value in model._feature(0)[0].tolist()
    X[17, 1] = value  # the wide field is no index now, and has too many values to compare
    model, routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert routes == {"indexed": 0, "compare": 0, "host": 2}
    assert value in model._feature(1)[0].tolist()


def test_the_largest_index_takes_the_indexed_route():
    X, y = zipf_fields(500, cardinalities=(2000,), seed=4)
    X[3, 0] = float((1 << 24) - 1)
    model, routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert routes == {"indexed": 1, "compare": 0, "host": 0}
    assert model.categories[-1] == (1 << 24) - 1


def test_nan_raises_on_the_indexed_route():
    X, y = zipf_fields(500, cardinalities=(2000, 4), seed=5)
    X[9, 0] = np.nan
    with pytest.raises(ValueError, match="Feature column contains null/NaN"):
        NaiveBayes().fit(device_table(X, y))
    X[9, 0] = 1.0
    y[11] = np.nan
    with pytest.raises(ValueError, match="Label column contains null/NaN"):
        NaiveBayes().fit(device_table(X, y))


def test_a_field_of_a_thousand_categories_moves_the_indexed_counter():
    """What the benchmark's generator asks of a program before it builds its
    table: a device fit of one field of 1,000 categories counts a field on
    the indexed route."""
    rows = 4000
    X = (np.arange(rows) % 1000).astype(np.float32)[:, None]
    y = (np.arange(rows) % 2).astype(np.float32)
    _, routes = route_counts(lambda: NaiveBayes().fit(device_table(X, y)))
    assert routes["indexed"] == 1


def test_unseen_value_raises_on_a_wide_model(data):
    X, y = data
    model = NaiveBayes().fit(device_table(X, y))
    bad = X[:50].copy()
    bad[5, 3] = 99_999.0 if 99_999.0 not in model._feature(3)[0] else 99_998.0
    with pytest.raises(ValueError, match="was not seen during training"):
        model.transform(Table({"features": jax.device_put(bad)}))


def test_round_trips_keep_the_arrays(data, tmp_path):
    X, y = data
    model = NaiveBayes().fit(device_table(X, y))
    model.save(str(tmp_path / "nb"))
    loaded = NaiveBayesModel.load(str(tmp_path / "nb"))
    for name in ("theta_array", "categories", "offsets", "pi", "labels"):
        np.testing.assert_array_equal(getattr(loaded, name), getattr(model, name))
    again = NaiveBayesModel().set_model_data(model.get_model_data()[0])
    np.testing.assert_array_equal(again.categories, model.categories)
    np.testing.assert_array_equal(again.theta_array, model.theta_array)
    assert again.theta == model.theta


def test_the_java_codec_reads_what_the_maps_and_the_arrays_write(data):
    import io

    X, y = data
    model = NaiveBayes().fit(device_table(X[:500], y[:500]))
    from_maps = javacodec.encode_naivebayes_model_data(model.theta, model.pi, model.labels)
    from_arrays = javacodec.encode_naivebayes_model_data(
        model.theta_array, model.pi, model.labels, model.categories, model.offsets
    )
    assert from_arrays == from_maps
    read = javacodec.read_naivebayes_model_data(io.BytesIO(from_arrays))
    np.testing.assert_array_equal(read["theta_array"], model.theta_array)
    np.testing.assert_array_equal(read["categories"], model.categories)
    np.testing.assert_array_equal(read["offsets"], model.offsets)
    # a JVM writes its maps in hash order: the reader sorts them
    shuffled = [[dict(reversed(list(m.items()))) for m in row] for row in model.theta]
    read = javacodec.read_naivebayes_model_data(
        io.BytesIO(javacodec.encode_naivebayes_model_data(shuffled, model.pi, model.labels))
    )
    np.testing.assert_array_equal(read["categories"], model.categories)
    np.testing.assert_array_equal(read["theta_array"], model.theta_array)


def test_a_model_saved_as_maps_still_loads(tmp_path):
    from flink_ml_tpu.utils import read_write

    X, y = zipf_fields(300, cardinalities=(4, 6), seed=6)
    model = NaiveBayes().fit(Table({"features": X, "label": y}))
    model.save(str(tmp_path / "nb"))
    read_write.save_model_arrays(
        str(tmp_path / "nb"), theta=np.asarray(model.theta, dtype=object), piArray=model.pi, labels=model.labels
    )
    loaded = NaiveBayesModel.load(str(tmp_path / "nb"))
    np.testing.assert_array_equal(loaded.categories, model.categories)
    np.testing.assert_array_equal(loaded.theta_array, model.theta_array)


def test_labels_the_probe_cannot_list_are_sorted(data):
    X, y = data[0][:2000], data[1][:2000]
    odd = np.where(y > 0, 1000.5, -3.0).astype(np.float32)
    syncs = metrics.get_counter("sync.sort.n")
    model = NaiveBayes().fit(device_table(X, odd))
    assert metrics.get_counter("sync.sort.n") == syncs + 1
    host = NaiveBayes().fit(Table({"features": X, "label": odd}))
    np.testing.assert_array_equal(model.labels, [-3.0, 1000.5])
    np.testing.assert_array_equal(model.categories, host.categories)
    np.testing.assert_array_equal(model.pi, host.pi)
    np.testing.assert_array_equal(model.theta_array, host.theta_array)


def table_maker(name):
    """The benchmark's table maker perf/tables/<name>.py, loaded by path as
    the benchmark loads it."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "perf" / "tables" / (name + ".py")
    spec = importlib.util.spec_from_file_location("perf_tables_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("shards", [1, 2])
def test_the_features_maker_draws_the_pipelines_categories(shards):
    """Where a shard's rows are whole blocks, the features layout holds the
    categories and labels the pipeline's column maker draws from the same key."""
    from jax.sharding import Mesh

    columns, features = table_maker("criteo_indexed"), table_maker("criteo_indexed_features")
    cards = [40, 23, 5000, 1100, 35, 24, 517, 33, 3, 145, 83, 2000, 94, 27, 92, 1306, 10, 52, 73, 4, 547, 18, 15, 181, 105, 72]
    data = {
        "integer_fields": 13, "count_sigmas": [1.0] * 13, "nnz": 13 + len(cards),
        "dim": 13 + sum(c - 1 for c in cards), "cardinalities": cards, "label_arity": 2,
    }
    mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
    rows = 2 * features.BLOCK_ROWS * shards
    key = jax.random.PRNGKey(2147483999)
    want = columns.make(key, rows, data, mesh)
    got = features.make(key, rows, data, mesh)
    matrix = np.asarray(got["features"])
    assert matrix.shape == (rows, len(cards))
    for j, name in enumerate(columns.category_columns(data)):
        np.testing.assert_array_equal(matrix[:, j], np.asarray(want[name]).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(got["label"]), np.asarray(want["label"]))
