"""The blocked Lloyd fit against the plain reference (perf/reference/
kmeans-mnist8m.py, found by path: it imports nothing of the program), on
seeded structured data on the CPU; that nothing of n x k elements exists in
the fit's program; and that a device-born float32 table is trained in place.

The block size comes from shapes (`kmeans._block_rows`); the tests that need
several blocks at a small size lower `kmeans._BLOCK_ELEMENTS` for themselves
and use row counts no other test uses, since a compiled program is kept by
shape.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "kmeans-mnist8m.py")
    spec = importlib.util.spec_from_file_location("kmeans_mnist8m_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def structured(n, d, bases, seed):
    """n rows of d whole numbers 0..255: `bases` base rows, about a fifth of
    each lit, and every row a base with noise on its lit coordinates (the
    two levels of the benchmark's table; whole numbers, so sums are exact)."""
    rng = np.random.RandomState(seed)
    lit = rng.rand(bases, d) < 0.3
    lit[:, 0] = True
    base = np.where(lit, rng.randint(40, 216, (bases, d)), 0)
    rows = base[np.arange(n) % bases]
    noise = rng.randint(-12, 13, (n, d))
    return np.where(rows > 0, rows + noise, 0).astype(np.float32)


def one_device_mesh():
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:1])


def reference_model(X, k, seed, max_iter):
    arrays = {"features": jax.device_put(jnp.asarray(X), jax.devices()[0])}
    params = {"k": k, "seed": seed, "maxIter": max_iter}
    packed, iterations, _ = reference.fit(arrays, {}, params)
    assert iterations == max_iter
    return reference.unpack(packed, k)


def assert_is_the_references(model, X, k, seed, max_iter):
    centroids, counts = reference_model(X, k, seed, max_iter)
    # whole-number rows: every sum is exact in float32, so the two differ only
    # where a row sits between two centroids to the last bit, and here none does
    np.testing.assert_array_equal(model.weights, counts)
    np.testing.assert_allclose(model.centroids, centroids, rtol=1e-6, atol=1e-4)
    assert model.weights.sum() == X.shape[0]


def few_rows_a_block(monkeypatch, k, d, rows=256):
    monkeypatch.setattr(km, "_BLOCK_ELEMENTS", rows * (k + d))


# n, k, d, bases, rows a block (None: the program's own choice, one block here)
CASES = [
    pytest.param(1003, 3, 5, 6, None, id="n-below-one-block-k3-d5"),
    pytest.param(1037, 3, 5, 6, 256, id="n-not-a-multiple-of-the-block-k3-d5"),
    pytest.param(1291, 64, 5, 90, 256, id="tail-k64-d5"),
    pytest.param(1024 + 7, 3, 784, 5, 512, id="tail-k3-d784"),
    pytest.param(777, 64, 784, 80, 256, id="tail-k64-d784"),
    pytest.param(1536 + 1, 64, 784, 80, 512, id="one-row-tail-k64-d784"),
]


@pytest.mark.parametrize("n, k, d, bases, block", CASES)
def test_one_shard_fit_is_the_references(monkeypatch, n, k, d, bases, block):
    if block is not None:
        few_rows_a_block(monkeypatch, k, d, block)
        assert km._num_blocks(n, k, d) > 1 and n % km._block_rows(n, k, d)
    else:
        assert km._num_blocks(n, k, d) == 1
    X = structured(n, d, bases, seed=n)
    with mesh_lib.use_mesh(one_device_mesh()):
        model = KMeans().set_k(k).set_seed(7).set_max_iter(4).fit(Table({"features": X}))
    assert_is_the_references(model, X, k, 7, 4)


@pytest.mark.parametrize(
    "n, k, d, bases, block",
    [
        pytest.param(1043, 3, 5, 6, None, id="padded-to-the-shards-k3-d5"),
        pytest.param(8 * 300 + 5, 64, 784, 80, 128, id="blocks-on-every-shard-k64-d784"),
    ],
)
def test_eight_shard_fit_is_the_references(monkeypatch, n, k, d, bases, block):
    assert len(jax.devices()) == 8 and n % 8
    if block is not None:
        few_rows_a_block(monkeypatch, k, d, block)
        assert km._num_blocks(-(-n // 8), k, d) > 1
    X = structured(n, d, bases, seed=n)
    model = KMeans().set_k(k).set_seed(11).set_max_iter(4).fit(Table({"features": X}))
    assert_is_the_references(model, X, k, 11, 4)


@pytest.mark.parametrize("shards", [1, 8])
def test_an_empty_cluster_keeps_its_centroid(shards):
    """Five distinct rows, 201 copies each, and eight centroids: the initial
    rows repeat, a tie goes to the lowest index, and the later twin gets no
    row and stays where it started, as in the reference."""
    distinct = structured(5, 6, 5, seed=3)
    X = np.tile(distinct, (201, 1))
    mesh = one_device_mesh() if shards == 1 else mesh_lib.default_mesh()
    with mesh_lib.use_mesh(mesh):
        model = KMeans().set_k(8).set_seed(5).set_max_iter(3).fit(Table({"features": X}))
    assert (model.weights == 0).sum() >= 3
    assert_is_the_references(model, X, 8, 5, 3)
    start = X[reference.initial_rows(len(X), 8, 5)]
    np.testing.assert_array_equal(model.centroids[model.weights == 0], start[model.weights == 0])


@pytest.mark.parametrize("blocked", [False, True], ids=["one-block", "several-blocks"])
def test_stream_whole_fit_is_the_in_memory_fit_of_the_same_rows(monkeypatch, blocked):
    from flink_ml_tpu import config
    from flink_ml_tpu.table import StreamTable

    n, k, d = 4 * 523, 16, 12
    if blocked:
        few_rows_a_block(monkeypatch, k, d, 128)
    X = structured(n, d, 30, seed=17 + blocked)
    batches = [Table({"features": X[i : i + 523]}) for i in range(0, n, 523)]
    before = metrics.snapshot()
    with config.whole_fit_mode("auto"):
        streamed = KMeans().set_k(k).set_seed(2).set_max_iter(3).fit(StreamTable.from_batches(batches))
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert delta.get("dispatch.whole_fit.lloyd") == 1  # the resident stream program, not the host loop
    in_memory = KMeans().set_k(k).set_seed(2).set_max_iter(3).fit(Table({"features": X}))
    np.testing.assert_array_equal(streamed.weights, in_memory.weights)
    np.testing.assert_allclose(streamed.centroids, in_memory.centroids, rtol=1e-6, atol=1e-4)
    assert_is_the_references(in_memory, X, k, 2, 3)


def _largest_array(text: str) -> int:
    """Elements of the largest array a lowered (StableHLO) or compiled (HLO)
    program names."""
    import re

    sizes = [1]
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
        sizes.append(int(np.prod([int(v) for v in dims.split("x") if v])))
    for dims in re.findall(r"\b(?:f32|s32|pred|bf16|u32|s8|u8)\[([\d,]+)\]", text):
        sizes.append(int(np.prod([int(v) for v in dims.split(",")])))
    return max(sizes)


@pytest.mark.parametrize("form", ["lowered", "compiled"])
def test_no_array_of_n_by_k_elements_exists(form):
    """200,000 rows against 2,048 centroids: n x k is 4.1e8 elements (1.6 GB
    of float32 a matrix). The fit's program holds the table (n x d), one
    block's distances and one-hot, and nothing larger."""
    n, k, d = 200_000, 2048, 16
    args = (
        jax.ShapeDtypeStruct((n, d), jnp.float32), None, jax.ShapeDtypeStruct((k, d), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    lowered = jax.jit(km._lloyd_fit_impl, static_argnums=(4, 5)).lower(*args, "euclidean", None)
    text = lowered.as_text() if form == "lowered" else lowered.compile().as_text()
    largest = _largest_array(text)
    block = km._block_rows(n, k, d)
    assert km._num_blocks(n, k, d) > 1
    assert n * d <= largest <= max(n * d, block * k) < n * k // 8


def device_table(n, d, dtype=jnp.float32, seed=0):
    X = structured(n, d, 10, seed)
    return X, jnp.asarray(X, dtype)  # born on the default device, committed to none


def fit_counters(table, mesh, k=4):
    before = metrics.snapshot()
    with mesh_lib.use_mesh(mesh):
        model = KMeans().set_k(k).set_seed(1).set_max_iter(3).fit(table)
    return model, metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def test_a_device_born_float32_table_is_trained_in_place():
    X, X_dev = device_table(909, 7)
    model, counters = fit_counters(Table({"features": X_dev}), one_device_mesh())
    assert counters.get("lloyd.table_copy", 0) == 0
    assert not X_dev.is_deleted()  # the table stays the caller's
    np.testing.assert_array_equal(np.asarray(X_dev), X)
    assert counters["lloyd.iterations"] == 3 and counters["lloyd.blocks"] == 3
    assert counters["iteration.host_sync"] == 1
    for phase in ("fit.extract", "fit.stage", "fit.launch", "fit.readback", "fit.total"):
        assert counters[phase + ".n"] == 1, phase
    assert_is_the_references(model, X, 4, 1, 3)


@pytest.mark.parametrize(
    "what, dtype, shards",
    [("a cast", jnp.bfloat16, 1), ("a pad and a re-sharding", jnp.float32, 8)],
)
def test_a_table_that_has_to_be_brought_there_counts_one_copy(what, dtype, shards):
    X, X_dev = device_table(911 if shards == 1 else 8 * 113 + 3, 7, dtype, seed=shards)
    mesh = one_device_mesh() if shards == 1 else mesh_lib.default_mesh()
    model, counters = fit_counters(Table({"features": X_dev}), mesh)
    assert counters["lloyd.table_copy"] == 1, what
    assert not X_dev.is_deleted()
    assert counters["lloyd.blocks"] == 3 * shards
    assert_is_the_references(model, np.asarray(X_dev.astype(jnp.float32)), 4, 1, 3)


def test_blocks_are_counted_from_the_shapes(monkeypatch):
    few_rows_a_block(monkeypatch, 4, 9, 256)
    X, X_dev = device_table(1000 + 29, 9)
    _, counters = fit_counters(Table({"features": X_dev}), one_device_mesh())
    assert km._block_rows(1029, 4, 9) == 256
    assert counters["lloyd.blocks"] == 3 * 5 and counters["lloyd.iterations"] == 3


@pytest.mark.parametrize("n, k, d, rows", [(2_700_000, 4096, 784, 6656), (1000, 10, 100, 1000), (300, 4096, 784, 300)])
def test_block_rows_come_from_the_shapes(n, k, d, rows):
    block = km._block_rows(n, k, d)
    assert block == rows and block <= n and block * (k + d) <= max(km._BLOCK_ELEMENTS, 256 * (k + d))


# --- the programs at the benchmark's size, compiled for a described v5e (no chip)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


N, K, D = 2_700_000, 4096, 784


@pytest.mark.parametrize("program", ["fit", "initial-rows"])
def test_the_codebook_fit_needs_no_second_table_on_a_v5e(one_chip, no_compile_cache, program):
    """2.7M x 784 float32 is 8.47e9 B of a 16 GiB chip: whatever copies the
    table cannot be compiled there. The fit's program and the one that takes
    the initial rows hold, besides their arguments, under 256 MB."""
    table = jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=one_chip)
    if program == "fit":
        args = (
            table, None, jax.ShapeDtypeStruct((K, D), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        )
        lowered = jax.jit(km._lloyd_fit_impl, static_argnums=(4, 5)).lower(*args, "euclidean", None)
    else:
        lowered = jax.jit(km._take_rows_impl).lower(table, jax.ShapeDtypeStruct((K,), jnp.int32, sharding=one_chip))
    memory = lowered.compile().memory_analysis()
    assert memory.argument_size_in_bytes >= N * D * 4
    assert memory.temp_size_in_bytes < 256 << 20
