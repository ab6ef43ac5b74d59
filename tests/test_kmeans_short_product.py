"""The k-means cross term multiplies only the pieces its points have.

A float32 product on the matrix unit is a sum over bfloat16 pieces of its
operands; a table whose every value is exact in bfloat16 (pixel bytes) has one
piece, and `ops/distance.cross_term(..., ONE_PIECE)` leaves out the products of
the two that are zeros. Held here, on the CPU: the short product is float32's,
the look at the table says yes only where every value is exact, a fit through
the short form gives the full form's model, a table that is not exact (and
every fit on the CPU) runs the program as it was, and the counters say which.
The short program compiled for a described v5e is among the last tests of
`tests/test_layout_exchange.py`.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flink_ml_tpu import Table
from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.ops import distance
from flink_ml_tpu.ops.distance import ALL_PIECES, ONE_PIECE
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics

ROOT = Path(__file__).resolve().parent.parent
N, K, D = 512, 96, 784


def pixels(rng, shape=(N, D)):
    return rng.integers(0, 256, shape).astype(np.float32)


POINTS = {
    "pixel_integers": pixels,
    "a_fifth_lit": lambda rng: pixels(rng) * (rng.random((N, D)) < 0.2),
    "zeros": lambda rng: np.zeros((N, D), np.float32),
    "negative_whole_numbers": lambda rng: -pixels(rng),
    "powers_of_two": lambda rng: (2.0 ** rng.integers(-20, 20, (N, D)) * rng.choice([-1.0, 1.0], (N, D))).astype(np.float32),
}
CENTROIDS = {
    "means_of_pixels": lambda rng: (rng.random((K, D)) * 255).astype(np.float32),
    "signed": lambda rng: rng.standard_normal((K, D)).astype(np.float32),
}


@pytest.mark.parametrize("centroids", sorted(CENTROIDS))
@pytest.mark.parametrize("points", sorted(POINTS))
def test_the_short_product_is_float32s_and_no_further_off_than_the_full_one(points, centroids):
    rng = np.random.default_rng(7)
    X, C = POINTS[points](rng), CENTROIDS[centroids](rng)
    want = X.astype(np.float64) @ C.astype(np.float64).T
    full = np.asarray(distance.cross_term(jnp.asarray(X), jnp.asarray(C)), np.float64)
    short = np.asarray(distance.cross_term(jnp.asarray(X), jnp.asarray(C), ONE_PIECE), np.float64)
    assert short.shape == full.shape == (N, K) and distance.cross_term(jnp.asarray(X), jnp.asarray(C), ONE_PIECE).dtype == jnp.float32
    # float32 rounding of a sum of d products: a few units in the last place of sum |x||c|
    scale = np.maximum(np.abs(X).astype(np.float64) @ np.abs(C).astype(np.float64).T, 1e-30)
    assert (np.abs(short - want) / scale).max() < 1e-6
    assert np.abs(short - want).max() <= np.abs(full - want).max()
    # one bfloat16 pass, the default of the TPU, is three orders further off (the centroids rounded to 8 bits)
    if points != "zeros":
        one_pass = np.asarray(jnp.matmul(jnp.asarray(X), distance.in_bfloat16(jnp.asarray(C)).T), np.float64)
        assert np.abs(one_pass - want).max() > 50 * np.abs(short - want).max()


@pytest.mark.parametrize("centroids", sorted(CENTROIDS))
def test_the_three_pieces_are_bfloat16_and_add_up_to_the_float32_value(centroids):
    C = CENTROIDS[centroids](np.random.default_rng(3))
    pieces = distance.bfloat16_pieces(jnp.asarray(C))
    assert [p.dtype for p in pieces] == [jnp.bfloat16] * 3
    hi, mid, lo = (np.asarray(p, np.float64) for p in pieces)
    assert (hi + mid + lo == C).all()
    assert (mid != 0).mean() > 0.9 and (lo != 0).mean() > 0.9  # nothing folded the pieces away
    assert np.abs(mid).max() <= np.abs(hi).max() * 2.0**-7 and np.abs(lo).max() <= np.abs(hi).max() * 2.0**-15


def test_points_of_two_pieces_have_no_product():
    with pytest.raises(ValueError, match="2 bfloat16 pieces"):
        distance.cross_term(jnp.zeros((4, 8)), jnp.zeros((2, 8)), 2)


@pytest.mark.parametrize("measure", [distance.EUCLIDEAN, distance.COSINE, distance.MANHATTAN])
def test_every_measure_takes_the_pieces_and_orders_centroids_the_same(measure):
    rng = np.random.default_rng(11)
    X, C = jnp.asarray(pixels(rng, (64, 32))), jnp.asarray((rng.random((8, 32)) * 255).astype(np.float32))
    m = distance.DistanceMeasure.get_instance(measure)
    np.testing.assert_allclose(m.pairwise(X, C, ONE_PIECE), m.pairwise(X, C), rtol=2e-5)
    np.testing.assert_array_equal(
        distance.first_minimum(m.closeness(X, C, ONE_PIECE)), distance.first_minimum(m.closeness(X, C))
    )


# --- the look ---------------------------------------------------------------


def cell_table(rows=135 * 16, seed=5):
    """The k-means cell's table maker (perf/tables/images_mnist8m.py) at a
    rehearsal's size, and its configuration's data."""
    import json

    spec = importlib.util.spec_from_file_location("perf_tables_images_mnist8m", ROOT / "perf" / "tables" / "images_mnist8m.py")
    maker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(maker)
    data = json.loads((ROOT / "perf" / "configs" / "kmeans-mnist8m.json").read_text())["data"]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    return maker.make(jax.random.PRNGKey(seed), rows, data, mesh)["features"]


def one_value_among_pixels(value):
    X = pixels(np.random.default_rng(2), (4096, 24))
    X[3071, 17] = value  # one value in one row of many
    return X


NOT_EXACT = {
    "a_tenth": lambda: one_value_among_pixels(0.1),
    "pixels_scaled_by_1_255": lambda: pixels(np.random.default_rng(2), (4096, 24)) / np.float32(255),
    "257": lambda: one_value_among_pixels(257.0),
    "a_nan": lambda: one_value_among_pixels(np.nan),
    "an_infinity": lambda: one_value_among_pixels(np.inf),
    "a_negative_infinity": lambda: one_value_among_pixels(-np.inf),
    "past_bfloat16s_largest": lambda: one_value_among_pixels(np.finfo(np.float32).max),
    "standard_normal": lambda: np.random.default_rng(2).standard_normal((4096, 24)).astype(np.float32),
}
EXACT = {
    "pixels": lambda: pixels(np.random.default_rng(2), (4096, 24)),
    "256": lambda: one_value_among_pixels(256.0),
    "zeros": lambda: np.zeros((64, 8), np.float32),
    "negative_whole_numbers": lambda: -pixels(np.random.default_rng(2), (4096, 24)),
    "powers_of_two": lambda: POINTS["powers_of_two"](np.random.default_rng(2)),
    "the_cells_table_maker": cell_table,
}


@pytest.mark.parametrize("table", sorted(NOT_EXACT))
def test_the_look_says_no(table):
    assert not bool(km._exact_in_bfloat16(jnp.asarray(NOT_EXACT[table]())))


@pytest.mark.parametrize("table", sorted(EXACT))
def test_the_look_says_yes(table):
    assert bool(km._exact_in_bfloat16(jnp.asarray(EXACT[table]())))


def test_the_look_rounds_by_an_op_the_compiler_keeps():
    """A cast to bfloat16 and back is carried out in float32 inside a fusion
    on the v5e and says yes to every table (PERF.md, PR 35): the look and the
    pieces round with `reduce_precision`."""
    table = jax.ShapeDtypeStruct((1024, 24), jnp.float32)
    assert "reduce_precision" in jax.jit(km._exact_in_bfloat16_impl).lower(table).as_text()
    assert "reduce_precision" in jax.jit(distance.bfloat16_pieces).lower(table).as_text()


# --- the fit ------------------------------------------------------------------


def pixel_clusters(n=1536, d=48, centres=6, seed=4):
    """Whole numbers 0..255 around a few centres: exact in bfloat16, and no
    row so near two centroids that the order of a float32 sum decides."""
    rng = np.random.default_rng(seed)
    base = rng.integers(40, 216, (centres, d))
    rows = base[rng.integers(0, centres, n)] + rng.integers(-12, 13, (n, d))
    return np.clip(rows, 0, 255).astype(np.float32)


def fit(X, shards, monkeypatch, on_tpu, k=6):
    mesh = mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:shards])
    X_dev = jax.device_put(X, mesh_lib.data_sharding(mesh, 2))
    if on_tpu is not None:
        monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: on_tpu)
    before = metrics.snapshot()
    with mesh_lib.use_mesh(mesh):
        model = KMeans().set_k(k).set_seed(3).set_max_iter(4).fit(Table({"features": X_dev}))
        counted = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        assign = np.asarray(model.transform(Table({"features": X}))[0].column("prediction"))
    return model, assign, counted


@pytest.mark.parametrize("shards", [1, 4])
def test_a_fit_through_the_short_form_gives_the_full_forms_model(shards, monkeypatch):
    X = pixel_clusters()
    short, short_assign, counted = fit(X, shards, monkeypatch, on_tpu=True)
    assert counted["lloyd.product.short"] == 1 and "lloyd.product.full" not in counted
    assert counted["iteration.host_sync.look"] == 1 and counted["iteration.host_sync"] == 2
    full, full_assign, counted = fit(X, shards, monkeypatch, on_tpu=False)
    assert counted["lloyd.product.full"] == 1 and "lloyd.product.short" not in counted
    np.testing.assert_array_equal(short_assign, full_assign)
    np.testing.assert_array_equal(short.weights, full.weights)
    np.testing.assert_allclose(short.centroids, full.centroids, rtol=1e-6)
    assert short.weights.sum() == len(X) and (short.weights > 0).all()


def test_a_table_that_is_not_exact_is_looked_at_and_keeps_the_full_product(monkeypatch):
    X = pixel_clusters() / np.float32(255)
    looked, _, counted = fit(X, 1, monkeypatch, on_tpu=True)
    assert counted["lloyd.product.full"] == 1 and "lloyd.product.short" not in counted
    assert counted["iteration.host_sync.look"] == 1 and counted["iteration.host_sync"] == 2
    unseen, _, _ = fit(X, 1, monkeypatch, on_tpu=False)
    np.testing.assert_array_equal(looked.centroids, unseen.centroids)  # the same program: to the bit
    np.testing.assert_array_equal(looked.weights, unseen.weights)


def test_a_fit_on_the_cpu_looks_at_nothing(monkeypatch):
    looks = []
    monkeypatch.setattr(km, "_exact_in_bfloat16", lambda X: looks.append(X))
    _, _, counted = fit(pixel_clusters(), 1, monkeypatch, on_tpu=None)
    assert counted["lloyd.product.full"] == 1 and "lloyd.product.short" not in counted
    assert counted["iteration.host_sync"] == 1 and "iteration.host_sync.look" not in counted
    assert not looks


def test_every_fit_looks_again(monkeypatch):
    """Nothing is kept between fits: a table that was exact and is not any
    more (the same array object cannot change, but nothing says that the
    next table is the last one) is seen by the next fit's own look."""
    X = pixel_clusters()
    _, _, first = fit(X, 1, monkeypatch, on_tpu=True)
    _, _, again = fit(X, 1, monkeypatch, on_tpu=True)
    _, _, scaled = fit(X / np.float32(255), 1, monkeypatch, on_tpu=True)
    assert first["iteration.host_sync.look"] == again["iteration.host_sync.look"] == scaled["iteration.host_sync.look"] == 1
    assert "lloyd.product.short" in again and "lloyd.product.full" in scaled


def test_the_overlap_schedule_keeps_the_six_passes(monkeypatch):
    from flink_ml_tpu import config

    monkeypatch.setattr(config, "collective_overlap", True)
    _, _, counted = fit(pixel_clusters(), 4, monkeypatch, on_tpu=True)
    assert counted["lloyd.product.full"] == 1 and "iteration.host_sync.look" not in counted


# --- the program ----------------------------------------------------------------


def lowered_fit(*statics):
    args = (
        jax.ShapeDtypeStruct((4096, 48), jnp.float32), jax.ShapeDtypeStruct((64, 48), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    # a function of its own each time: nothing traced before is found again
    fit = lambda X, init, max_iter: km._lloyd_fit_impl(X, None, init, max_iter, "euclidean", None, *statics)
    return jax.jit(fit).lower(*args).as_text()


def test_the_program_for_a_table_that_is_not_exact_has_the_parents_text(monkeypatch):
    """`ALL_PIECES`, passed or left out, lowers to the program of the parent
    commit, whose `cross_term` was this one line; `ONE_PIECE` does not."""
    as_it_is = lowered_fit(ALL_PIECES)
    assert as_it_is == lowered_fit()
    short = lowered_fit(ONE_PIECE)
    monkeypatch.setattr(
        distance, "cross_term", lambda X, C, point_pieces=None: jnp.matmul(X, C.T, precision=lax.Precision.HIGHEST)
    )
    assert lowered_fit() == as_it_is
    assert short != as_it_is and "bf16" in short and "bf16" not in as_it_is
    assert "HIGHEST" in as_it_is and "HIGHEST" not in short
