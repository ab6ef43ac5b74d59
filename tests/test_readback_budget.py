"""Readback-budget contract for the hot fit paths.

Every first readback of a device array is its own blocking host round
trip, so a fit must pull its results in ONE packed
transfer. These tests run fits on device-born inputs under
``jax.transfer_guard_device_to_host("disallow")``, which raises on any IMPLICIT
device→host transfer (a stray ``np.asarray`` on a device array) while
letting the explicit `packed_device_get` / `jax.device_get` readback
through — and count that exactly one such explicit readback happens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.table import Table
from flink_ml_tpu.utils import packing


@pytest.fixture
def readback_counter(monkeypatch):
    calls = []
    real = jax.device_get

    def counting_device_get(x):
        calls.append(np.shape(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    return calls


def _device_table_Xyw(n=512, d=8):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    X = jax.random.uniform(k1, (n, d), jnp.float32)
    y = (jax.random.uniform(k2, (n,)) > 0.5).astype(jnp.float32)
    w = jax.random.uniform(k3, (n,))
    return Table({"features": X, "label": y, "weight": w})


def test_kmeans_fit_single_packed_readback(readback_counter):
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    table = _device_table_Xyw()
    with jax.transfer_guard_device_to_host("disallow"):
        model = KMeans().set_k(4).set_max_iter(5).set_seed(2).fit(table)
    assert len(readback_counter) == 1, readback_counter
    assert model.centroids.shape == (4, 8)


def test_logisticregression_fit_single_packed_readback(readback_counter):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )

    table = _device_table_Xyw()
    with jax.transfer_guard_device_to_host("disallow"):
        model = LogisticRegression().set_max_iter(5).set_global_batch_size(
            256
        ).set_weight_col("weight").fit(table)
    assert len(readback_counter) == 1, readback_counter
    assert model.coefficient.shape == (8,)


def test_standardscaler_fit_single_packed_readback(readback_counter):
    from flink_ml_tpu.models.feature.standardscaler import StandardScaler

    table = _device_table_Xyw()
    with jax.transfer_guard_device_to_host("disallow"):
        StandardScaler().set_input_col("features").set_output_col("out").fit(table)
    assert len(readback_counter) == 1, readback_counter


def test_minmax_and_maxabs_fit_single_packed_readback(readback_counter):
    from flink_ml_tpu.models.feature.maxabsscaler import MaxAbsScaler
    from flink_ml_tpu.models.feature.minmaxscaler import MinMaxScaler

    table = _device_table_Xyw()
    with jax.transfer_guard_device_to_host("disallow"):
        MinMaxScaler().set_input_col("features").set_output_col("out").fit(table)
    assert len(readback_counter) == 1, readback_counter
    readback_counter.clear()
    with jax.transfer_guard_device_to_host("disallow"):
        MaxAbsScaler().set_input_col("features").set_output_col("out").fit(table)
    assert len(readback_counter) == 1, readback_counter


def test_packed_device_get_round_trips():
    a = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    b = jnp.asarray([7.0, 8.0])
    c = jnp.asarray(9, jnp.int32)
    ha, hb, hc = packing.packed_device_get(a, b, c)
    np.testing.assert_array_equal(ha, np.arange(6).reshape(2, 3))
    np.testing.assert_array_equal(hb, [7.0, 8.0])
    assert hc == 9
    # host inputs pass through untouched
    (h,) = packing.packed_device_get(np.asarray([1.0]))
    np.testing.assert_array_equal(h, [1.0])

# ---------------------------------------------------------------------------
# host-input transforms: the pulls the tpulint host-sync-leak rule fixed
# ---------------------------------------------------------------------------
# Before the tpulint pass these paths pulled device results back with bare
# np.asarray — a silent, UNACCOUNTED device→host sync (hostSyncCount 0 on
# the estimator's BENCH entry despite a real blocking readback, and two
# round trips for the two-column predictors). Now they ride
# packed_device_get: exactly ONE accounted sync per transform.


def _transform_sync_delta(fn):
    from flink_ml_tpu.utils import metrics

    before = metrics.snapshot()["counters"].get("iteration.host_sync.transform", 0)
    fn()
    after = metrics.snapshot()["counters"].get("iteration.host_sync.transform", 0)
    return after - before


def test_kmeans_host_transform_sync_is_accounted(readback_counter):
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    X = np.random.RandomState(0).rand(64, 4)
    table = Table({"features": X})
    model = KMeans().set_k(3).set_max_iter(3).fit(table)
    readback_counter.clear()
    delta = _transform_sync_delta(lambda: model.transform(table))
    assert delta == 1  # was 0 accounted (silent np.asarray) before the fix
    assert len(readback_counter) == 1  # ... and exactly one real transfer


def test_logreg_host_transform_is_one_packed_sync(readback_counter):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )

    rng = np.random.RandomState(1)
    X = rng.rand(128, 6)
    y = (rng.rand(128) > 0.5).astype(np.float64)
    table = Table({"features": X, "label": y})
    model = LogisticRegression().set_max_iter(3).fit(table)
    readback_counter.clear()
    delta = _transform_sync_delta(lambda: model.transform(table))
    # prediction + rawPrediction come back in ONE packed transfer (two
    # bare np.asarray pulls would each be their own blocking readback)
    assert delta == 1
    assert len(readback_counter) == 1
