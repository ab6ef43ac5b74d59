"""Metrics/profiling surface (utils/metrics.py) — the analogue of the
reference's Flink metric groups + modelDataVersion gauge + the benchmark
module's wall-clock accounting (SURVEY.md §5)."""

import numpy as np
import pytest

from flink_ml_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.reset()
    yield
    metrics.reset()


def test_timed_accumulates():
    with metrics.timed("phase.a"):
        pass
    with metrics.timed("phase.a"):
        pass
    snap = metrics.snapshot()
    assert snap["timers"]["phase.a"]["count"] == 2
    assert snap["timers"]["phase.a"]["totalMs"] >= 0.0
    assert metrics.timer_totals()["phase.a"] >= 0.0


def test_a_timer_does_not_grow_with_its_records():
    """A server records a time with every fit and readback for as long as
    it lives: a name keeps count, total and last, and the views are as
    before."""
    for i in range(1000):
        metrics.record_time("phase.b", 0.001 * (i + 1))
    assert len(metrics._timers["phase.b"]) == 3
    stats = metrics.snapshot()["timers"]["phase.b"]
    assert stats["count"] == 1000
    assert stats["totalMs"] == pytest.approx(500500.0)
    assert stats["lastMs"] == pytest.approx(1000.0)
    assert metrics.timer_totals()["phase.b"] == pytest.approx(500.5)
    before = metrics.snapshot()
    metrics.record_time("phase.b", 0.25)
    delta = metrics.snapshot_delta(before, metrics.snapshot())["timers"]["phase.b"]
    assert delta == {"count": 1, "totalMs": pytest.approx(250.0), "lastMs": pytest.approx(250.0)}


def test_gauges_and_counters():
    metrics.set_gauge("g", 7.5)
    metrics.inc_counter("c")
    metrics.inc_counter("c", 2)
    snap = metrics.snapshot()
    assert snap["gauges"]["g"] == 7.5
    assert snap["counters"]["c"] == 3
    assert metrics.get_gauge("g") == 7.5
    assert metrics.get_gauge("missing", -1) == -1


def test_iteration_epoch_timing():
    """Host-driven iterations record per-epoch wall clock; the on-device
    while_loop records the loop total + epoch gauge."""
    import jax.numpy as jnp

    from flink_ml_tpu.parallel.iteration import IterationListener, iterate_bounded

    def body(carry, epoch):
        return carry + 1.0, jnp.asarray(1.0, jnp.float32)

    class L(IterationListener):
        pass

    iterate_bounded(body, jnp.asarray(0.0), max_iter=3, listener=L())
    snap = metrics.snapshot()
    assert snap["timers"]["iteration.epoch"]["count"] == 3
    assert snap["gauges"]["iteration.epochs"] == 3

    metrics.reset()
    iterate_bounded(body, jnp.asarray(0.0), max_iter=4)
    snap = metrics.snapshot()
    assert snap["timers"]["iteration.device_loop"]["count"] == 1
    assert snap["gauges"]["iteration.epochs"] == 4


def test_benchmark_phase_breakdown(mesh8):
    from flink_ml_tpu.benchmark.runner import run_benchmark

    entry = {
        "stage": {
            "className": "org.apache.flink.ml.clustering.kmeans.KMeans",
            "paramMap": {"k": 2, "maxIter": 2},
        },
        "inputData": {
            "className": "org.apache.flink.ml.benchmark.datagenerator.common.DenseVectorGenerator",
            "paramMap": {"colNames": [["features"]], "numValues": 64, "vectorDim": 3},
        },
    }
    result = run_benchmark("KMeans-phase", entry)
    assert set(result["phaseTimesMs"]) == {"datagen", "fit", "transform", "collect"}
    assert all(v >= 0.0 for v in result["phaseTimesMs"].values())
    # phases also land in the process-wide registry
    assert "benchmark.KMeans-phase.fit" in metrics.snapshot()["timers"]


def test_online_model_version_gauge(mesh8):
    from flink_ml_tpu.models.clustering.onlinekmeans import (
        OnlineKMeans,
        generate_random_model_data,
    )
    from flink_ml_tpu.table import StreamTable, Table

    rng = np.random.default_rng(0)
    batches = [
        Table({"features": rng.standard_normal((16, 2)).astype(np.float32)})
        for _ in range(3)
    ]
    model = (
        OnlineKMeans()
        .set_global_batch_size(16)
        .set_initial_model_data(generate_random_model_data(2, 2, 0.0, seed=5))
    ).fit(StreamTable.from_batches(batches))
    model.process_updates()
    assert metrics.get_gauge("OnlineKMeansModel.modelDataVersion") == model.model_version
