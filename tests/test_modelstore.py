"""ModelStore — multi-tenant HBM-paged model residency (ISSUE 19).

Pins the paging contract: LRU eviction under the byte budget with
`hbm.live.model` never exceeding it, deterministic page-out (ledger falls
when the store decides), zero recompiles across page cycles (constants
are runtime operands of the same compiled plan), and
lifecycle/quota/serving integration.
"""

import numpy as np
import pytest

from flink_ml_tpu.data.modelstore import ModelStore, ModelStoreBudgetExceeded
from flink_ml_tpu.obs import memledger
from flink_ml_tpu.pipeline import PipelineModel
from flink_ml_tpu.serving import MicroBatchServer
from flink_ml_tpu.table import Table
from flink_ml_tpu.utils import metrics

RNG = np.random.RandomState(7)
D = 64


@pytest.fixture(autouse=True)
def _clean_ledger():
    memledger.reset()
    yield
    memledger.reset()


def _scaler(d=D):
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel

    ss = StandardScalerModel()
    ss.mean = RNG.randn(d)
    ss.std = np.abs(RNG.randn(d)) + 0.1
    ss.set_input_col("features").set_output_col("scaled")
    return ss


def _olr(d=16, version=0):
    from flink_ml_tpu.models.classification.onlinelogisticregression import (
        OnlineLogisticRegressionModel,
    )

    m = OnlineLogisticRegressionModel()
    m.publish_model_arrays((np.ones(d),), version)
    m.set_features_col("features").set_prediction_col("pred")
    return m


def _feature_batch(n, d=D):
    return Table({"features": RNG.randn(n, d).astype(np.float32)})


def _est(model) -> int:
    """One model's host-side admission estimate via a throwaway store."""
    probe = ModelStore(budget_bytes=None, name="probe")
    probe.register("x", model)
    est = probe.estimated_nbytes("x")
    probe.unregister("x")
    return est


def _dev(model) -> int:
    """One model's actual device-resident bytes (< the host estimate
    under default x64-disabled canonicalization)."""
    probe = ModelStore(budget_bytes=None, name="probe")
    probe.register("x", model)
    probe.page_in("x")
    dev = probe.stats["bytes"]
    probe.unregister("x")
    return dev


# ---------------------------------------------------------------------------
# registry + budget admission
# ---------------------------------------------------------------------------

def test_register_estimate_contains_unregister():
    store = ModelStore(budget_bytes=None)
    store.register("a", _scaler())
    assert "a" in store and store.keys() == ["a"]
    # mean + std, float64 host arrays
    assert store.estimated_nbytes("a") == 2 * D * 8
    store.unregister("a")
    assert "a" not in store and store.keys() == []
    with pytest.raises(KeyError):
        store.acquire("a")


def test_oversized_model_rejected_with_numbers():
    one = _est(_scaler())
    store = ModelStore(budget_bytes=one - 1)
    with pytest.raises(ModelStoreBudgetExceeded) as ei:
        store.register("big", _scaler())
    assert ei.value.key == "big"
    assert ei.value.nbytes == one
    assert ei.value.budget == one - 1


def test_rejects_non_model_types():
    store = ModelStore(budget_bytes=None)
    with pytest.raises(TypeError):
        store.register("x", object())


# ---------------------------------------------------------------------------
# LRU paging under the byte budget
# ---------------------------------------------------------------------------

def test_lru_eviction_and_budget_never_exceeded():
    est, dev = _est(_scaler()), _dev(_scaler())
    # admission is conservative (host estimate): a page-in fits while
    # `used + est <= budget`. Two residents fit; a third must evict.
    budget = 2 * dev + est - 1
    store = ModelStore(budget_bytes=budget)
    for key in ("a", "b", "c"):
        store.register(key, _scaler())

    def check_budget():
        assert memledger.live_bytes("model") <= budget
        assert store.stats["bytes"] <= budget
        store.check_ledger_parity()

    store.page_in("a")
    check_budget()
    store.page_in("b")
    check_budget()
    assert sorted(store.resident_keys()) == ["a", "b"]
    store.page_in("c")  # evicts a — the least recently used
    check_budget()
    assert sorted(store.resident_keys()) == ["b", "c"]
    store.acquire("b")  # touch: b becomes most recently used
    store.page_in("a")  # evicts c, not b
    check_budget()
    assert sorted(store.resident_keys()) == ["a", "b"]
    s = store.stats
    assert s["models"] == 3 and s["resident"] == 2
    assert s["evictions"] == 2
    assert s["misses"] == 4 and s["hits"] == 1


def test_page_out_releases_ledger_deterministically():
    store = ModelStore(budget_bytes=None)
    store.register("a", _scaler())
    base = memledger.live_bytes("model")
    store.page_in("a")
    resident = memledger.live_bytes("model")
    assert resident > base
    assert store.stats["bytes"] == resident - base
    store.page_out("a")
    # no GC grace: invalidation dropped the only reference, so the
    # tracked entries' finalizers already ran (CPython refcounting)
    assert memledger.live_bytes("model") == base
    assert store.stats["bytes"] == 0 and store.resident_keys() == []
    store.check_ledger_parity()


def test_prefetch_warms_off_the_dispatch_path():
    store = ModelStore(budget_bytes=None)
    store.register("a", _scaler())
    store.register("b", _scaler())
    before = metrics.get_counter("modelstore.prefetch", 0)
    store.prefetch(["a", "b"])  # wait=True
    assert sorted(store.resident_keys()) == ["a", "b"]
    assert metrics.get_counter("modelstore.prefetch", 0) == before + 2
    store.page_out("a")
    worker = store.prefetch(["a"], wait=False)
    worker.join(timeout=30)
    assert not worker.is_alive()
    assert sorted(store.resident_keys()) == ["a", "b"]
    # both already resident: a hit, not a restage
    s = store.stats
    store.prefetch(["a", "b"])
    assert store.stats["hits"] == s["hits"] + 2


# ---------------------------------------------------------------------------
# zero recompiles across page cycles (the servingSlo pin, in miniature)
# ---------------------------------------------------------------------------

def test_paging_cycles_never_recompile():
    """Page a model out and back N times while serving: the constants are
    runtime operands, so every cycle re-uploads into the SAME compiled
    program — `jit.compiles` stays flat after warmup."""
    from flink_ml_tpu.obs import tracing

    tracing.install_jax_hooks()
    pm_a = PipelineModel([_scaler()])
    pm_b = PipelineModel([_scaler()])
    est, dev = _est(_scaler()), _dev(_scaler())
    store = ModelStore(budget_bytes=dev + est - 1)  # only ONE fits
    store.register("a", pm_a)
    store.register("b", pm_b)

    def serve_once(key):
        server = MicroBatchServer(store.acquire(key), in_flight=1, buckets=(8,))
        outs = list(server.serve(iter([_feature_batch(8)])))
        assert outs[0].num_rows == 8

    serve_once("a")  # warmup: each pipeline owns its fused-segment jit
    serve_once("b")
    before = metrics.get_counter("jit.compiles", 0)
    page_ins_before = metrics.get_counter("modelstore.pageIn", 0)
    for _ in range(3):  # every serve evicts the other model
        serve_once("a")
        serve_once("b")
        assert memledger.live_bytes("model") <= store.budget_bytes
    assert metrics.get_counter("jit.compiles", 0) == before, (
        "steady-state paging must be recompile-free"
    )
    assert metrics.get_counter("modelstore.pageIn", 0) >= page_ins_before + 6
    store.check_ledger_parity()


# ---------------------------------------------------------------------------
# lifecycle + quota + serving integration
# ---------------------------------------------------------------------------

def test_promote_through_store_refreshes_residency():
    from flink_ml_tpu.lifecycle import ModelLifecycle

    model = _olr(d=16, version=1)
    store = ModelStore(budget_bytes=None)
    store.register("t", model, lifecycle=ModelLifecycle(model), quota=4)
    assert store.quota("t") == 4
    assert store.lifecycle("t") is not None
    store.page_in("t")
    c0 = np.asarray(store.acquire("t").device_constants()["coefficient"])
    np.testing.assert_array_equal(c0, np.ones(16))
    mv = store.promote("t", (np.full(16, 2.0),))
    assert mv.version_id == 2
    # the republish restaged under the store's accounting: still resident,
    # parity intact, and the compiled path sees the NEW coefficients
    assert store.resident_keys() == ["t"]
    store.check_ledger_parity()
    c1 = np.asarray(store.acquire("t").device_constants()["coefficient"])
    np.testing.assert_array_equal(c1, np.full(16, 2.0))


def test_promote_without_lifecycle_raises():
    store = ModelStore(budget_bytes=None)
    store.register("t", _olr())
    with pytest.raises(ValueError, match="no lifecycle"):
        store.promote("t", (np.zeros(16),))


def test_external_republish_heals_on_next_page_in():
    """A publish OUTSIDE `promote` invalidates the cached constants; the
    next page_in notices (resident flag vs missing cache), drops the
    stale accounting without counting an eviction, and restages."""
    model = _olr(d=16, version=1)
    store = ModelStore(budget_bytes=None)
    store.register("t", model)
    store.page_in("t")
    evictions = store.stats["evictions"]
    model.publish_model_arrays((np.full(16, 3.0),), 2)  # bypasses the store
    entry = store.page_in("t")  # miss: restage + re-measure
    assert entry.resident
    assert store.stats["evictions"] == evictions
    store.check_ledger_parity()
    np.testing.assert_array_equal(
        np.asarray(store.acquire("t").device_constants()["coefficient"]),
        np.full(16, 3.0),
    )


def test_server_submit_unregistered_tenant_is_typed():
    store = ModelStore(budget_bytes=None)
    store.register("known", PipelineModel([_scaler()]))
    server = MicroBatchServer(store=store, in_flight=1, admission=4)
    with pytest.raises(KeyError, match="ghost"):
        server.submit(_feature_batch(4), tenant="ghost")
    # a store-only server has no default model for tenantless submits
    server.submit(_feature_batch(4), tenant="known")
    server.close()
    results = list(server.results())
    assert [r.status for r in results] == ["ok"]
    assert results[0].tenant == "known"
    h = server.health()
    assert h.modelStore is not None and h.modelStore["models"] == 1


def test_round_robin_tenants_page_through_one_server_without_recompiles():
    """Six tenants whose constants together exceed the store's budget,
    served round-robin by ONE continuous-batching server: the store pages,
    `hbm.live.model` never passes the budget, every request retires ok and
    `jit.compiles` stays flat once each tenant's plan has compiled."""
    from flink_ml_tpu.obs import tracing

    tracing.install_jax_hooks()
    d, n_tenants, n_requests = 512, 6, 48
    tenants = [f"tenant{i}" for i in range(n_tenants)]

    def tenant_model():
        scaler = _scaler(d).set_output_col("features")
        return PipelineModel([scaler, _olr(d)])

    per_model = _est(tenant_model())
    budget = int(per_model * 3.3)  # room for three of the six
    store = ModelStore(budget_bytes=budget)
    for t in tenants:
        store.register(t, tenant_model(), quota=16)

    def serve_round_robin(count, start):
        # admission and every tenant's quota exceed the requests in flight:
        # nothing is refused, and the results wait in the server's buffer
        server = MicroBatchServer(
            store=store, buckets=(8, 32), batching="continuous", form_rows=32, admission=64
        )
        peak = 0
        for i in range(count):
            server.submit(_feature_batch(4, d), tenant=tenants[(start + i) % n_tenants])
            peak = max(peak, memledger.live_bytes("model"))
        server.close()
        outputs = list(server.results())
        return outputs, max(peak, memledger.live_bytes("model"))

    for t in tenants:  # each tenant's fused plan compiles once per bucket shape
        list(
            MicroBatchServer(store.acquire(t), buckets=(8, 32)).serve(
                iter([_feature_batch(8, d), _feature_batch(32, d)])
            )
        )
    serve_round_robin(2 * n_tenants, start=0)
    compiles = metrics.get_counter("jit.compiles", 0)
    page_ins = metrics.get_counter("modelstore.pageIn", 0)
    outputs, peak = serve_round_robin(n_requests, start=1)
    assert len(outputs) == n_requests and all(r.status == "ok" for r in outputs)
    assert metrics.get_counter("jit.compiles", 0) == compiles
    assert metrics.get_counter("modelstore.pageIn", 0) > page_ins
    assert peak <= budget
    store.check_ledger_parity()


def test_server_requires_model_or_store():
    with pytest.raises(TypeError, match="model"):
        MicroBatchServer()
