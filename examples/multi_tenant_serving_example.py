"""Multi-tenant serving from one mesh: HBM-paged models + live hot-swap.

Three tenants each serve their own model from ONE `MicroBatchServer`
with continuous batching, routed through a `data.modelstore.ModelStore`
whose byte budget deliberately fits only two of the three models — the
store pages model constants host<->HBM under LRU, with every resident
byte on the `hbm.live.model` ledger and ZERO recompiles on page-in
(model tensors are runtime operands of the compiled plan). Mid-load,
tenant "b"'s model is hot-swapped through the store's lifecycle ring
without pausing the server (docs/serving.md).
"""

import time

import numpy as np

from flink_ml_tpu import flow
from flink_ml_tpu.data.modelstore import ModelStore
from flink_ml_tpu.lifecycle import ModelLifecycle
from flink_ml_tpu.models.classification.onlinelogisticregression import (
    OnlineLogisticRegressionModel,
)
from flink_ml_tpu.obs import memledger
from flink_ml_tpu.pipeline import PipelineModel
from flink_ml_tpu.serving import MicroBatchServer, ServerOverloaded
from flink_ml_tpu.table import Table

DIM = 64
TENANTS = ("a", "b", "c")
rng = np.random.RandomState(7)


def make_model(seed):
    m = OnlineLogisticRegressionModel()
    m.publish_model_arrays((np.random.RandomState(seed).randn(DIM),), 0)
    m.set_features_col("features").set_prediction_col("pred")
    return PipelineModel([m])


models = {t: make_model(i) for i, t in enumerate(TENANTS)}
olr = {t: pm.stages[0] for t, pm in models.items()}  # the swap-capable stage

# budget for ~2 of the 3 models: serving all three MUST page
probe = ModelStore(budget_bytes=None)
probe.register("a", models["a"])
per_model = probe.estimated_nbytes("a")
budget = int(per_model * 2.3)
store = ModelStore(budget_bytes=budget)
for t in TENANTS:
    lc = ModelLifecycle(olr[t]) if t == "b" else None
    store.register(t, models[t], lifecycle=lc, quota=8)
print(f"3 models x {per_model} bytes (est) into a {budget}-byte budget")

server = MicroBatchServer(
    store=store, batching="continuous", form_rows=16, buckets=(16,), admission=32
)
results = []
collector = flow.spawn(lambda: results.extend(server.results()), name="example.collect")


# model bytes the process held before this store staged any (none in a run of
# its own; a fitted pipeline kept by an earlier fit of the same process)
held_before = memledger.live_bytes("model")


def submit_round_robin(count):
    peak = 0
    for i in range(count):
        batch = Table({"features": rng.randn(4, DIM).astype(np.float32)})
        while True:  # closed-loop: wait out transient overload
            try:
                server.submit(batch, tenant=TENANTS[i % len(TENANTS)])
                break
            except ServerOverloaded:
                time.sleep(0.002)
        peak = max(peak, memledger.live_bytes("model") - held_before)
    return peak


peak = submit_round_robin(15)

# live hot-swap: tenant b's new version promotes through the store's
# lifecycle ring (validation gate + version ring) and restages its
# residency — the server never pauses and the plan never recompiles
new_coeff = np.linspace(1.0, -1.0, DIM)
mv = store.promote("b", (new_coeff,))
print(f"hot-swapped tenant b to version {mv.version_id} mid-load")

peak = max(peak, submit_round_robin(15))
server.close()
collector.join(timeout=60)
assert not collector.is_alive()

assert len(results) == 30 and all(r.status == "ok" for r in results)
assert peak <= budget, f"hbm.live.model peaked at {peak} over {budget}"
stats = store.stats
assert stats["evictions"] > 0, "three models in a two-model budget must evict"
store.check_ledger_parity()
store.page_in("b")
swapped = np.asarray(olr["b"].device_constants()["coefficient"])
np.testing.assert_array_equal(swapped, new_coeff.astype(swapped.dtype))

by_tenant = {t: sum(1 for r in results if r.tenant == t) for t in TENANTS}
print(f"served {by_tenant} requests; store stats {stats}")
print(f"peak model bytes {peak} <= budget {budget}; coefficients live-swapped")
