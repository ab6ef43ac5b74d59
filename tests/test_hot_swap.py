"""Versioned zero-pause model hot-swap (lifecycle.py + the fused swap
path in pipeline.py/api.py):

- swap-capable online models serve through the FUSED path and a live
  publication is zero-recompile (jit compile counter pinned across N
  swaps) with every served row stamped by exactly one version;
- an in-flight batch keeps the version it was dispatched with (no torn
  reads across a swap);
- `(version, arrays)` publication is ONE atomic reference swap (hammered
  by a concurrent trainer/server pair — sanitizer-clean under
  FLINK_ML_TPU_SANITIZE=1);
- the promotion gate refuses NaN/shape/dtype/canary-regressed candidates
  (`lifecycle.promoteRejected`), the version ring rolls back bit-exactly
  and quarantines the trainer, and the JobSnapshot meta contract makes a
  killed+resumed train-while-serve job re-publish the same version;
- the chaos soak composes ckpt fault sites with the new
  lifecycle.promote/lifecycle.swap sites, with deterministic invariants.
"""

import numpy as np
import pytest

import jax

from flink_ml_tpu import config, flow
from flink_ml_tpu.ckpt import faults
from flink_ml_tpu.ckpt.faults import InjectedFault
from flink_ml_tpu.lifecycle import (
    ModelLifecycle,
    PromotionRejected,
    TrainerQuarantined,
)
from flink_ml_tpu.models.classification.onlinelogisticregression import (
    OnlineLogisticRegressionModel,
)
from flink_ml_tpu.models.clustering.onlinekmeans import OnlineKMeansModel
from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
from flink_ml_tpu.obs import tracing
from flink_ml_tpu.pipeline import PipelineModel
from flink_ml_tpu.serving import MicroBatchServer
from flink_ml_tpu.table import Table
from flink_ml_tpu.utils import metrics

RNG = np.random.RandomState(11)
DIM = 4


def _olr_model(coeff=None, version=0):
    m = OnlineLogisticRegressionModel()
    m.publish_model_arrays((np.zeros(DIM) if coeff is None else coeff,), version)
    m.set_features_col("features").set_prediction_col("pred")
    return m


def _scaler():
    m = StandardScalerModel()
    m.mean = np.zeros(DIM)
    m.std = np.ones(DIM)
    m.set_input_col("features").set_output_col("features")
    return m


def _device_batch(n=8, seed=0):
    rng = np.random.RandomState(seed)
    return Table({"features": jax.device_put(rng.randn(n, DIM).astype(np.float32))})


# ---------------------------------------------------------------------------
# satellite: explicit constants-cache invalidation on set_model_data
# ---------------------------------------------------------------------------

class TestModelDataVersionBump:
    def test_scaler_in_place_mutation_cannot_serve_stale_uploads(self):
        """`device_constants` is keyed on array identity, which in-place
        mutation defeats (and GC id-reuse could too). Every
        `set_model_data` now routes through an explicit version bump —
        the memoized upload refreshes even when the array OBJECTS (and
        thus their ids) are unchanged."""
        from flink_ml_tpu.linalg import DenseVector

        m = _scaler()
        mean = np.zeros(DIM)
        m.mean = mean
        before = np.asarray(m.device_constants()["mean"])
        np.testing.assert_array_equal(before, np.zeros(DIM))
        mean[:] = 5.0  # in-place: same object identity, same params version
        m.set_model_data(
            Table({"mean": [DenseVector(mean)], "std": [DenseVector(np.ones(DIM))]})
        )
        assert m.model_data_version > 0
        after = np.asarray(m.device_constants()["mean"])
        np.testing.assert_array_equal(after, np.full(DIM, 5.0))

    def test_online_model_publication_bumps_and_refreshes(self):
        m = _olr_model(np.ones(DIM), version=1)
        v0 = m.model_data_version
        c0 = np.asarray(m.device_constants()["coefficient"])
        m.publish_model_arrays((np.full(DIM, 2.0),), 2)
        assert m.model_data_version > v0
        c1 = np.asarray(m.device_constants()["coefficient"])
        np.testing.assert_array_equal(c0, np.ones(DIM))
        np.testing.assert_array_equal(c1, np.full(DIM, 2.0))


# ---------------------------------------------------------------------------
# tentpole: fused serving with live swaps — zero recompile, no torn reads
# ---------------------------------------------------------------------------

def test_fused_swap_zero_recompile_version_stamped():
    """N live publications against a served fused plan: the compiled
    program is reused (compile counter pinned), every output batch is
    scored by exactly the just-published version, and the fused plan
    object itself survives the swaps (no plan-cache thrash)."""
    model = _olr_model()
    pm = PipelineModel([_scaler(), model])
    batch = _device_batch()
    out = pm.transform(batch)[0]  # warm: compiles the segment once
    assert metrics.get_gauge("pipeline.fused_stages") == 2
    assert np.unique(np.asarray(out.column("modelVersion"))).tolist() == [0]
    plan_before = pm._fusion_plan()

    from flink_ml_tpu.linalg import DenseVector

    tracing.install_jax_hooks()
    compiles_before = metrics.get_counter("jit.compiles", 0)
    for v in range(1, 6):
        coeff = RNG.randn(DIM)
        if v % 2:  # the reference's actual publication API, live
            model.set_model_data(
                Table({"coefficient": [DenseVector(coeff)], "modelVersion": [v]})
            )
        else:
            model.publish_model_arrays((coeff,), v)
        out = pm.transform(batch)[0]
        versions = np.unique(np.asarray(out.column("modelVersion")))
        assert versions.tolist() == [v], "a served batch must carry ONE version"
        # the swap actually reached the compiled program: predictions
        # match the freshly-published coefficients
        X = np.asarray(batch.column("features"))
        want = (X.astype(np.float32) @ coeff.astype(np.float32) >= 0).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(out.column("pred")), want)
    assert metrics.get_counter("jit.compiles", 0) == compiles_before, (
        "a live model swap must not recompile the fused plan"
    )
    assert pm._fusion_plan() is plan_before, "swaps must reuse the cached plan"


def test_inflight_batch_keeps_its_dispatch_version():
    """A swap landing while a batch sits in the serving window must not
    rewrite that batch: each batch retires with the version it was
    DISPATCHED with (the no-torn-read contract of the swap path)."""
    model = _olr_model(np.ones(DIM), version=7)
    pm = PipelineModel([model])
    server = MicroBatchServer(pm, in_flight=2, device_input=True)

    def stream():
        yield Table({"features": RNG.randn(8, DIM).astype(np.float32)})
        # batch 0 is now dispatched (still in flight); swap before batch 1
        model.publish_model_arrays((np.full(DIM, -1.0),), 8)
        yield Table({"features": RNG.randn(8, DIM).astype(np.float32)})

    outs = list(server.serve(stream()))
    assert np.unique(np.asarray(outs[0].column("modelVersion"))).tolist() == [7]
    assert np.unique(np.asarray(outs[1].column("modelVersion"))).tolist() == [8]


def test_concurrent_publish_is_atomic():
    """Trainer thread hammering publications vs a reader thread snapping
    the published record: every snapshot is a consistent (version,
    centroids, weights) triple — value == version by construction, so a
    torn (new arrays, old version) read would be caught. Runs
    sanitizer-clean under FLINK_ML_TPU_SANITIZE=1."""
    model = OnlineKMeansModel()
    model.publish_model_arrays((np.zeros((3, DIM)), np.zeros(3)), 0)
    model.set_features_col("features").set_prediction_col("pred")
    stop = []
    tears = []

    def trainer():
        for v in range(1, 400):
            model.publish_model_arrays(
                (np.full((3, DIM), float(v)), np.full(3, float(v))), v
            )
        stop.append(True)

    def reader():
        while not stop:
            c, w = model.model_arrays()
            if c[0, 0] != w[0]:
                tears.append((c[0, 0], w[0]))
            pub = model._published
            if pub.centroids[0, 0] != float(pub.version) and pub.version > 0:
                tears.append((pub.version, pub.centroids[0, 0]))

    t1 = flow.spawn(trainer, name="hotswap.trainer")
    t2 = flow.spawn(reader, name="hotswap.reader")
    t1.join(timeout=60)
    t2.join(timeout=60)
    assert not t1.is_alive() and not t2.is_alive()
    assert tears == [], f"torn publication observed: {tears[:3]}"
    assert model.model_version == 399


# ---------------------------------------------------------------------------
# promotion gate
# ---------------------------------------------------------------------------

class TestPromotionGate:
    def test_nan_candidate_rejected_and_counted(self):
        model = _olr_model(np.ones(DIM), version=1)
        lc = ModelLifecycle(model)
        before = metrics.get_counter("lifecycle.promoteRejected", 0)
        bad = np.ones(DIM)
        bad[2] = np.nan
        with pytest.raises(PromotionRejected) as ei:
            lc.promote((bad,))
        assert ei.value.reason == "nonfinite"
        assert metrics.get_counter("lifecycle.promoteRejected", 0) == before + 1
        # serving model untouched
        np.testing.assert_array_equal(model.coefficient, np.ones(DIM))
        assert model.model_version == 1

    def test_shape_and_arity_rejected(self):
        model = _olr_model(np.ones(DIM), version=1)
        lc = ModelLifecycle(model)
        with pytest.raises(PromotionRejected) as ei:
            lc.promote((np.ones(DIM + 1),))
        assert ei.value.reason == "shape"
        with pytest.raises(PromotionRejected) as ei:
            lc.promote((np.ones(DIM), np.ones(DIM)))
        assert ei.value.reason == "arity"

    def test_canary_regression_rejected_but_healthy_step_promotes(self):
        coeff = np.full(DIM, 0.5)
        model = _olr_model(coeff, version=1)
        canary = {"features": RNG.randn(16, DIM).astype(np.float32)}
        lc = ModelLifecycle(model, canary=canary, canary_rtol=0.2)
        promoted = lc.promote((coeff + 0.001,))  # tiny move: passes
        assert promoted.version_id == 2
        flipped = -5.0 * coeff  # sign-flips every canary prediction
        with pytest.raises(PromotionRejected) as ei:
            lc.promote((flipped,))
        assert ei.value.reason == "canary"
        assert model.model_version == 2

    def test_device_candidate_accepted(self):
        """Trainer updates arrive as device arrays (the online loop yields
        jnp carries); the gate pulls them in one packed readback."""
        model = _olr_model(np.zeros(DIM), version=0)
        lc = ModelLifecycle(model)
        entry = lc.promote((jax.device_put(np.full(DIM, 0.25)),))
        assert entry.version_id == 1
        np.testing.assert_array_equal(model.coefficient, np.full(DIM, 0.25))


# ---------------------------------------------------------------------------
# version ring + automatic rollback + quarantine
# ---------------------------------------------------------------------------

class TestRollback:
    def _lifecycle(self, model):
        return ModelLifecycle(model, retained=3, health_window=4, error_rate_trigger=0.5)

    def test_guard_error_window_triggers_bit_exact_rollback(self):
        model = _olr_model(np.zeros(DIM), version=0)
        lc = self._lifecycle(model)
        good = RNG.randn(DIM)
        lc.promote((good,))  # v1
        lc.record_serve_ok()  # v1 proven good
        lc.promote((RNG.randn(DIM),))  # v2: the bad one
        rollbacks = metrics.get_counter("lifecycle.rollback", 0)
        for _ in range(4):
            lc.record_guard_error(ValueError("guard fired"))
        assert metrics.get_counter("lifecycle.rollback", 0) == rollbacks + 1
        # bit-exact restore of the retained last-good version, original id
        assert model.model_version == 1
        np.testing.assert_array_equal(model.coefficient, good)
        assert lc.quarantined
        with pytest.raises(TrainerQuarantined):
            lc.promote((RNG.randn(DIM),))
        assert any(e.kind == "quarantined" for e in lc.events)
        lc.release_quarantine()
        assert lc.promote((good + 0.1,)).version_id > 2

    def test_ring_is_bounded(self):
        model = _olr_model(np.zeros(DIM), version=0)
        lc = self._lifecycle(model)  # retained=3
        for _ in range(6):
            lc.promote((RNG.randn(DIM),))
        assert len(lc.retained_versions()) == 3
        assert lc.retained_versions() == [4, 5, 6]

    def test_manual_rollback_without_serve_evidence_targets_seed(self):
        """With no serve outcome recorded since the seed, last-good is the
        seed version the server started on — rollback restores it."""
        model = _olr_model(np.zeros(DIM), version=0)
        lc = self._lifecycle(model)
        lc.promote((RNG.randn(DIM),))
        lc.promote((RNG.randn(DIM),))
        lc.rollback("operator")
        assert model.model_version == 0
        np.testing.assert_array_equal(model.coefficient, np.zeros(DIM))


# ---------------------------------------------------------------------------
# persistence: the JobSnapshot meta contract
# ---------------------------------------------------------------------------

def test_resume_republishes_persisted_version_not_zero(tmp_path):
    model = _olr_model(np.zeros(DIM), version=0)
    lc = ModelLifecycle(model, checkpoint_dir=str(tmp_path), job_key="tws")
    final = RNG.randn(DIM)
    lc.promote((RNG.randn(DIM),))
    lc.record_serve_ok()
    lc.promote((final,))
    # "restart": a fresh process builds the model from initial data again
    model2 = _olr_model(np.zeros(DIM), version=0)
    lc2 = ModelLifecycle(model2, checkpoint_dir=str(tmp_path), job_key="tws")
    assert model2.model_version == 2, "resume must re-publish the persisted version"
    np.testing.assert_array_equal(model2.coefficient, final)
    assert lc2.last_good == 1
    next_entry = lc2.promote((final + 1.0,))
    assert next_entry.version_id == 3, "version ids must continue, not restart"


# ---------------------------------------------------------------------------
# the chaos soak (deterministic invariants)
# ---------------------------------------------------------------------------

def test_train_while_serving_chaos_soak(tmp_path):
    """Trainer thread promoting through the gated lifecycle (with NaN
    poisonings, flaky snapshot I/O and a mid-publish kill) vs a serving
    loop on the fused plan. Invariants, independent of interleaving:

    - every served batch carries exactly ONE model version;
    - only gate-accepted versions are ever served (a poisoned candidate's
      coefficients never reach traffic: no NaN output rows);
    - served versions are monotone non-decreasing (pre-rollback phase)
      and staleness is bounded: after the trainer finishes, the next
      served batch carries the newest promoted version;
    - zero recompiles after warmup (the swaps reuse the compiled plan);
    - the post-soak rollback restores the retained last-good bit-exactly.
    """
    model = _olr_model()
    lc = ModelLifecycle(
        model,
        retained=4,
        health_window=4,
        error_rate_trigger=0.5,
        checkpoint_dir=str(tmp_path),
        job_key="soak",
    )
    pm = PipelineModel([_scaler(), model])
    server = MicroBatchServer(pm, in_flight=2, device_input=True, lifecycle=lc)

    accepted: list = []
    rejections = []
    base = np.zeros(DIM)

    def trainer():
        for i in range(1, 13):
            candidate = base + 0.05 * i
            if i % 4 == 0:  # NaN-poisoned update: the gate must eat it
                poisoned = candidate.copy()
                poisoned[i % DIM] = np.nan
                try:
                    lc.promote((poisoned,))
                except PromotionRejected as e:
                    rejections.append(e)
                continue
            if i == 5:  # flaky snapshot I/O under the retry budget
                with faults.flaky("snapshot.write", times=2):
                    accepted.append(lc.promote((candidate,)).version_id)
                continue
            if i == 9:  # trainer killed mid-publish (after persist, pre-swap)
                with faults.inject("lifecycle.swap", after=1):
                    try:
                        lc.promote((candidate,))
                    except InjectedFault:
                        pass
                # the recovered trainer re-promotes; ids stay monotone
                accepted.append(lc.promote((candidate,)).version_id)
                continue
            accepted.append(lc.promote((candidate,)).version_id)

    trainer_thread = flow.spawn(trainer, name="soak.trainer")

    def stream(n=24):
        for i in range(n):
            yield Table({"features": RNG.randn(8, DIM).astype(np.float32)})

    pm.transform(_device_batch())  # warm the fused plan before pinning compiles
    tracing.install_jax_hooks()
    compiles_before = metrics.get_counter("jit.compiles", 0)

    served_versions = []
    for out in server.serve(stream()):
        versions = np.unique(np.asarray(out.column("modelVersion")))
        assert len(versions) == 1, "torn read: one batch served by two versions"
        served_versions.append(int(versions[0]))
        assert np.all(np.isfinite(np.asarray(out.column("pred")))), (
            "a rejected (NaN) candidate reached traffic"
        )
    trainer_thread.join(timeout=120)
    assert not trainer_thread.is_alive(), "trainer wedged"

    assert len(rejections) == 3, "every poisoned candidate must be rejected"
    assert metrics.get_counter("jit.compiles", 0) == compiles_before, (
        f"{metrics.get_counter('jit.compiles', 0) - compiles_before} recompiles "
        "during the soak — swaps must be zero-recompile"
    )
    valid = set(accepted) | {0}
    assert set(served_versions) <= valid, (
        f"served versions {sorted(set(served_versions) - valid)} were never promoted"
    )
    assert served_versions == sorted(served_versions), (
        "served versions went backwards without a rollback"
    )
    # staleness bound: with the trainer done, the next batch serves the tip
    tip = list(server.serve(stream(n=1)))[0]
    assert np.unique(np.asarray(tip.column("modelVersion"))).tolist() == [accepted[-1]]
    lc.record_serve_ok()

    # rollback leg: a bad-but-finite promotion slips the gate, guard errors
    # accumulate, traffic rolls back bit-exactly to the retained last-good
    good_arrays = tuple(np.copy(a) for a in model.model_arrays())
    good_version = model.model_version
    lc.promote((base + 99.0,))
    for _ in range(4):
        lc.record_guard_error(ValueError("downstream guard fired"))
    assert model.model_version == good_version
    np.testing.assert_array_equal(model.coefficient, good_arrays[0])
    assert lc.quarantined and lc.rollback_count == 1
    after = list(server.serve(stream(n=1)))[0]
    assert np.unique(np.asarray(after.column("modelVersion"))).tolist() == [good_version]
