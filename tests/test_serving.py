"""MicroBatchServer — double-buffered fused micro-batch serving tests.

Pins the serving contract: in-order bit-identical outputs under bucket
padding, bounded in-flight deferral of guard errors (late by at most the
window, never dropped or reordered), and per-batch host syncs independent
of pipeline depth.
"""

import numpy as np
import pytest

import jax

from flink_ml_tpu import config
from flink_ml_tpu.pipeline import PipelineModel
from flink_ml_tpu.serving import MicroBatchServer, _next_bucket, serve_stream
from flink_ml_tpu.table import SparseBatch, StreamTable, Table
from flink_ml_tpu.utils import metrics

RNG = np.random.RandomState(11)


def _scaler_pipeline(d=4):
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel

    ss = StandardScalerModel()
    ss.mean = RNG.randn(d)
    ss.std = np.abs(RNG.randn(d)) + 0.1
    ss.set_input_col("features").set_output_col("scaled")
    norm = Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm")
    return PipelineModel([ss, norm])


def _batches(sizes, d=4):
    return [Table({"features": RNG.randn(n, d).astype(np.float32)}) for n in sizes]


def test_bucket_schedule():
    assert _next_bucket(1, None) == 8
    assert _next_bucket(8, None) == 8
    assert _next_bucket(9, None) == 16
    assert _next_bucket(700, None) == 1024
    assert _next_bucket(5, [16, 64]) == 16
    assert _next_bucket(65, [16, 64]) == 65  # beyond largest bucket: exact
    assert _next_bucket(0, None) == 0


def test_serve_in_order_parity():
    pm = _scaler_pipeline()
    batches = _batches([5, 13, 16, 3, 40])
    outs = serve_stream(pm, StreamTable.from_batches(batches))
    assert [t.num_rows for t in outs] == [5, 13, 16, 3, 40]
    with config.pipeline_fusion_mode("off"):
        for batch, out in zip(batches, outs):
            # reference: the eager per-stage path on the SAME device-born
            # batch (a host-table transform computes the scaler in numpy
            # f64 — a different, legitimate answer)
            dev = Table(
                {name: jax.device_put(batch.column(name)) for name in batch.column_names}
            )
            ref = pm.transform(dev)[0]
            assert np.array_equal(
                np.asarray(ref.column("norm")), np.asarray(out.column("norm"))
            ), "padded+fused serving output differs from eager per-batch transform"


def test_padding_bounds_compiles():
    """Batches sharing a bucket share the compiled segment program."""
    from flink_ml_tpu.obs import tracing

    pm = _scaler_pipeline()
    tracing.install_jax_hooks()

    def compiles():
        return metrics.snapshot()["counters"].get("jit.compile", 0)

    warm = _batches([7])  # bucket 8
    list(MicroBatchServer(pm).serve(StreamTable.from_batches(warm)))
    before = compiles()
    more = _batches([5, 3, 8, 6, 2])  # all bucket 8: zero new compiles
    outs = list(MicroBatchServer(pm).serve(StreamTable.from_batches(more)))
    assert [t.num_rows for t in outs] == [5, 3, 8, 6, 2]
    assert compiles() == before, "same-bucket batches must not recompile"
    assert metrics.get_gauge("serving.buckets") == 1


def test_guard_error_deferred_not_dropped():
    """A bad batch raises when IT is retired from the window — later than
    eager by at most in_flight batches, with every prior batch's output
    already yielded correctly."""
    from flink_ml_tpu.models.feature.bucketizer import Bucketizer

    stage = (
        Bucketizer()
        .set_input_cols("a")
        .set_output_cols("oa")
        .set_splits_array([[0.0, 1.0, 2.0]])
    )
    pm = PipelineModel([stage])
    good = Table({"a": np.array([0.5, 1.5], dtype=np.float32)})
    bad = Table({"a": np.array([0.5, 99.0], dtype=np.float32)})  # out of range
    stream = StreamTable.from_batches([good, bad, good])
    got = []
    with pytest.raises(ValueError, match="invalid value"):
        for out in MicroBatchServer(pm, in_flight=2).serve(stream):
            got.append(np.asarray(out.column("oa")))
    assert len(got) == 1  # the batch before the bad one came through intact
    assert got[0].tolist() == [0.0, 1.0]


def test_per_batch_syncs_independent_of_stage_count():
    """The double-buffer claim: a deep all-device pipeline with guard
    stages pays ONE transform sync per batch — not one per stage."""
    from flink_ml_tpu.models.feature.binarizer import Binarizer
    from flink_ml_tpu.models.feature.bucketizer import Bucketizer
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.models.feature.vectorassembler import VectorAssembler

    ss = StandardScalerModel()
    ss.mean = RNG.randn(5)
    ss.std = np.abs(RNG.randn(5)) + 0.1
    ss.set_input_col("assembled").set_output_col("scaled")
    pm = PipelineModel(
        [
            VectorAssembler().set_input_cols("va", "vb").set_output_col("assembled"),
            ss,
            Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm"),
            Bucketizer()
            .set_input_cols("raw")
            .set_output_cols("bucket")
            .set_splits_array([[-100.0, 0.0, 100.0]]),
            Binarizer().set_input_cols("bucket").set_output_cols("bin").set_thresholds(0.5),
        ]
    )

    def batch(n):
        return Table(
            {
                "va": RNG.randn(n, 2).astype(np.float32),
                "vb": RNG.randn(n, 3).astype(np.float32),
                "raw": RNG.randn(n).astype(np.float32),
            }
        )

    batches = [batch(6) for _ in range(4)]
    # warm the compile for bucket 8
    list(MicroBatchServer(pm).serve(StreamTable.from_batches([batch(6)])))

    before = metrics.snapshot()["counters"].get("iteration.host_sync.transform", 0)
    outs = list(MicroBatchServer(pm).serve(StreamTable.from_batches(batches)))
    after = metrics.snapshot()["counters"].get("iteration.host_sync.transform", 0)
    assert len(outs) == 4
    assert after - before == len(batches), (
        f"wanted 1 sync per batch (4), got {after - before} — "
        "per-batch syncs must not scale with stage count"
    )


def test_sparse_column_through_serving():
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel,
    )

    m = LogisticRegressionModel()
    m.coefficient = RNG.randn(16)
    m.set_features_col("features").set_prediction_col("pred")
    pm = PipelineModel([m])

    def sparse_batch(n):
        return Table(
            {
                "features": SparseBatch(
                    16,
                    RNG.randint(0, 16, size=(n, 3)).astype(np.int32),
                    RNG.rand(n, 3).astype(np.float32),
                )
            }
        )

    batches = [sparse_batch(5), sparse_batch(11)]
    outs = serve_stream(pm, StreamTable.from_batches(batches))
    assert [t.num_rows for t in outs] == [5, 11]
    with config.pipeline_fusion_mode("off"):
        for batch, out in zip(batches, outs):
            ref = pm.transform(batch)[0]
            assert np.array_equal(
                np.asarray(ref.column("pred")), np.asarray(out.column("pred"))
            )


def test_empty_stream_and_empty_batch():
    pm = _scaler_pipeline()
    assert serve_stream(pm, StreamTable.from_batches([])) == []
    outs = serve_stream(pm, StreamTable.from_batches(_batches([0, 4])))
    assert [t.num_rows for t in outs] == [0, 4]


def test_server_rejects_non_pipeline():
    with pytest.raises(TypeError):
        MicroBatchServer(object())


# ---------------------------------------------------------------------------
# flow-control sweep: early-exit cleanup, admission, deadlines, retries
# ---------------------------------------------------------------------------

def test_early_termination_releases_window():
    """A consumer that stops after 2 batches must not leak the staged
    in-flight window: closing the generator drains/releases every pending
    batch and frees its queue slots (serving.cancelled counts them)."""
    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=3)
    stream = StreamTable.from_batches(_batches([4, 4, 4, 4, 4, 4]))
    before = metrics.get_counter("serving.cancelled", 0)
    got = []
    it = server.serve(stream)
    for out in it:
        got.append(out)
        if len(got) == 2:
            break
    it.close()  # the consumer walks away mid-stream
    assert len(got) == 2
    assert server._window is not None and len(server._window) == 0, (
        "in-flight batches leaked past generator close"
    )
    assert server._window.closed
    released = metrics.get_counter("serving.cancelled", 0) - before
    assert released > 0, "the pending window must be accounted as released"
    assert server.health().cancelled == released


def test_deferred_guard_error_releases_window():
    """When a deferred guard error terminates serve(), the batches still
    parked behind the failing one are released too — no staged buffers or
    slots survive the raise."""
    from flink_ml_tpu.models.feature.bucketizer import Bucketizer

    stage = (
        Bucketizer()
        .set_input_cols("a")
        .set_output_cols("oa")
        .set_splits_array([[0.0, 1.0, 2.0]])
    )
    pm = PipelineModel([stage])
    good = Table({"a": np.array([0.5, 1.5], dtype=np.float32)})
    bad = Table({"a": np.array([0.5, 99.0], dtype=np.float32)})
    server = MicroBatchServer(pm, in_flight=3)
    with pytest.raises(ValueError, match="invalid value"):
        # bad retires first in the drain loop; good batches queue behind it
        list(server.serve(StreamTable.from_batches([bad, good, good])))
    assert len(server._window) == 0 and server._window.closed


def test_submit_rejects_when_admission_full():
    """The push API's admission control: a burst beyond the admission
    queue fast-fails with a typed ServerOverloaded carrying the live
    depth — bounded memory instead of grow-until-OOM."""
    from flink_ml_tpu.serving import ServerOverloaded

    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=2, admission=3)
    submitted, rejected = 0, 0
    for _ in range(40):
        try:
            server.submit(Table({"features": RNG.randn(8, 4).astype(np.float32)}))
            submitted += 1
        except ServerOverloaded as e:
            rejected += 1
            assert e.depth <= e.capacity == 3
    server.close()
    results = list(server.results())
    assert len(results) == submitted, "every admitted request must retire"
    assert [r.seq for r in results] == sorted(r.seq for r in results)
    assert rejected > 0, "an unpaced 40-burst must overflow admission=3"
    h = server.health()
    assert h.rejected == rejected and h.submitted == submitted
    assert server._requests.stats.peak_depth <= 3
    assert server._window.stats.peak_depth <= 2


def test_submit_deadline_expires_before_dispatch():
    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=2, admission=8)
    seqs = [
        server.submit(
            Table({"features": RNG.randn(8, 4).astype(np.float32)}), deadline_ms=0.0
        )
        for _ in range(3)
    ]
    server.close()
    results = {r.seq: r for r in server.results()}
    assert set(results) == set(seqs)
    assert all(r.status in ("expired", "late") for r in results.values())
    h = server.health()
    assert h.expired + h.late == 3
    assert metrics.get_counter("serving.deadlineMiss", 0) >= 3


def test_push_per_request_error_does_not_kill_stream():
    """One bad batch surfaces as a status='error' result; later requests
    still retire ok."""
    from flink_ml_tpu.models.feature.bucketizer import Bucketizer

    stage = (
        Bucketizer()
        .set_input_cols("a")
        .set_output_cols("oa")
        .set_splits_array([[0.0, 1.0, 2.0]])
    )
    pm = PipelineModel([stage])
    server = MicroBatchServer(pm, in_flight=2, admission=8)
    server.submit(Table({"a": np.array([0.5, 1.5], dtype=np.float32)}))
    server.submit(Table({"a": np.array([0.5, 99.0], dtype=np.float32)}))  # guard fires
    server.submit(Table({"a": np.array([1.5, 0.5], dtype=np.float32)}))
    server.close()
    results = list(server.results())
    assert [r.status for r in results] == ["ok", "error", "ok"]
    assert isinstance(results[1].error, ValueError)
    assert server.health().errors == 1


def test_serving_batch_transient_fault_retried_bit_identical():
    """A flaky batch dispatch under the retry budget is invisible to the
    results; with the budget at 0 the same fault is fatal."""
    from flink_ml_tpu.ckpt import faults
    from flink_ml_tpu.ckpt.faults import TransientFault

    pm = _scaler_pipeline()
    batches = _batches([5, 9, 7])
    clean = serve_stream(pm, StreamTable.from_batches(batches))
    with config.transient_retry_mode(3):
        with faults.flaky("serving.batch", times=2) as plan:
            retried = serve_stream(pm, StreamTable.from_batches(batches))
    assert plan.failures == 2
    for a, b in zip(clean, retried):
        np.testing.assert_array_equal(
            np.asarray(a.column("norm")), np.asarray(b.column("norm"))
        )
    with config.transient_retry_mode(0):
        with faults.flaky("serving.batch", times=1):
            with pytest.raises(TransientFault):
                serve_stream(pm, StreamTable.from_batches(batches))


def test_health_snapshot_shape():
    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=2)
    list(server.serve(StreamTable.from_batches(_batches([4, 4]))))
    h = server.health()
    assert h.inFlight == 2 and h.windowDepth == 0
    assert h.bucketsSeen == 1
    assert h.emaBatchMs >= 0.0


def test_health_reports_hbm_ledger(mesh8):
    """ROADMAP item 3 memory surface: ServerHealth carries the HBM
    ledger's live bytes and peak watermark (docs/observability.md
    "Device memory") — serving uploads ride the `serving` category."""
    from flink_ml_tpu.obs import memledger

    memledger.reset()
    try:
        pm = _scaler_pipeline()
        server = MicroBatchServer(pm, in_flight=2)
        list(server.serve(StreamTable.from_batches(_batches([4, 4]))))
        h = server.health()
        assert h.hbmLiveBytes == memledger.live_bytes()
        assert h.hbmPeakBytes == memledger.peak_bytes()
        # staged serving batches + published model constants went through
        # the accounted funnels, so the fit's peak is nonzero
        assert h.hbmPeakBytes > 0
        assert h.hbmLiveBytes <= h.hbmPeakBytes
    finally:
        memledger.reset()


# ---------------------------------------------------------------------------
# SLO surface: per-stage latency histograms (obs/hist.py) — ISSUE 12
# ---------------------------------------------------------------------------

@pytest.fixture
def _clean_hist():
    from flink_ml_tpu.obs import hist

    hist.reset()
    hist.configure(True)
    yield hist
    hist.reset()
    hist.configure(True)


def test_server_health_stage_latency_percentiles(_clean_hist):
    """ISSUE 12 acceptance: ServerHealth reports p50/p99/p999 per-stage
    latency (queue-wait, batch-form, dispatch, readback) from the
    obs/hist.py histograms."""
    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=2, admission=16)
    for _ in range(8):
        server.submit(Table({"features": RNG.randn(8, 4).astype(np.float32)}))
    server.close()
    results = list(server.results())
    assert all(r.status == "ok" for r in results)
    h = server.health()
    for stage in ("queueWait", "batchForm", "dispatch", "readback"):
        p = h.stageLatencyMs[stage]
        assert p["count"] >= 8, stage
        assert 0.0 <= p["p50"] <= p["p99"] <= p["p999"], stage
    # no deadline was set: the stage is reported, but with no
    # observations its percentile summary is None (never fabricated)
    assert h.stageLatencyMs["deadlineMargin"] is None
    # with a generous deadline the margin distribution appears too
    server2 = MicroBatchServer(pm, in_flight=2, admission=16)
    server2.submit(
        Table({"features": RNG.randn(8, 4).astype(np.float32)}), deadline_ms=60_000.0
    )
    server2.close()
    assert [r.status for r in server2.results()] == ["ok"]
    assert server2.health().stageLatencyMs["deadlineMargin"]["count"] >= 1


def test_serving_bit_identical_with_histograms_on_vs_off(_clean_hist):
    """ISSUE 12 acceptance: bit-for-bit identical serving results with
    histograms on vs off (the SLO surface never touches the data path)."""
    from flink_ml_tpu.obs import hist

    pm = _scaler_pipeline()
    batches = _batches([5, 13, 9])
    on = serve_stream(pm, StreamTable.from_batches(batches))
    assert hist.percentiles("serving.dispatchMs")["count"] >= 3
    hist.reset()
    hist.configure(False)
    off = serve_stream(pm, StreamTable.from_batches(batches))
    assert hist.snapshot() == {}  # recording really was off
    for a, b in zip(on, off):
        np.testing.assert_array_equal(
            np.asarray(a.column("norm")), np.asarray(b.column("norm"))
        )


def test_deadline_miss_cause_attribution(_clean_hist):
    """`serving.deadlineMiss` splits into expired-in-queue vs
    late-after-dispatch; the old name stays as their sum."""
    import time as _time

    from flink_ml_tpu import flow
    from flink_ml_tpu.obs import hist

    base_sum = metrics.get_counter("serving.deadlineMiss", 0)
    base_expired = metrics.get_counter("serving.deadlineMiss.expired", 0)
    base_late = metrics.get_counter("serving.deadlineMiss.late", 0)

    # expired IN QUEUE: 0ms deadline passes before dispatch
    pm = _scaler_pipeline()
    server = MicroBatchServer(pm, in_flight=2, admission=8)
    server.submit(
        Table({"features": RNG.randn(8, 4).astype(np.float32)}), deadline_ms=0.0
    )
    server.close()
    (r,) = list(server.results())
    assert r.status == "expired"
    assert metrics.get_counter("serving.deadlineMiss.expired", 0) == base_expired + 1

    # late AFTER dispatch: retire a really-transformed batch whose
    # deadline already passed (white-box: deterministic, no sleep races)
    late_server = MicroBatchServer(pm, in_flight=2)
    late_server._out = flow.BoundedChannel(4, name="test.results")
    staged, n = late_server._stage_batch(
        Table({"features": RNG.randn(8, 4).astype(np.float32)})
    )
    out, pending = pm.transform_deferred(staged)
    late_server._retire(
        (((0, _time.monotonic() - 1.0, 0, n, None),), out, pending, n)
    )
    result = late_server._out.get()
    assert result.status == "late"
    assert metrics.get_counter("serving.deadlineMiss.late", 0) == base_late + 1
    assert hist.percentiles("serving.lateByMs")["count"] >= 1

    # compatibility: the old counter is exactly the sum of the causes
    assert metrics.get_counter("serving.deadlineMiss", 0) == base_sum + 2


# ---------------------------------------------------------------------------
# continuous batching (ISSUE 19 tentpole): mid-flight forming, budget flush
# ---------------------------------------------------------------------------

def _push_all(server, batches, tenant=None):
    """Submit every batch, close, and collect results keyed by seq."""
    seqs = [server.submit(b, tenant=tenant) for b in batches]
    server.close()
    return seqs, {r.seq: r for r in server.results()}


@pytest.mark.parametrize(
    "batching,form_rows", [("continuous", 32), ("fixed", 8)], ids=["continuous", "fixed"]
)
def test_continuous_bit_identical_to_request_mode(batching, form_rows):
    """ISSUE 19 acceptance: continuous batching returns bit-identical
    per-request rows — coalescing is a scheduling decision, never a
    numerics decision (same bucket padding, same fused plan). So does the
    fixed-batch baseline it is compared with."""
    pm = _scaler_pipeline()
    sizes = [3, 5, 2, 8, 1, 4, 7, 2]
    batches = _batches(sizes)
    ref_server = MicroBatchServer(pm, in_flight=2, admission=16, buckets=(8, 32))
    _, ref = _push_all(ref_server, batches)
    cont = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        buckets=(8, 32),
        batching=batching,
        form_rows=form_rows,
        form_budget_ms=20.0,
    )
    _, got = _push_all(cont, batches)
    assert sorted(got) == sorted(ref) == list(range(len(sizes)))
    for seq in ref:
        assert ref[seq].status == "ok" and got[seq].status == "ok"
        assert got[seq].table.num_rows == sizes[seq]
        np.testing.assert_array_equal(
            np.asarray(ref[seq].table.column("norm")),
            np.asarray(got[seq].table.column("norm")),
        )


def test_continuous_bucket_full_flushes_immediately():
    """A forming batch that reaches `form_rows` dispatches NOW — it does
    not sit out the rest of its forming budget."""
    import time as _time

    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        buckets=(8,),
        batching="continuous",
        form_rows=8,
        form_budget_ms=10_000.0,  # a budget flush would blow the timing assert
    )
    before = metrics.get_counter("serving.coalesced", 0)
    t0 = _time.monotonic()
    server.submit(Table({"features": RNG.randn(4, 4).astype(np.float32)}))
    server.submit(Table({"features": RNG.randn(4, 4).astype(np.float32)}))
    it = server.results()
    results = [next(it), next(it)]
    dt = _time.monotonic() - t0
    server.close()
    assert [r.status for r in results] == ["ok", "ok"]
    assert [r.table.num_rows for r in results] == [4, 4]
    assert dt < 5.0, "bucket-full flush must not wait for the forming budget"
    assert metrics.get_counter("serving.coalesced", 0) >= before + 2


def test_continuous_form_budget_flushes_partial_batch():
    """A lone request in a huge bucket dispatches once its forming budget
    expires — continuous batching never strands a partial batch."""
    import time as _time

    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        batching="continuous",
        form_rows=64,
        form_budget_ms=30.0,
    )
    t0 = _time.monotonic()
    server.submit(Table({"features": RNG.randn(2, 4).astype(np.float32)}))
    r = next(server.results())
    dt = _time.monotonic() - t0
    server.close()
    assert r.status == "ok" and r.table.num_rows == 2
    assert dt < 5.0, "the forming-budget flush must fire without more arrivals"


def test_fixed_batching_waits_for_full_bucket():
    """The fixed baseline only flushes on a full bucket (or close) —
    the structural latency continuous batching removes."""
    import time as _time

    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        batching="fixed",
        form_rows=8,
    )
    server.submit(Table({"features": RNG.randn(4, 4).astype(np.float32)}))
    _time.sleep(0.25)  # many forming budgets; fixed mode must still hold it
    assert len(server._out) == 0, "fixed batching must not flush a partial bucket"
    server.close()  # drain flush: the partial batch still dispatches
    (r,) = list(server.results())
    assert r.status == "ok" and r.table.num_rows == 4


def test_continuous_never_coalesces_across_tenants():
    """Two tenants' signature-identical requests stay separate forming
    batches — results carry their tenant, and `serving.coalesced` stays
    flat (a merged dispatch would route one tenant through the other's
    model)."""
    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        batching="continuous",
        form_rows=8,
        form_budget_ms=60.0,
    )
    before = metrics.get_counter("serving.coalesced", 0)
    server.submit(Table({"features": RNG.randn(4, 4).astype(np.float32)}), tenant="a")
    server.submit(Table({"features": RNG.randn(4, 4).astype(np.float32)}), tenant="b")
    server.close()
    results = list(server.results())
    assert sorted(r.tenant for r in results) == ["a", "b"]
    assert all(r.status == "ok" for r in results)
    assert metrics.get_counter("serving.coalesced", 0) == before


def test_continuous_incompatible_signature_flushes_old_first():
    """An arriving request whose columns don't match the forming batch
    flushes the OLD batch first — per-tenant FIFO survives coalescing."""
    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        batching="continuous",
        form_rows=64,
        form_budget_ms=60.0,
    )
    before = metrics.get_counter("serving.coalesced", 0)
    server.submit(Table({"features": RNG.randn(3, 4).astype(np.float32)}))
    server.submit(Table({"features": RNG.randn(3, 4).astype(np.float64)}))  # new sig
    server.close()
    results = list(server.results())
    assert [r.seq for r in results] == [0, 1], "old forming batch must retire first"
    assert all(r.status == "ok" for r in results)
    assert [r.table.num_rows for r in results] == [3, 3]
    assert metrics.get_counter("serving.coalesced", 0) == before


def test_continuous_expired_while_forming_is_shed():
    """A request whose deadline passes inside the forming buffer is shed
    as expired at flush time — it never pays dispatch."""
    import time as _time

    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=2,
        admission=16,
        batching="fixed",  # never budget-flushes: the deadline passes forming
        form_rows=64,
    )
    server.submit(
        Table({"features": RNG.randn(2, 4).astype(np.float32)}), deadline_ms=30.0
    )
    _time.sleep(0.08)
    server.close()
    (r,) = list(server.results())
    assert r.status == "expired"


# ---------------------------------------------------------------------------
# multi-tenant admission: per-tenant quota gates + fairness under flood
# ---------------------------------------------------------------------------

def test_tenant_quota_rejects_are_typed_and_attributed():
    from flink_ml_tpu.serving import ServerOverloaded

    pm = _scaler_pipeline()
    server = MicroBatchServer(
        pm,
        in_flight=1,
        admission=32,
        batching="continuous",
        form_rows=4,
        tenant_quotas={"A": 2},
    )
    before = metrics.get_counter("serving.rejected.tenant.A", 0)
    accepted, rejected = 0, 0
    for _ in range(12):
        try:
            server.submit(
                Table({"features": RNG.randn(4, 4).astype(np.float32)}), tenant="A"
            )
            accepted += 1
        except ServerOverloaded as e:
            rejected += 1
            assert e.channel == "serving.tenant.A"
            assert e.capacity == 2
    assert rejected > 0, "an unpaced 12-burst must overflow quota=2"
    server.close()
    results = list(server.results())
    assert len(results) == accepted
    assert all(r.tenant == "A" for r in results)
    assert metrics.get_counter("serving.rejected.tenant.A", 0) == before + rejected
    h = server.health()
    assert h.tenantAdmission["A"]["rejected"] == rejected
    assert h.tenantAdmission["A"]["capacity"] == 2


def test_tenant_fairness_soak():
    """ISSUE 19 satellite: tenant A floods past its quota; its overflow
    fast-fails with the typed per-tenant reject while tenant B's
    closed-loop latency stays within tolerance of B running alone."""
    import time as _time

    from flink_ml_tpu.serving import ServerOverloaded

    pm = _scaler_pipeline()

    def b_batch():
        return Table({"features": RNG.randn(4, 4).astype(np.float32)})

    def closed_loop_b(server, rounds, flood_a=None):
        """Submit one B request at a time, waiting for ITS result; returns
        per-request client latencies (ms)."""
        it = server.results()
        lat = []
        for _ in range(rounds):
            if flood_a is not None:
                flood_a()
            t0 = _time.monotonic()
            seq = server.submit(b_batch(), tenant="B")
            while True:
                r = next(it)
                if r.tenant == "B" and r.seq == seq:
                    break
            assert r.status == "ok"
            lat.append((_time.monotonic() - t0) * 1000.0)
        return lat

    def make_server():
        return MicroBatchServer(
            pm,
            in_flight=2,
            admission=32,
            buckets=(8,),
            batching="continuous",
            form_rows=8,
            form_budget_ms=2.0,
            tenant_quotas={"A": 4, "B": 8},
        )

    # solo baseline: B alone (first round also absorbs any compile)
    solo = make_server()
    solo_lat = closed_loop_b(solo, 20)
    solo.close()
    list(solo.results())
    solo_p99 = float(np.percentile(solo_lat[1:], 99))

    # soak: A floods past quota=4 before every B submit
    soak = make_server()
    a_rejects = [0]

    def flood_a():
        for _ in range(8):
            try:
                soak.submit(b_batch(), tenant="A")
            except ServerOverloaded as e:
                assert e.channel == "serving.tenant.A"
                a_rejects[0] += 1

    soak_lat = closed_loop_b(soak, 20, flood_a=flood_a)
    soak.close()
    list(soak.results())
    soak_p99 = float(np.percentile(soak_lat[1:], 99))

    assert a_rejects[0] > 0, "the flood must overflow tenant A's quota"
    h = soak.health()
    assert h.tenantAdmission["A"]["rejected"] == a_rejects[0]
    # fairness: B's p99 under flood stays within a generous envelope of
    # its solo p99 (A's overflow was shed at admission, not queued ahead)
    assert soak_p99 <= 5.0 * solo_p99 + 100.0, (
        f"tenant B p99 {soak_p99:.1f}ms vs solo {solo_p99:.1f}ms — "
        "a quota'd flood must not starve the well-behaved tenant"
    )
