"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline: the north-star workload from BASELINE.md — the reference's
logisticregression-benchmark.json (10M points x dim 100, maxIter 20,
globalBatchSize 100k, flink-ml-benchmark/src/main/resources/
logisticregression-benchmark.json) — reported as training records/s/chip.

The reference publishes no CPU number for this workload, so `vs_baseline`
is measured here against a same-process numpy implementation of the exact
reference SGD semantics (SGD.java:82-292 math, same batch schedule, same
timing method: wall clock around datagen+fit, BenchmarkUtils.java:131-144).
That numpy run is a *stronger* baseline than the reference's Flink job
(pure BLAS, no streaming-engine overhead), so the reported ratio is a
lower bound on the speedup over the actual reference.

Also reported inside the same JSON line (details):
- loss parity: TPU final loss vs the numpy reference-semantics loss on an
  identical workload (must match to float32 tolerance);
- an MFU estimate for the training loop (flops model: 4*B*d per epoch —
  the X@coeff and X.T@mult MXU contractions);
- the KMeans README workload (10k x dim 10, k=2) vs its published
  1398.99 records/s (flink-ml-benchmark/README.md:100-110).

Every stage runs under an internal wall-clock budget (BENCH_BUDGET_S,
default 420s) and the headline JSON ALWAYS prints, stamped with the
device jax reports. A stage that misses the budget appears as
{"skipped": "budget"}, one that raised as {"failed": ...} and is listed
under failedStages — and the process then exits non-zero.

Usage: python bench.py [--logreg-rows N] [--skip-parity] [--skip-cpu]
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

BASELINE_KMEANS_THROUGHPUT = 1398.9927252378288  # records/s, README.md:104-108
DIM = 100
MAX_ITER = 20
BATCH = 100_000
LR_RATE = 0.1
TOL = 1e-6


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Published per-chip peaks, keyed by the `device_kind` string jax reports
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).
# A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbmGBps": 819.0},
}


def device_facts():
    """The device as jax reports it — stamped into the headline line."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _device_peaks():
    kind = device_facts()["kind"]
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r} in bench.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})"
        )
    return DEVICE_PEAKS[kind]


def _make_logreg(num_rows, max_iter=MAX_ITER):
    from flink_ml_tpu.models.classification.logisticregression import LogisticRegression

    return (
        LogisticRegression()
        .set_max_iter(max_iter)
        .set_learning_rate(LR_RATE)
        .set_global_batch_size(min(BATCH, num_rows))
        .set_tol(TOL)
        .set_weight_col("weight")
    )


def _gen_table(num_rows, seed):
    """The reference benchmark's input source (LabeledPointWithWeightGenerator,
    logisticregression-benchmark.json inputData) — data born in the cluster
    there, born in device HBM here."""
    from flink_ml_tpu.benchmark.datagenerator import LabeledPointWithWeightGenerator

    gen = (
        LabeledPointWithWeightGenerator()
        .set_col_names(["features", "label", "weight"])
        .set_num_values(num_rows)
        .set_vector_dim(DIM)
        .set_feature_arity(0)
        .set_seed(seed)
    )
    return gen.get_data()[0]


def bench_logreg(num_rows, in_budget=lambda: True):
    """North-star workload. Reports cold (includes XLA compile) and warm
    end-to-end job times (datagen + fit, the reference's netRuntime span).
    Because gen and fit are NOT separated by a device sync (see loop note),
    fitTimeMs absorbs the pipelined device-side datagen: it is the span
    from fit() call to model-on-host, and trainLoopMFU computed from it is
    a lower bound on the true train-loop MFU. totalTimeMs (gen dispatch +
    fit) is the honest job span and the basis of every throughput number."""
    import jax

    runs = []
    fit_times = []
    # 5 runs: run 0 is cold (compile); the min over the warm runs smooths
    # run-to-run jitter in the fit's one dispatch + one readback
    for i in range(5):
        if i > 0 and len(runs) > 1 and not in_budget():
            break
        # No sync between gen and fit: generation, batching, and training
        # pipeline as async dispatches, and fit's single packed readback is
        # the only host round trip. t_gen+t_fit still spans datagen through
        # model-on-host (the reference's netRuntime span) — fit just absorbs
        # the device-side generation time.
        t0 = time.perf_counter()
        table = _gen_table(num_rows, seed=2 + i)
        t_gen = time.perf_counter() - t0
        t0 = time.perf_counter()
        model = _make_logreg(num_rows).fit(table)
        t_fit = time.perf_counter() - t0
        runs.append(t_gen + t_fit)
        fit_times.append(t_fit)
        log(
            f"logreg run {i}: gen {t_gen * 1000:.0f} ms + fit {t_fit * 1000:.0f} ms"
            + (" (cold: includes compile)" if i == 0 else "")
        )
    warm = min(runs[1:])
    warm_fit = min(fit_times[1:])
    # FLOPs model: per epoch, X@coeff and X.T@multiplier over one batch =
    # 2*(2*B*d), against the chip's published peak.
    flops = MAX_ITER * 4.0 * min(BATCH, num_rows) * DIM
    mfu = flops / warm_fit / _device_peaks()["flops"]
    n_chips = jax.device_count()
    return {
        "coldTimeMs": runs[0] * 1000.0,
        "totalTimeMs": warm * 1000.0,
        "fitTimeMs": warm_fit * 1000.0,
        "inputRecordNum": num_rows,
        "inputThroughput": num_rows / warm,
        "throughputPerChip": num_rows / warm / n_chips,
        "numChips": n_chips,
        # flop-model fallback; overwritten with the profiler-trace MFU by
        # the trace stage when it runs (trainLoopMFUSource says which)
        "trainLoopMFU": mfu,
        "trainLoopMFUSource": "flop_model_fallback",
    }


def bench_logreg_trace(num_rows):
    """Profiler-trace evidence for the headline fit (round-3/4 ask): ONE
    warm fit under jax.profiler, reduced to device-busy time, measured HBM
    traffic, and executed FLOPs — the MFU from the device timeline rather
    than a flop model, and an explicit name for what the wall actually is
    (device compute vs the host's dispatch+readback latency)."""
    from flink_ml_tpu.utils.traceprof import capture_trace

    peaks = _device_peaks()
    peak, peak_hbm = peaks["flops"], peaks["hbmGBps"]
    table = _gen_table(num_rows, seed=2)
    np.asarray(table.column("label")[:1])  # barrier: keep datagen off the trace
    stats = capture_trace(lambda: _make_logreg(num_rows).fit(table))
    busy_s = stats["deviceBusyMs"] / 1000.0
    stats["peakFlops"] = peak
    stats["trainLoopMFU_trace"] = (
        stats["modelFlops"] / busy_s / peak if busy_s > 0 else None
    )
    # this workload is bandwidth-bound (arithmetic intensity ~0.5 flop/byte),
    # so HBM utilization, not MFU, is the roofline that matters
    stats["peakHbmGBps"] = peak_hbm
    stats["hbmUtilization"] = (
        stats["hbmGBps"] / peak_hbm if stats["hbmGBps"] is not None else None
    )
    stats["hostDispatchMs"] = stats["wallMs"] - stats["deviceBusyMs"]
    stats["wallIs"] = (
        "host-dispatch+readback-latency"
        if stats["deviceBusyMs"] < 0.5 * stats["wallMs"]
        else "device-compute"
    )
    if stats["hbmGBps"] is not None:
        log(
            f"trace: wall {stats['wallMs']:.0f} ms, device busy {stats['deviceBusyMs']:.1f} ms, "
            f"HBM {stats['hbmGBps']:.0f} GB/s ({stats['hbmUtilization']:.0%} of roofline), "
            f"MFU(trace) {stats['trainLoopMFU_trace']:.4f}, wall is {stats['wallIs']}"
        )
    else:
        log(f"trace: wall {stats['wallMs']:.0f} ms, no device activity recorded")
    return stats


def bench_logreg_amortized(num_rows, max_iter=200, in_budget=lambda: True):
    """Same headline workload at maxIter 200: amortizes the fixed
    dispatch+readback floor over 10x the training work, showing the
    train loop's own throughput. trainedExamplesPerSec counts SGD work
    actually done (batch records x epochs per second); epochMsAmortized is
    the per-epoch cost once the fixed floor is spread thin."""
    from flink_ml_tpu.obs import timeline
    from flink_ml_tpu.utils import metrics

    runs = []
    last_attr = None
    last_dispatch_ms = 0.0
    for i in range(3):
        if i > 0 and len(runs) > 1 and not in_budget():
            break
        # flight-record the warm runs: the per-fit dispatch-wall
        # attribution (wall = dispatch + device + readback + idle-gap)
        # is the item-2 evidence next to the throughput number
        if i > 0:
            timeline.configure(ring_size=16384)
        mark_us = timeline.now_us()
        before = metrics.snapshot()
        t0 = time.perf_counter()
        table = _gen_table(num_rows, seed=2 + i)
        _make_logreg(num_rows, max_iter=max_iter).fit(table)
        runs.append(time.perf_counter() - t0)
        if i > 0:
            events, _ = timeline.snapshot_events()
            attr = timeline.dispatch_attribution(
                [e for e in events if e["tsUs"] >= mark_us]
            )
            if attr:
                attr.pop("chunks", None)
                last_attr = attr
            delta = metrics.snapshot_delta(before, metrics.snapshot())
            last_dispatch_ms = delta["timers"].get("iteration.dispatch", {}).get(
                "totalMs", 0.0
            )
            timeline.configure()
        log(
            f"logreg maxIter={max_iter} run {i}: {runs[-1] * 1000:.0f} ms"
            + (" (cold: includes compile)" if i == 0 else "")
        )
    warm = min(runs[1:] or runs)
    return {
        "maxIter": max_iter,
        "coldTimeMs": runs[0] * 1000.0,
        "totalTimeMs": warm * 1000.0,
        "inputRecordNum": num_rows,
        "inputThroughput": num_rows / warm,
        "trainedExamplesPerSec": min(BATCH, num_rows) * max_iter / warm,
        "epochMsAmortized": warm * 1000.0 / max_iter,
        # host-side dispatch time of the LAST warm fit and its residual
        # gap (device + readback + idle): the measurable form of the
        # "wall is host-dispatch+readback" verdict, per run
        "hostDispatchMs": last_dispatch_ms,
        "dispatchGapMs": max(0.0, runs[-1] * 1000.0 - last_dispatch_ms),
        "dispatchAttribution": last_attr,
    }


def _numpy_reference_sgd(X, y, w, max_iter, batch, lr, tol):
    """The reference's exact SGD semantics (SGD.java:82-292 +
    TerminateOnMaxIterOrTol.java) in plain numpy: batch k = rows
    [k*B,(k+1)*B) cycling; first epoch computes the gradient on the init
    model before any update; one extra update after termination."""
    n, d = X.shape
    coeff = np.zeros(d, X.dtype)
    grad = np.zeros(d, X.dtype)
    wsum = 0.0
    loss = np.inf
    epoch = 0
    while epoch < max_iter and loss > tol:
        if wsum > 0:
            coeff = coeff - (lr / wsum) * grad
        k = epoch % max(1, -(-n // batch))
        sl = slice(k * batch, min((k + 1) * batch, n))
        Xk, yk, wk = X[sl], y[sl], w[sl]
        margin = (Xk @ coeff) * (2.0 * yk - 1.0)
        loss_sum = float(np.sum(wk * np.logaddexp(0.0, -margin)))
        mult = wk * (-(2.0 * yk - 1.0) / (np.exp(margin) + 1.0))
        grad = Xk.T @ mult
        wsum = float(np.sum(wk))
        loss = loss_sum / max(wsum, 1e-30)
        epoch += 1
    if wsum > 0:
        coeff = coeff - (lr / wsum) * grad
    return coeff, loss


def bench_loss_parity(num_rows=200_000):
    """Same small workload through the TPU engine and the numpy
    reference-semantics loop; losses must agree to f32 tolerance."""
    from flink_ml_tpu.models._linear import run_sgd  # noqa: F401  (engine import check)
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD

    rng = np.random.default_rng(7)
    X = rng.random((num_rows, DIM), dtype=np.float32)
    truth = rng.random(DIM, dtype=np.float32) - 0.5
    y = (X @ truth > 0).astype(np.float32)
    w = rng.random(num_rows, dtype=np.float32)

    sgd = SGD(
        max_iter=MAX_ITER,
        learning_rate=LR_RATE,
        global_batch_size=min(BATCH, num_rows),
        tol=TOL,
    )
    _, tpu_loss, _ = sgd.optimize(
        np.zeros(DIM, np.float32), X, y, w, BINARY_LOGISTIC_LOSS
    )
    _, ref_loss = _numpy_reference_sgd(
        X.astype(np.float64),
        y.astype(np.float64),
        w.astype(np.float64),
        MAX_ITER,
        min(BATCH, num_rows),
        LR_RATE,
        TOL,
    )
    rel = abs(tpu_loss - ref_loss) / max(abs(ref_loss), 1e-30)
    log(f"loss parity: tpu {tpu_loss:.6f} vs reference-semantics {ref_loss:.6f} (rel {rel:.2e})")
    return {"tpuLoss": tpu_loss, "referenceLoss": ref_loss, "relDiff": rel, "parity": rel < 1e-3}


def bench_cpu_baseline(num_rows):
    """CPU baseline for vs_baseline: the same job (datagen + reference-
    semantics SGD) in numpy on host — a stronger baseline than the
    reference's Flink job, making the reported speedup a lower bound."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(2)
    X = rng.random((num_rows, DIM), dtype=np.float32)  # f32 direct: no 8GB f64 spike
    y = rng.integers(0, 2, size=num_rows).astype(np.float32)
    w = rng.random(num_rows, dtype=np.float32)
    _numpy_reference_sgd(X, y, w, MAX_ITER, min(BATCH, num_rows), LR_RATE, TOL)
    elapsed = time.perf_counter() - t0
    log(f"cpu baseline (numpy, same job): {elapsed * 1000:.0f} ms -> {num_rows / elapsed:.0f} records/s")
    return {"totalTimeMs": elapsed * 1000.0, "inputThroughput": num_rows / elapsed}


def bench_wide_sparse_lr(num_rows=1_000_000, dim=1_000_000, nnz=39):
    """The Criteo-style wide-model workload (SURVEY §2.3's TP motivation):
    LR at dim 1e6 over padded-CSR sparse rows (nnz=39 mirrors Criteo's 39
    features). Densified float32 this would be num_rows*dim*4 = 4TB — the
    sparse path holds (n, nnz) index/value arrays (~312MB) plus the (d,)
    model. Data is device-born like the headline workload; the dp x tp
    feature-sharded layout of the same engine is exercised by
    tests/test_sparse_training.py::TestShardedSparse and
    __graft_entry__.dryrun_multichip (one chip here, so no tp split to
    time)."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.ops.losses import SPARSE_BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD

    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    indices = jax.random.randint(k1, (num_rows, nnz), 0, dim, dtype=jnp.int32)
    values = jax.random.uniform(k2, (num_rows, nnz), dtype=jnp.float32)
    y = (jax.random.uniform(k3, (num_rows,)) > 0.5).astype(jnp.float32)
    sgd = SGD(
        max_iter=MAX_ITER,
        learning_rate=LR_RATE,
        global_batch_size=min(BATCH, num_rows),
        tol=TOL,
    )
    runs = []
    losses = []
    for i in range(3):  # run 0 = cold (compile)
        t0 = time.perf_counter()
        coeff, loss, epochs = sgd.optimize(
            np.zeros(dim, np.float32), (indices, values), y, None,
            SPARSE_BINARY_LOGISTIC_LOSS,
        )
        runs.append(time.perf_counter() - t0)
        losses.append(loss)
        log(
            f"wide sparse LR run {i}: fit {runs[-1] * 1000:.0f} ms, loss {loss:.6f}"
            + (" (cold: includes compile)" if i == 0 else "")
        )
    warm = min(runs[1:])
    return {
        "coldTimeMs": runs[0] * 1000.0,
        "totalTimeMs": warm * 1000.0,
        "inputRecordNum": num_rows,
        "dim": dim,
        "nnzPerRow": nnz,
        "inputThroughput": num_rows / warm,
        "finalLoss": float(losses[-1]),
        "densifiedBytesAvoided": float(num_rows) * dim * 4,
    }


def bench_sparse_2d_mesh(n=4096, dim=100_000, nnz=8, max_iter=8, batch_rows=1024):
    """The feature-sharded (data x feature) 2D-mesh workload (ISSUE 17,
    PAPER §2.3's beyond-HBM motivation): sparse LR with the coefficient
    AND the SGD grad carry living as model-axis slices while batches
    shard over data. Reports per-axis collective wire bytes (the SparCML
    pair exchange on `data`, active-feature assembly psums on `model`),
    per-shard carry residency vs the replicated layout (satellite:
    hbm.live.* reads ONE shard, never the sum across virtual hosts), the
    whole-fit ONE-dispatch contract on the 2D program, GSPMD-vs-2D
    coefficient agreement on the same mesh, and the admission
    acceptance: under a budget below one replicated f32 copy the 2D
    layout trains while replicated staging is refused with the typed
    HbmBudgetExceeded (docs/performance.md "2D mesh")."""
    import jax

    from flink_ml_tpu import config
    from flink_ml_tpu.obs import memledger
    from flink_ml_tpu.ops.losses import SPARSE_BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.parallel import collectives, overlap
    from flink_ml_tpu.parallel import mesh as mesh_lib
    from flink_ml_tpu.parallel import prefetch as h2d
    from flink_ml_tpu.utils import metrics

    n_dev = len(jax.devices())
    model_shards = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    rng = np.random.default_rng(17)
    indices = rng.integers(0, dim, size=(n, nnz)).astype(np.int32)
    values = rng.random((n, nnz))
    y = rng.integers(0, 2, size=n).astype(np.float64)
    init = np.zeros(dim)
    args = ((indices, values), y, None, SPARSE_BINARY_LOGISTIC_LOSS)

    def fit(mesh, sgd):
        with mesh_lib.use_mesh(mesh):
            return sgd.optimize(init, *args, mesh=mesh)

    def per_shard_bytes(mesh):
        # what the ledger sees for ONE staged carry under each layout —
        # per-device residency, not the sum across shards
        memledger.reset()
        staged = h2d.stage_to_device(
            np.zeros(dim, np.float32), mesh_lib.model_sharding(mesh),
            category="optimizer",
        )
        live = memledger.live_bytes("optimizer")
        del staged
        memledger.reset()
        return live

    mesh2d = mesh_lib.create_mesh_2d(model_shards)
    mesh1d = mesh_lib.create_mesh((mesh_lib.DATA_AXIS,))
    sgd = SGD(
        max_iter=max_iter, learning_rate=LR_RATE,
        global_batch_size=min(batch_rows, n), tol=0.0, shard_features=True,
    )

    # cold run: compile + trace-time per-axis wire accounting
    overlap.clear_program_cache()
    before = metrics.snapshot()
    t0 = time.perf_counter()
    fit(mesh2d, sgd)
    cold = time.perf_counter() - t0
    wire = collectives.axis_wire_bytes(
        metrics.snapshot_delta(before, metrics.snapshot())
    )

    # warm run: wall, dispatch count, peak residency
    memledger.reset()
    mark = memledger.mark_peak()
    before = metrics.snapshot()
    t0 = time.perf_counter()
    coeff, loss, epochs = fit(mesh2d, sgd)
    warm = time.perf_counter() - t0
    delta = metrics.snapshot_delta(before, metrics.snapshot())
    peak_2d = memledger.peak_since(mark)
    dispatches = int(delta["timers"].get("iteration.dispatch", {}).get("count", 0))
    assert dispatches == 1, f"2D whole fit paid {dispatches} dispatches"

    # replicated reference on the same devices (1D mesh: model_sharding
    # falls back to replication) — peak watermark + GSPMD agreement
    memledger.reset()
    mark = memledger.mark_peak()
    rep_coeff, _, rep_epochs = fit(mesh1d, sgd)
    peak_rep = memledger.peak_since(mark)
    memledger.reset()
    assert rep_epochs == epochs
    assert np.allclose(coeff, rep_coeff, rtol=3e-5, atol=3e-6), (
        "2D coefficients diverged from the replicated reference"
    )

    # admission acceptance: budget below ONE replicated f32 copy
    refused = 0.0
    if model_shards > 1:
        with config.hbm_budget_mode(3 * dim):
            fit(mesh2d, sgd)  # per-shard carries fit
            try:
                fit(mesh1d, sgd)
            except memledger.HbmBudgetExceeded:
                refused = 1.0
        memledger.reset()
        assert refused == 1.0, "replicated staging was not refused at budget"

    log(
        f"sparse2dMesh: ({n_dev // model_shards}x{model_shards}) mesh, dim {dim}: "
        f"fit {warm * 1000:.0f} ms ({dispatches} dispatch), wire "
        f"data {wire.get('data', 0)}B / model {wire.get('model', 0)}B, peak "
        f"{peak_2d}B vs replicated {peak_rep}B"
    )
    return {
        "inputRecordNum": n,
        "dim": dim,
        "nnzPerRow": nnz,
        "maxIter": max_iter,
        "dataShards": n_dev // model_shards,
        "modelShards": model_shards,
        "coldTimeMs": cold * 1000.0,
        "wallMs": warm * 1000.0,
        "trainedExamplesPerSec": min(batch_rows, n) * max_iter / warm,
        "finalLoss": float(loss),
        # gated lower-better leaves (scripts/bench_diff.py direction rules)
        "dispatchCount": dispatches,
        "dataAxisWireBytes": int(wire.get("data", 0)),
        "modelAxisWireBytes": int(wire.get("model", 0)),
        "peakHbmBytes": int(peak_2d),
        "optimizerPerShardBytes": int(per_shard_bytes(mesh2d)),
        # informational reference side (no direction: *Replicated)
        "peakHbmBytesReplicated": int(peak_rep),
        "optimizerBytesReplicated": int(per_shard_bytes(mesh1d)),
        "agreesWithGspmdReference": 1.0,  # asserted above
        "replicatedRefusedAtBudget": refused,
    }


def bench_kmeans():
    """The reference README's only published number (10k x dim 10, k=2)."""
    from flink_ml_tpu.models.clustering.kmeans import KMeans
    from flink_ml_tpu.table import Table

    rng = np.random.RandomState(2)
    X = rng.rand(10_000, 10)
    table = Table({"features": X})
    times = []
    for _ in range(3):  # min over warm runs smooths run-to-run jitter
        start = time.perf_counter()
        model = KMeans().set_k(2).set_seed(2).fit(table)
        for t in model.get_model_data():
            t.collect()
        times.append(time.perf_counter() - start)
    warm = min(times[1:] or times)
    log(
        f"kmeans: warm {warm * 1000:.0f} ms, {10_000 / warm:.0f} records/s "
        f"(reference: 7148 ms, {BASELINE_KMEANS_THROUGHPUT:.0f} records/s)"
    )
    return {
        "coldTimeMs": times[0] * 1000.0,
        "totalTimeMs": warm * 1000.0,
        "inputThroughput": 10_000 / warm,
        "vsPublishedBaseline": 10_000 / warm / BASELINE_KMEANS_THROUGHPUT,
    }


def bench_pipeline_serving(num_batches=48, batch_rows=4096):
    """Serving-path workload (ISSUE 3): a 5-stage all-device feature
    pipeline driven over a micro-batch stream, fused+double-buffered
    (serving.MicroBatchServer) vs the eager per-stage transform loop.
    The contrast under measurement: eager pays one device program PLUS
    one blocking probe sync per guard stage per batch; fused pays one
    program and ONE packed drain per batch, with batch i+1's upload and
    compute overlapping batch i's drain. Outputs stay device-resident in
    both paths (a serving tier hands them to the next system; pulling
    them to host would time the caller's readback, not the pipeline)."""
    import jax

    from flink_ml_tpu import config
    from flink_ml_tpu.models.feature.binarizer import Binarizer
    from flink_ml_tpu.models.feature.bucketizer import Bucketizer
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.models.feature.vectorassembler import VectorAssembler
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.serving import MicroBatchServer
    from flink_ml_tpu.table import StreamTable, Table
    from flink_ml_tpu.utils import metrics

    d_a, d_b = 64, 36
    rng = np.random.default_rng(3)
    scaler = StandardScalerModel()
    scaler.mean = rng.standard_normal(d_a + d_b)
    scaler.std = np.abs(rng.standard_normal(d_a + d_b)) + 0.1
    scaler.set_input_col("assembled").set_output_col("scaled")
    pipeline = PipelineModel(
        [
            VectorAssembler().set_input_cols("va", "vb").set_output_col("assembled"),
            scaler,
            Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm"),
            Bucketizer()
            .set_input_cols("raw")
            .set_output_cols("bucket")
            .set_splits_array([[-1e6, -1.0, 0.0, 1.0, 1e6]]),
            Binarizer().set_input_cols("bucket").set_output_cols("bin").set_thresholds(1.5),
        ]
    )

    def make_batches():
        return [
            Table(
                {
                    "va": rng.standard_normal((batch_rows, d_a), dtype=np.float32),
                    "vb": rng.standard_normal((batch_rows, d_b), dtype=np.float32),
                    "raw": rng.standard_normal(batch_rows, dtype=np.float32),
                }
            )
            for _ in range(num_batches)
        ]

    def block_on(outputs):
        for t in outputs:
            jax.block_until_ready(
                [t.column(n) for n in ("norm", "bin") if n in t]
            )

    def run_fused(batches):
        server = MicroBatchServer(pipeline)
        before = metrics.snapshot()
        t0 = time.perf_counter()
        outs = list(server.serve(StreamTable.from_batches(batches)))
        block_on(outs[-1:])
        elapsed = time.perf_counter() - t0
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        return elapsed, delta

    def run_eager(batches):
        before = metrics.snapshot()
        t0 = time.perf_counter()
        outs = []
        with config.pipeline_fusion_mode("off"):
            for batch in batches:
                dev = Table(
                    {n: jax.device_put(batch.column(n)) for n in batch.column_names}
                )
                outs.append(pipeline.transform(dev)[0])
        block_on(outs[-1:])
        elapsed = time.perf_counter() - t0
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        return elapsed, delta

    records = num_batches * batch_rows
    run_fused(make_batches()[:2])  # compile warmup, both bucket + plan
    run_eager(make_batches()[:2])
    # min over repeats smooths scheduler jitter (the per-batch cost is
    # milliseconds, well inside CPU-host noise); interleaved so neither
    # path systematically benefits from a warmer cache
    fused_s, fused_delta = run_fused(make_batches())
    eager_s, eager_delta = run_eager(make_batches())
    for _ in range(2):
        s, d = run_fused(make_batches())
        if s < fused_s:
            fused_s, fused_delta = s, d
        s, d = run_eager(make_batches())
        if s < eager_s:
            eager_s, eager_delta = s, d
    fused_syncs = fused_delta["counters"].get("iteration.host_sync.transform", 0)
    eager_syncs = eager_delta["counters"].get("iteration.host_sync.transform", 0)
    result = {
        "numBatches": num_batches,
        "batchRows": batch_rows,
        "numStages": len(pipeline.stages),
        "inputRecordNum": records,
        "fusedRecordsPerSec": records / fused_s,
        "eagerRecordsPerSec": records / eager_s,
        "speedup": eager_s / fused_s,
        "fusedTimeMs": fused_s * 1000.0,
        "eagerTimeMs": eager_s * 1000.0,
        # first-class dispatch evidence: fused syncs once per batch no
        # matter the stage count; eager syncs once per guard stage per batch
        "hostSyncCount": int(fused_syncs),
        "hostSyncCountEager": int(eager_syncs),
        "hostSyncsPerBatch": fused_syncs / num_batches,
        "hostSyncsPerBatchEager": eager_syncs / num_batches,
        "fusedSegments": int(fused_delta["gauges"].get("pipeline.fused_segments", 0)),
        "servingInFlight": int(fused_delta["gauges"].get("serving.in_flight", 0)),
    }
    log(
        f"pipelineServing: fused {result['fusedRecordsPerSec']:.0f} rec/s vs eager "
        f"{result['eagerRecordsPerSec']:.0f} rec/s ({result['speedup']:.2f}x), "
        f"syncs/batch {result['hostSyncsPerBatch']:.1f} vs {result['hostSyncsPerBatchEager']:.1f}, "
        f"{result['fusedSegments']} fused segment(s) of {result['numStages']} stages"
    )
    return result


def bench_input_pipeline(num_batches=8, batch_rows=20_000, d=64, epochs=6):
    """The input-layer workload (ISSUE 5): a bounded stream fit replayed
    over `epochs` passes, device-epoch-cached vs eager re-upload
    (`config.device_cache_bytes` None vs 0). The claims under measurement:
    epochs >= 1 of the cached path move ZERO host→device bytes (the
    `h2d.bytes` counter, asserted in-process), both paths produce
    bit-identical coefficients, and bucketed staging compiles fewer
    programs than exact-shape staging on a ragged KMeans stream."""
    from flink_ml_tpu import config
    from flink_ml_tpu.models.clustering.kmeans import KMeans
    from flink_ml_tpu.obs import tracing
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.table import StreamTable, Table
    from flink_ml_tpu.utils import metrics

    tracing.install_jax_hooks()
    n = num_batches * batch_rows
    rng = np.random.default_rng(9)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)
    max_iter = epochs * num_batches  # full passes over the cached stream

    def chunks():
        return iter(
            [
                (X[i : i + batch_rows], y[i : i + batch_rows], None)
                for i in range(0, n, batch_rows)
            ]
        )

    def run(budget):
        # whole_fit off: this entry measures the per-epoch replay pipeline
        # (cache vs eager re-upload); the resident path bypasses it and
        # has its own wholeFitDispatch entry
        with config.whole_fit_mode("off"), config.device_cache_budget(budget):
            sgd = SGD(max_iter=max_iter, global_batch_size=batch_rows, tol=0.0)
            before = metrics.snapshot()
            t0 = time.perf_counter()
            coeff, _, _, _ = sgd.optimize_stream(None, chunks(), BINARY_LOGISTIC_LOSS)
            wall = time.perf_counter() - t0
            delta = metrics.snapshot_delta(before, metrics.snapshot())
        return coeff, wall, delta["counters"]

    run(None)  # compile warmup for both kernels
    cached_coeff, cached_wall, cached_c = run(None)
    eager_coeff, eager_wall, eager_c = run(0)
    cached_bytes = cached_c.get("h2d.bytes", 0)
    eager_bytes = eager_c.get("h2d.bytes", 0)
    epoch0_bytes = eager_bytes / epochs  # eager re-uploads every pass alike
    later_epochs_bytes = (cached_bytes - epoch0_bytes) / max(1, epochs - 1)
    assert np.array_equal(cached_coeff, eager_coeff), (
        "cached epochs diverged from the eager re-upload path"
    )

    # bucketed vs unbucketed compile counts on a deliberately ragged
    # KMeans stream (the micro-batch-jitter recompile story). Each mode
    # is measured at its own feature dim after a uniform-batch warmup fit
    # at that dim, so the counted compiles are exactly the ones the
    # jittered batch SHAPES caused — not shared first-fit warmup.
    rng_k = np.random.default_rng(10)
    sizes = [257, 511, 383, 640, 333, 476, 600]
    offs = np.cumsum([0] + sizes)

    def compile_cost(bucketing, dim):
        Xk = rng_k.standard_normal((offs[-1], dim)).astype(np.float32)
        uniform = [
            Table({"features": Xk[i : i + 512]}) for i in range(0, 1024, 512)
        ]
        ragged = [
            Table({"features": Xk[offs[i] : offs[i + 1]]})
            for i in range(len(sizes))
        ]
        kfit = lambda b: KMeans().set_k(4).set_seed(3).set_max_iter(2).fit(  # noqa: E731
            StreamTable.from_batches(b)
        )
        with config.whole_fit_mode("off"), config.input_bucketing_mode(bucketing):
            kfit(uniform)  # warm every kernel at the uniform batch shape
            before = metrics.get_counter("jit.compiles")
            kfit(ragged)
            return metrics.get_counter("jit.compiles") - before

    compiles_bucketed = compile_cost(True, 16)
    compiles_unbucketed = compile_cost(False, 17)

    result = {
        "numBatches": num_batches,
        "batchRows": batch_rows,
        "dim": d,
        "epochs": epochs,
        "cachedWallMs": cached_wall * 1000.0,
        "eagerWallMs": eager_wall * 1000.0,
        "cachedEpochWallMs": cached_wall * 1000.0 / epochs,
        "eagerEpochWallMs": eager_wall * 1000.0 / epochs,
        "speedup": eager_wall / cached_wall,
        # the acceptance number: host→device bytes per epoch after epoch 0
        # on the cached path — 0 within budget
        "h2dBytesPerEpochCached": later_epochs_bytes,
        "h2dBytesPerEpochEager": epoch0_bytes,
        "h2dBytesCachedTotal": cached_bytes,
        "h2dBytesEagerTotal": eager_bytes,
        "deviceCacheHits": int(cached_c.get("devicecache.hit", 0)),
        "bitIdenticalToEager": True,  # asserted above
        "raggedStreamCompilesBucketed": int(compiles_bucketed),
        "raggedStreamCompilesUnbucketed": int(compiles_unbucketed),
    }
    log(
        f"inputPipeline: cached epoch {result['cachedEpochWallMs']:.1f}ms vs eager "
        f"{result['eagerEpochWallMs']:.1f}ms ({result['speedup']:.2f}x), "
        f"H2D/epoch cached {later_epochs_bytes / 1e6:.2f}MB vs eager "
        f"{epoch0_bytes / 1e6:.2f}MB; ragged-stream compiles bucketed "
        f"{compiles_bucketed} vs unbucketed {compiles_unbucketed}"
    )
    return result


def bench_whole_fit_dispatch(n=400_000, d=32, max_iter=200, batch_rows=4096):
    """The whole-fit resident-program workload (ISSUE 13 / ROADMAP item
    2a): the SAME maxIter=200 out-of-core LR fit on the per-epoch dispatch
    pipeline (`config.whole_fit` off — one dispatch + one drained readback
    PER EPOCH) vs the resident program (one dispatch + one packed readback
    PER FIT). Reports the dispatch count (`iteration.dispatch` launches),
    `hostSyncCount`, host-dispatch wall and the flight-recorder
    attribution for both sides, asserts bit-identical coefficients
    in-process, and derives the trace-MFU proxy delta: with fixed device
    work per fit, MFU scales as 1/wall, so the wall ratio IS the MFU lift
    on this workload."""
    from flink_ml_tpu import config
    from flink_ml_tpu.obs import timeline
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(23)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)

    def chunks():
        return iter(
            [
                (X[i : i + batch_rows], y[i : i + batch_rows], None)
                for i in range(0, n, batch_rows)
            ]
        )

    def run(mode):
        with config.whole_fit_mode(mode):
            sgd = SGD(max_iter=max_iter, global_batch_size=batch_rows, tol=0.0)
            sgd.optimize_stream(None, chunks(), BINARY_LOGISTIC_LOSS)  # warm
            timeline.configure(ring_size=65536)
            mark_us = timeline.now_us()
            before = metrics.snapshot()
            t0 = time.perf_counter()
            coeff, _, epochs, _ = sgd.optimize_stream(
                None, chunks(), BINARY_LOGISTIC_LOSS
            )
            wall = time.perf_counter() - t0
            delta = metrics.snapshot_delta(before, metrics.snapshot())
            events, _ = timeline.snapshot_events()
            attr = timeline.dispatch_attribution(
                [e for e in events if e["tsUs"] >= mark_us]
            )
            timeline.configure()
            if attr:
                attr.pop("chunks", None)
        return {
            "coeff": coeff,
            "epochs": epochs,
            "wallMs": wall * 1000.0,
            "hostSyncCount": int(delta["counters"].get("iteration.host_sync", 0)),
            "dispatchCount": int(
                delta["timers"].get("iteration.dispatch", {}).get("count", 0)
            ),
            "hostDispatchMs": float(
                delta["timers"].get("iteration.dispatch", {}).get("totalMs", 0.0)
            ),
            "wholeFitCount": int(delta["counters"].get("dispatch.whole_fit", 0)),
            "wholeFitFallbacks": int(
                delta["counters"].get("dispatch.whole_fit_fallback", 0)
            ),
            "attribution": attr,
        }

    chunked = run("off")
    whole = run("auto")
    assert np.array_equal(chunked["coeff"], whole["coeff"]), (
        "whole-fit diverged from the chunked reference"
    )
    assert whole["hostSyncCount"] == 1, (
        f"whole-fit paid {whole['hostSyncCount']} host syncs, expected 1"
    )
    examples = min(batch_rows, n) * max_iter
    result = {
        "maxIter": max_iter,
        "inputRecordNum": n,
        "dim": d,
        # gated side: the resident program (lower-better leaves)
        "wallMs": whole["wallMs"],
        "hostSyncCount": whole["hostSyncCount"],
        "dispatchCount": whole["dispatchCount"],
        "hostDispatchMs": whole["hostDispatchMs"],
        "trainedExamplesPerSec": examples / (whole["wallMs"] / 1000.0),
        "wholeFitFallbacks": whole["wholeFitFallbacks"],
        "dispatchAttribution": whole["attribution"],
        # reference side (informational leaves: *Chunked has no direction)
        "wallMsChunked": chunked["wallMs"],
        "hostSyncCountChunked": chunked["hostSyncCount"],
        "dispatchCountChunked": chunked["dispatchCount"],
        "hostDispatchMsChunked": chunked["hostDispatchMs"],
        "dispatchAttributionChunked": chunked["attribution"],
        # fixed device work per fit => MFU ~ 1/wall: the wall ratio is
        # the trace-MFU lift of going resident on this workload
        "mfuProxyLift": chunked["wallMs"] / whole["wallMs"],
        "dispatchReduction": (
            chunked["dispatchCount"] / max(1, whole["dispatchCount"])
        ),
        "bitIdenticalToChunked": True,  # asserted above
    }
    log(
        f"wholeFitDispatch: {chunked['dispatchCount']} dispatches/"
        f"{chunked['hostSyncCount']} syncs -> {whole['dispatchCount']}/"
        f"{whole['hostSyncCount']} at maxIter={max_iter}; wall "
        f"{chunked['wallMs']:.0f}ms -> {whole['wallMs']:.0f}ms "
        f"({result['mfuProxyLift']:.2f}x MFU proxy), hostDispatch "
        f"{whole['hostDispatchMs']:.1f}ms of {whole['wallMs']:.0f}ms wall"
    )
    return result


def bench_fleet_sweep(
    n=100_000,
    d=32,
    max_iter=12,
    batch_rows=4096,
    fleet_sizes=(1, 32, 512),
    in_budget=lambda: True,
):
    """The FitFleet many-model workload (docs/performance.md §11): the
    SAME LR fit swept over per-member learning rates, trained as ONE
    vmapped resident dispatch at each fleet size. Reports models/s and
    trained-examples/s at N in {1, 32, 512}; the N=32 point asserts the
    amortization contract in-process — ONE dispatch, ONE blocking host
    sync for the whole fleet — and every member's coefficients within
    float rounding of its solo whole-fit run (`bitIdenticalToSolo`
    reports whether they were bitwise equal: true on the CPU backend, not
    on the v5e). The gated leaves
    (dispatchCount / hostSyncCount / modelsPerSecond /
    trainedExamplesPerSec) come from that N=32 point."""
    from flink_ml_tpu.fleet import FitFleet
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )
    from flink_ml_tpu.table import Table
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(29)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)
    table = Table({"features": X, "label": y})

    def member(i, size):
        # a real sweep: every member trains a distinct hyper point
        return (
            LogisticRegression()
            .set_max_iter(max_iter)
            .set_tol(0.0)
            .set_learning_rate(0.05 * (1.0 + i / max(1, size)))
            .set_global_batch_size(batch_rows)
        )

    def run(size):
        fleet = FitFleet([member(i, size) for i in range(size)])
        fleet.fit(table)  # warm: compile the size-N program off the clock
        before = metrics.snapshot()
        t0 = time.perf_counter()
        models = FitFleet([member(i, size) for i in range(size)]).fit(table)
        wall = time.perf_counter() - t0
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        examples = int(delta["counters"].get("fleet.examplesTrained", 0))
        return models, {
            "fleetSize": size,
            "wallMs": wall * 1000.0,
            "modelsPerSecond": size / wall,
            "trainedExamplesPerSec": examples / wall,
            "dispatchCount": int(
                delta["timers"].get("iteration.dispatch", {}).get("count", 0)
            ),
            "hostSyncCount": int(delta["counters"].get("iteration.host_sync", 0)),
            "wholeFitFleetCount": int(
                delta["counters"].get("dispatch.whole_fit.fleet", 0)
            ),
        }

    # the gate point: N=32 when swept, else the largest size that runs —
    # so smoke-scale sweeps still pin the bit-identity contract in-process
    gate_size = (
        32
        if 32 in fleet_sizes
        else max((s for s in fleet_sizes if s <= 32), default=min(fleet_sizes))
    )
    by_size = {}
    gate_models = None
    for size in fleet_sizes:
        if size > 32 and not in_budget():
            log(f"fleetSweep: skipping N={size} (budget)")
            continue
        models, point = run(size)
        by_size[str(size)] = point
        if size == gate_size:
            gate_models = models
        log(
            f"fleetSweep N={size}: {point['modelsPerSecond']:.1f} models/s, "
            f"{point['trainedExamplesPerSec']:.3g} examples/s, "
            f"{point['dispatchCount']} dispatch / {point['hostSyncCount']} sync "
            f"in {point['wallMs']:.0f}ms"
        )

    gate = by_size[str(gate_size)]
    assert gate["dispatchCount"] == 1, (
        f"fleet fit paid {gate['dispatchCount']} dispatches, expected 1"
    )
    assert gate["hostSyncCount"] == 1, (
        f"fleet fit paid {gate['hostSyncCount']} host syncs, expected 1"
    )
    bit_identical, max_rel = None, None
    if gate_models is not None:
        # every member vs its solo whole-fit run: bit-identical on the CPU
        # backend (CI asserts the reported flag there); on the TPU the
        # vmapped program's contractions round differently, so the hard
        # bound is float rounding and the flag reports what was seen
        bit_identical, max_rel = True, 0.0
        for i, model in enumerate(gate_models):
            got = np.asarray(model.coefficient, np.float64)
            solo = np.asarray(member(i, gate_size).fit(table).coefficient, np.float64)
            bit_identical = bit_identical and np.array_equal(got, solo)
            rel = float(np.abs(got - solo).max() / max(np.abs(solo).max(), 1e-30))
            max_rel = max(max_rel, rel)
            assert rel <= 1e-5, (
                f"fleet member {i} diverged from its solo fit (rel {rel:.2e})"
            )

    result = {
        "inputRecordNum": n,
        "dim": d,
        "maxIter": max_iter,
        # gated leaves: the N=32 amortization point (lower-better counts,
        # higher-better throughputs — bench_diff direction rules)
        "dispatchCount": gate["dispatchCount"],
        "hostSyncCount": gate["hostSyncCount"],
        "wallMs": gate["wallMs"],
        "modelsPerSecond": gate["modelsPerSecond"],
        "trainedExamplesPerSec": gate["trainedExamplesPerSec"],
        "bitIdenticalToSolo": bit_identical,  # measured; within 1e-5 asserted above
        "maxRelDiffVsSolo": max_rel,
        "byFleetSize": by_size,
    }
    if "1" in by_size and "32" in by_size:
        # the headline amortization ratio: models/s lift of batching 32
        # fits into one program vs training them one at a time
        result["modelsPerSecondLift32"] = (
            by_size["32"]["modelsPerSecond"] / by_size["1"]["modelsPerSecond"]
        )
    return result


def bench_checkpoint_resume(n=200_000, d=64, max_iter=24, kill_after_chunks=8):
    """The preemption-safety workload (ISSUE 6): dense SGD with JobSnapshot
    checkpointing every epoch. Reports (a) snapshot cost — wall delta per
    epoch vs the same fit without checkpointing, plus the checkpoint.bytes/
    count the run actually wrote; (b) resume-to-first-step wall — restore
    the snapshot and advance ONE epoch (the recovery-latency number: how
    long after a preemption the job is training again); (c) bit-identity —
    a fit killed mid-training by the fault harness and resumed must land on
    the uninterrupted run's exact coefficients (asserted in-process)."""
    import shutil
    import tempfile

    from flink_ml_tpu.ckpt import InjectedFault, faults
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(17)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)
    B = 20_000

    def fit(ckpt_dir=None, max_iter=max_iter):
        sgd = SGD(
            max_iter=max_iter, global_batch_size=B, tol=0.0,
            checkpoint_dir=ckpt_dir, checkpoint_interval=1,
            checkpoint_key="checkpointResume",  # namespaced: no un-keyed warning
        )
        t0 = time.perf_counter()
        coeff, _, epochs = sgd.optimize(
            np.zeros(d, np.float32), X, y, None, BINARY_LOGISTIC_LOSS
        )
        return coeff, epochs, time.perf_counter() - t0

    work = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        fit()  # compile warmup (both the plain and chunked programs)
        fit(os.path.join(work, "warm"))
        _, _, plain_wall = fit()
        before = metrics.snapshot()
        # the uninterrupted reference for the bit-identity assert runs the
        # SAME checkpointed (chunked) program as the killed fit — the flat
        # single-shard path is a different batch layout (allclose, not
        # bit-equal, to the batched one)
        expected, _, ckpt_wall = fit(os.path.join(work, "cadence"))
        delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        save_count = int(delta.get("checkpoint.count", 0))
        save_bytes = int(delta.get("checkpoint.bytes", 0))

        # kill mid-training at a chunk boundary, then resume to completion
        kill_dir = os.path.join(work, "kill")
        killed_at = None
        try:
            with faults.inject("chunk", after=kill_after_chunks):
                fit(kill_dir)
        except InjectedFault as e:
            killed_at = e.hits
        assert killed_at is not None, "fault never fired — raise max_iter"
        resumed, epochs, resume_wall = fit(kill_dir)
        bit_identical = bool(np.array_equal(np.asarray(resumed), np.asarray(expected)))
        assert bit_identical, "kill -> resume diverged from the uninterrupted fit"

        # recovery latency: restore the snapshot and advance ONE epoch
        first_dir = os.path.join(work, "first")
        try:
            with faults.inject("chunk", after=kill_after_chunks):
                fit(first_dir)
        except InjectedFault:
            pass
        t0 = time.perf_counter()
        _, first_epochs, _ = fit(first_dir, max_iter=kill_after_chunks + 1)
        resume_to_first_step = time.perf_counter() - t0
    finally:
        shutil.rmtree(work, ignore_errors=True)

    result = {
        "numRows": n,
        "dim": d,
        "maxIter": max_iter,
        "plainWallMs": plain_wall * 1000.0,
        "checkpointedWallMs": ckpt_wall * 1000.0,
        "saveMsPerEpoch": (ckpt_wall - plain_wall) * 1000.0 / max_iter,
        "checkpointCount": save_count,
        "checkpointBytes": save_bytes,
        "checkpointBytesPerSave": save_bytes / max(1, save_count),
        "killedAtChunk": killed_at,
        "resumeWallMs": resume_wall * 1000.0,
        "resumeToFirstStepMs": resume_to_first_step * 1000.0,
        "resumedEpochs": int(epochs),
        "bitIdenticalToUninterrupted": bit_identical,  # asserted above
    }
    log(
        f"checkpointResume: save {result['saveMsPerEpoch']:.2f}ms/epoch "
        f"({result['checkpointBytesPerSave'] / 1e3:.1f}KB/save, "
        f"{save_count} saves), kill@chunk {killed_at} -> resume-to-first-step "
        f"{result['resumeToFirstStepMs']:.1f}ms, bit-identical resume"
    )
    return result


def bench_multihost_checkpoint(
    n=200_000, d=64, max_iter=12, host_counts=(1, 4, 8), kill_after=6
):
    """Multi-host snapshot workload (ISSUE 14): dense SGD checkpointing
    every epoch through the sharded two-phase-commit coordinator
    (ckpt/coordinator.py) at several simulated host counts. Reports per
    host count: (a) save wall per epoch (wall delta vs the same fit
    without checkpointing) and shard bytes per host — the scaling curve
    of the per-host write path; (b) kill@manifest-commit -> resume wall
    (the recovery number for a cut torn exactly at the two-phase-commit
    window); (c) bit-identity — the killed+resumed sharded fit must land
    on the single-file path's exact coefficients (asserted in-process:
    the snapshot transport changes WHERE bytes live, never the model)."""
    import shutil
    import tempfile

    from flink_ml_tpu import config as _config
    from flink_ml_tpu.ckpt import InjectedFault, faults
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(23)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)

    def fit(ckpt_dir=None, max_iter=max_iter):
        sgd = SGD(
            max_iter=max_iter, global_batch_size=20_000, tol=0.0,
            checkpoint_dir=ckpt_dir, checkpoint_interval=1,
            checkpoint_key="multiHostCheckpoint",
        )
        t0 = time.perf_counter()
        coeff, _, epochs = sgd.optimize(
            np.zeros(d, np.float32), X, y, None, BINARY_LOGISTIC_LOSS
        )
        return coeff, epochs, time.perf_counter() - t0

    work = tempfile.mkdtemp(prefix="bench_mh_ckpt_")
    per_hosts = {}
    try:
        fit()  # compile warmup
        fit(os.path.join(work, "warm"))
        _, _, plain_wall = fit()
        expected, _, _ = fit(os.path.join(work, "single"))  # single-file ref

        for hosts in host_counts:
            with _config.snapshot_hosts_mode(hosts):
                before = metrics.snapshot()
                _, _, wall = fit(os.path.join(work, f"h{hosts}"))
                delta = metrics.snapshot_delta(before, metrics.snapshot())[
                    "counters"
                ]
            shard_bytes = int(delta.get("checkpoint.shard.bytes", 0))
            shard_count = int(delta.get("checkpoint.shard.count", 0))
            saves = int(delta.get("checkpoint.manifest.count", 0))
            per_hosts[f"host{hosts}"] = {
                "wallMs": wall * 1000.0,
                "savePerEpochMs": (wall - plain_wall) * 1000.0 / max_iter,
                "shardBytesPerHost": shard_bytes / max(1, saves * hosts),
                "shardFilesPerSave": shard_count / max(1, saves),
                "manifestCommits": saves,
            }

        # kill exactly inside the two-phase-commit window (shards landed,
        # manifest rename never ran), then resume elastically onto a
        # DIFFERENT simulated host count
        kill_dir = os.path.join(work, "kill")
        killed_at = None
        with _config.snapshot_hosts_mode(host_counts[-1]):
            try:
                with faults.inject("snapshot.commit", after=kill_after):
                    fit(kill_dir)
            except InjectedFault as e:
                killed_at = e.hits
        assert killed_at is not None, "commit fault never fired"
        with _config.snapshot_hosts_mode(host_counts[0]):
            t0 = time.perf_counter()
            resumed, epochs, _ = fit(kill_dir)
            resume_wall = time.perf_counter() - t0
        bit_identical = bool(
            np.array_equal(np.asarray(resumed), np.asarray(expected))
        )
        assert bit_identical, (
            "sharded kill@commit -> resume diverged from the single-file fit"
        )
    finally:
        shutil.rmtree(work, ignore_errors=True)

    result = {
        "numRows": n,
        "dim": d,
        "maxIter": max_iter,
        "plainWallMs": plain_wall * 1000.0,
        **per_hosts,
        "killedAtCommit": killed_at,
        "resumeWallMs": resume_wall * 1000.0,
        "resumedEpochs": int(epochs),
        "bitIdenticalToSingleFile": bit_identical,  # asserted above
    }
    biggest = per_hosts[f"host{host_counts[-1]}"]
    log(
        f"multiHostCheckpoint: {host_counts[-1]} hosts save "
        f"{biggest['savePerEpochMs']:.2f}ms/epoch "
        f"({biggest['shardBytesPerHost'] / 1e3:.1f}KB/host/save), "
        f"kill@commit {killed_at} -> resume {resume_wall * 1000.0:.1f}ms, "
        "bit-identical to the single-file path"
    )
    return result


def bench_elastic_recovery(n=100_000, d=32, max_iter=12, hosts=4):
    """Elastic-supervisor workload (ISSUE 15): a checkpointed dense SGD
    fit under `parallel/supervisor.supervise` with sharded snapshots,
    chaos-injected twice: (a) a collective HANG mid-drain — detected by
    the dispatch-progress deadline, host readmitted, SAME-host-count
    resume asserted BIT-IDENTICAL to the unkilled fit; (b) a host DEATH
    mid-epoch — detected by heartbeat timeout, host quarantined, mesh
    re-formed over survivors, cross-count resume asserted allclose per
    the reduction-order caveat. Reports per scenario: detection latency
    (fault observable -> monitor detected) and recovery wall (detected ->
    resumed fit's first progress); top-level detectionMs/recoveryWallMs
    are the worst of the two (the conservative SLO numbers the CI
    bench_diff rules gate)."""
    import shutil
    import tempfile

    from flink_ml_tpu import config as _config
    from flink_ml_tpu.ckpt import faults
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.parallel import supervisor

    rng = np.random.default_rng(31)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d).astype(np.float32) > 0).astype(np.float32)

    def make_fit(ckpt_dir):
        def fit(mesh):
            return SGD(
                max_iter=max_iter, global_batch_size=20_000, tol=0.0,
                checkpoint_dir=ckpt_dir, checkpoint_interval=1,
                checkpoint_key="elasticRecovery",
            ).optimize(
                np.zeros(d, np.float32), X, y, None,
                BINARY_LOGISTIC_LOSS, mesh=mesh,
            )

        return fit

    work = tempfile.mkdtemp(prefix="bench_elastic_")
    scenarios = {}
    try:
        from flink_ml_tpu.parallel import mesh as mesh_lib

        expected, _, _ = make_fit(os.path.join(work, "ref"))(
            mesh_lib.default_mesh()
        )
        expected = np.asarray(expected)

        with _config.snapshot_hosts_mode(hosts):
            # (a) collective hang mid-drain: readmit, bit-identical resume
            hang_dir = os.path.join(work, "hang")
            with faults.inject("host.hang.collective", after=3):
                t0 = time.perf_counter()
                res = supervisor.supervise(
                    make_fit(hang_dir), hosts=hosts,
                    checkpoint_dir=hang_dir, job_key="elasticRecovery",
                    heartbeat_timeout_s=30.0, poll_interval_s=0.005,
                )
                hang_wall = time.perf_counter() - t0
            assert res.recoveries == 1 and res.hosts == hosts
            (ev,) = res.events
            assert ev.kind == "collectiveHang"
            coeff, _, epochs = res.value
            assert epochs == max_iter
            assert np.array_equal(np.asarray(coeff), expected), (
                "same-host-count elastic resume diverged from the unkilled fit"
            )
            scenarios["hang"] = {
                "detectionMs": ev.detection_ms,
                "recoveryWallMs": ev.recovery_ms,
                "supervisedWallMs": hang_wall * 1000.0,
                "hostsAfter": res.hosts,
                "bitIdentical": True,  # asserted above
            }

            # (b) host death mid-epoch: quarantine + shrink, allclose resume
            die_dir = os.path.join(work, "die")
            with faults.inject("host.die.dispatch", after=3):
                t0 = time.perf_counter()
                res = supervisor.supervise(
                    make_fit(die_dir), hosts=hosts,
                    checkpoint_dir=die_dir, job_key="elasticRecovery",
                    heartbeat_timeout_s=0.25, poll_interval_s=0.005,
                )
                die_wall = time.perf_counter() - t0
            assert res.recoveries == 1 and res.hosts == hosts - 1
            (ev,) = res.events
            assert ev.kind == "hostFailure" and ev.quarantined
            coeff, _, epochs = res.value
            assert epochs == max_iter
            assert np.allclose(np.asarray(coeff), expected, rtol=5e-4, atol=1e-6), (
                "shrink resume diverged beyond the reduction-order envelope"
            )
            scenarios["hostDeath"] = {
                "detectionMs": ev.detection_ms,
                "recoveryWallMs": ev.recovery_ms,
                "supervisedWallMs": die_wall * 1000.0,
                "hostsAfter": res.hosts,
                "allclose": True,  # asserted above
            }
    finally:
        shutil.rmtree(work, ignore_errors=True)

    result = {
        "numRows": n,
        "dim": d,
        "maxIter": max_iter,
        "hosts": hosts,
        **scenarios,
        "detectionMs": max(s["detectionMs"] for s in scenarios.values()),
        "recoveryWallMs": max(
            s["recoveryWallMs"] or 0.0 for s in scenarios.values()
        ),
        "parityAsserted": True,
    }
    log(
        f"elasticRecovery: hang detected {scenarios['hang']['detectionMs']:.0f}ms"
        f" / recovered {scenarios['hang']['recoveryWallMs']:.0f}ms"
        " (bit-identical resume), host death detected "
        f"{scenarios['hostDeath']['detectionMs']:.0f}ms / recovered "
        f"{scenarios['hostDeath']['recoveryWallMs']:.0f}ms "
        f"({hosts}->{hosts - 1} hosts, allclose)"
    )
    return result


def bench_overload_soak(num_requests=60, batch_rows=256, d=24):
    """Robustness workload (ISSUE 8): bursty producer x slow/flaky
    consumer, asserted in-process:

    1. **Overloaded serving sheds at the door with bounded memory** — an
       unpaced producer fires `num_requests` submits at a MicroBatchServer
       with a small admission queue + in-flight window. The reject policy
       must fast-fail (ServerOverloaded) instead of queueing, both queue
       depths must peak within their configured capacities (the bounded-
       peak-memory claim, reported in bytes), every admitted request must
       retire, and the dispatch worker must exit — zero deadlock,
       enforced by a bounded join.
    2. **shed_oldest bounds model staleness** — a producer bursts 40x the
       channel capacity between consumer gets; consumed lag must stay
       BELOW the capacity while sheds are counted (the staleness contract
       of docs/flow_control.md).
    3. **Transient-fault retries are result-invisible** — one stream-SGD
       fit runs clean, then again with a flaky spill-read fault under the
       retry budget (bit-identical coefficients required, retries proven
       by the fault plan AND the flow.retry counter), then again with the
       budget at 0 (the same fault must now be fatal).
    """
    import jax

    from flink_ml_tpu import config, flow
    from flink_ml_tpu.ckpt import faults
    from flink_ml_tpu.ckpt.faults import TransientFault
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.serving import MicroBatchServer, ServerOverloaded
    from flink_ml_tpu.table import Table
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(17)
    t_start = time.perf_counter()

    # -- 1. serving under burst: reject at the door, bounded queues --------
    scaler = StandardScalerModel()
    scaler.mean = rng.standard_normal(d)
    scaler.std = np.abs(rng.standard_normal(d)) + 0.1
    scaler.set_input_col("features").set_output_col("scaled")
    pipeline = PipelineModel(
        [scaler, Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm")]
    )
    server = MicroBatchServer(pipeline, in_flight=2, admission=4)
    batch_nbytes = batch_rows * d * 4
    submitted = rejected = 0
    for _ in range(num_requests):
        try:
            server.submit(Table({"features": rng.standard_normal((batch_rows, d), dtype=np.float32)}))
            submitted += 1
        except ServerOverloaded as e:
            assert e.depth <= e.capacity, "reject must fire AT capacity, not past it"
            rejected += 1
    server.close()
    results = list(server.results())
    server._worker.join(timeout=120.0)
    assert not server._worker.is_alive(), "dispatch worker wedged: deadlock"
    health = server.health()
    assert submitted + rejected == num_requests
    assert len(results) == submitted, "every admitted request must retire"
    assert all(r.status == "ok" for r in results)
    peak_admit = server._requests.stats.peak_depth
    peak_window = server._window.stats.peak_depth
    assert peak_admit <= server.admission, "admission queue exceeded its bound"
    assert peak_window <= server.in_flight, "in-flight window exceeded its bound"
    jax.block_until_ready(
        [results[-1].table.column("norm")] if results else []
    )
    # deadline leg on a fresh server: a request whose deadline passed
    # before dispatch is shed WITHOUT paying staging or compute
    expiry_server = MicroBatchServer(pipeline, in_flight=2, admission=8)
    expired_submits = 0
    for _ in range(5):
        try:
            expiry_server.submit(
                Table({"features": rng.standard_normal((batch_rows, d), dtype=np.float32)}),
                deadline_ms=0.0,
            )
            expired_submits += 1
        except ServerOverloaded:
            pass
    expiry_server.close()
    expiry_results = list(expiry_server.results())
    assert len(expiry_results) == expired_submits
    expired = sum(1 for r in expiry_results if r.status in ("expired", "late"))
    assert expired == expired_submits, "0ms-deadline requests must be shed/late"

    # -- 2. shed_oldest staleness bound ------------------------------------
    capacity = 4
    chan = flow.BoundedChannel(capacity, policy=flow.SHED_OLDEST, name="soak.online")
    produced = 0
    for round_ in range(10):
        for _ in range(capacity * 40):  # the burst: 40x capacity per get
            chan.put(produced)
            produced += 1
        chan.get()  # the slow consumer folds one item per burst
    assert chan.stats.shed > 0, "the burst must actually shed"
    assert chan.stats.max_lag < capacity, (
        f"staleness contract broken: lag {chan.stats.max_lag} >= capacity {capacity}"
    )

    # -- 3. retries on vs off: bit-identical or fatal ----------------------
    X = rng.standard_normal((480, 16)).astype(np.float32)
    y = (X @ rng.standard_normal(16).astype(np.float32) > 0).astype(np.float32)

    def chunks():
        return iter([(X[i : i + 120], y[i : i + 120], None) for i in range(0, 480, 120)])

    def fit():
        sgd = SGD(max_iter=6, global_batch_size=100, tol=0.0)
        return sgd.optimize_stream(None, chunks(), BINARY_LOGISTIC_LOSS)

    clean, _, _, _ = fit()
    retry_before = metrics.get_counter("flow.retry", 0)
    with config.transient_retry_mode(4):
        with faults.flaky("datacache.read", times=3) as plan:
            retried, _, _, _ = fit()
    retries_paid = metrics.get_counter("flow.retry", 0) - retry_before
    assert plan.failures == 3, "the flaky plan must actually fire"
    assert retries_paid >= 3, "retries must ride flow.with_retries (counted)"
    assert np.array_equal(np.asarray(clean), np.asarray(retried)), (
        "transient-fault retries changed the training result"
    )
    fatal = False
    with config.transient_retry_mode(0):
        with faults.flaky("datacache.read", times=1):
            try:
                fit()
            except TransientFault:
                fatal = True
    assert fatal, "with the retry budget at 0 the transient fault must be fatal"

    result = {
        "numRequests": num_requests,
        "batchRows": batch_rows,
        "submitted": submitted,
        "rejected": rejected,
        "completed": len(results),
        # the SLO surface (ISSUE 12): per-stage latency percentiles from
        # the obs/hist.py histograms, via ServerHealth — queue-wait vs
        # batch-form vs dispatch vs readback, p50/p90/p99/p999 each
        "stageLatencyMs": health.stageLatencyMs,
        "admissionCapacity": server.admission,
        "inFlight": server.in_flight,
        "peakAdmissionDepth": int(peak_admit),
        "peakWindowDepth": int(peak_window),
        # the bounded-memory claim in bytes: the deepest the queues got,
        # priced at one staged batch each — versus the unbounded
        # alternative of `rejected` extra batches parked in memory
        "peakQueuedBytes": int((peak_admit + peak_window) * batch_nbytes),
        "shedCount": int(chan.stats.shed),
        "maxStalenessLag": int(chan.stats.max_lag),
        "stalenessCapacity": capacity,
        "retryCount": int(retries_paid),
        "retriesBitIdentical": True,  # asserted above
        "zeroDeadlock": True,  # asserted above (bounded join)
        "wallMs": (time.perf_counter() - t_start) * 1000.0,
    }
    log(
        f"overloadSoak: {rejected}/{num_requests} rejected at the door, queue "
        f"peaks {result['peakAdmissionDepth']}/{result['admissionCapacity']} admit "
        f"+ {result['peakWindowDepth']}/{result['inFlight']} window "
        f"({result['peakQueuedBytes'] / 1e6:.1f}MB), staleness lag "
        f"{result['maxStalenessLag']} < {capacity}, {retries_paid} transient "
        "retries bit-identical"
    )
    return result


def bench_hot_swap_soak(num_batches=96, batch_rows=512, d=32, num_swaps=24):
    """Robustness workload (ISSUE 10): versioned zero-pause model hot-swap
    under serving load, asserted in-process:

    1. **Zero-pause, zero-recompile swaps** — a trainer thread promotes
       `num_swaps` validated versions through `lifecycle.ModelLifecycle`
       while a MicroBatchServer drives the FUSED plan over `num_batches`
       batches. The jit compile counter must stay flat after warmup
       (model tensors are runtime operands, not baked constants), and
       per-batch p99 latency across the swap phase is reported against
       the no-swap steady state — the "zero pause" number.
    2. **Zero torn reads** — every served batch's modelVersion column
       must hold exactly ONE value, that value must have been promoted
       (never a rejected candidate), and versions must be monotone.
    3. **Gate + rollback** — a NaN-poisoned candidate is refused at the
       gate (`promoteRejected`); a bad-but-finite promotion followed by a
       guard-error window triggers the automatic rollback, which must
       restore the retained last-good version BIT-EXACTLY; the wall from
       first guard error to the first batch served on the rolled-back
       version is the rollback-to-recovery number.
    """
    import jax

    from flink_ml_tpu import flow
    from flink_ml_tpu.lifecycle import ModelLifecycle, PromotionRejected
    from flink_ml_tpu.models.classification.onlinelogisticregression import (
        OnlineLogisticRegressionModel,
    )
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.obs import tracing
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.serving import MicroBatchServer
    from flink_ml_tpu.table import Table
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(23)
    t_start = time.perf_counter()

    scaler = StandardScalerModel()
    scaler.mean = rng.standard_normal(d)
    scaler.std = np.abs(rng.standard_normal(d)) + 0.1
    scaler.set_input_col("features").set_output_col("features")
    model = OnlineLogisticRegressionModel()
    model.publish_model_arrays((np.zeros(d),), 0)
    model.set_features_col("features").set_prediction_col("pred")
    lifecycle = ModelLifecycle(model, retained=4, health_window=4, error_rate_trigger=0.5)
    pm = PipelineModel([scaler, model])
    server = MicroBatchServer(pm, in_flight=2, device_input=True, lifecycle=lifecycle)

    def batches(n):
        for _ in range(n):
            yield Table(
                {"features": rng.standard_normal((batch_rows, d), dtype=np.float32)}
            )

    def timed_serve(n):
        walls, versions = [], []
        t_prev = time.perf_counter()
        for out in server.serve(batches(n)):
            got = np.unique(np.asarray(out.column("modelVersion")))
            assert len(got) == 1, "torn read: one batch served by two versions"
            versions.append(int(got[0]))
            now = time.perf_counter()
            walls.append((now - t_prev) * 1000.0)
            t_prev = now
        return walls, versions

    # warmup + steady state (no swaps)
    timed_serve(4)
    tracing.install_jax_hooks()
    compiles_before = metrics.get_counter("jit.compiles", 0)
    steady_walls, _ = timed_serve(num_batches // 2)

    # swap phase: trainer promotes while the server serves
    accepted: list = []
    rejected_count = [0]
    base = np.zeros(d)

    def trainer():
        for i in range(1, num_swaps + 1):
            candidate = base + 0.01 * i
            if i % 6 == 0:  # NaN-poisoned update: the gate must refuse it
                poisoned = candidate.copy()
                poisoned[i % d] = np.nan
                try:
                    lifecycle.promote((poisoned,))
                except PromotionRejected:
                    rejected_count[0] += 1
                continue
            accepted.append(lifecycle.promote((candidate,)).version_id)
            time.sleep(0.001)

    t_swap = time.perf_counter()
    worker = flow.spawn(trainer, name="hotswap.trainer")
    swap_walls, served_versions = timed_serve(num_batches)
    worker.join(timeout=120.0)
    assert not worker.is_alive(), "trainer wedged"
    swap_phase_s = time.perf_counter() - t_swap

    compiles_during = metrics.get_counter("jit.compiles", 0) - compiles_before
    assert compiles_during == 0, f"{compiles_during} recompiles across {len(accepted)} swaps"
    valid = set(accepted) | {0}
    assert set(served_versions) <= valid, "a never-promoted version was served"
    assert served_versions == sorted(served_versions), "served versions went backwards"
    assert rejected_count[0] == num_swaps // 6, "every poisoned candidate must be refused"
    lifecycle.record_serve_ok()

    # rollback leg: bad-but-finite promotion slips the gate; guard errors
    # roll traffic back; recovery = first batch served on the good version
    good_version = model.model_version
    good_coeff = np.copy(model.coefficient)
    lifecycle.promote((base + 1e6,))
    t_trigger = time.perf_counter()
    for _ in range(4):
        lifecycle.record_guard_error(ValueError("downstream guard fired"))
    assert lifecycle.rollback_count == 1
    _, recovered = timed_serve(1)
    rollback_recovery_ms = (time.perf_counter() - t_trigger) * 1000.0
    assert recovered == [good_version], "post-rollback traffic must serve last-good"
    assert np.array_equal(model.coefficient, good_coeff), "rollback must be bit-exact"
    jax.block_until_ready([])

    p99 = lambda xs: float(np.percentile(np.asarray(xs), 99)) if xs else 0.0
    result = {
        "numBatches": num_batches,
        "batchRows": batch_rows,
        "swapCount": len(accepted),
        "promoteRejected": rejected_count[0],
        "rollbackCount": 1,
        "swapsPerSec": len(accepted) / swap_phase_s if swap_phase_s else 0.0,
        "steadyP50Ms": float(np.percentile(np.asarray(steady_walls), 50)),
        "steadyP99Ms": p99(steady_walls),
        "swapPhaseP50Ms": float(np.percentile(np.asarray(swap_walls), 50)),
        "swapPhaseP99Ms": p99(swap_walls),
        "rollbackRecoveryMs": rollback_recovery_ms,
        "recompilesDuringSwaps": int(compiles_during),  # asserted 0
        "tornReads": 0,  # asserted per batch above
        "servedVersionsMonotone": True,  # asserted above
        "rollbackBitExact": True,  # asserted above
        "wallMs": (time.perf_counter() - t_start) * 1000.0,
    }
    log(
        f"hotSwapSoak: {result['swapCount']} swaps at "
        f"{result['swapsPerSec']:.0f}/s under load, p99 {result['swapPhaseP99Ms']:.2f}ms "
        f"across swaps vs {result['steadyP99Ms']:.2f}ms steady, 0 recompiles, "
        f"{result['promoteRejected']} NaN candidates refused, rollback recovered "
        f"bit-exact in {rollback_recovery_ms:.1f}ms"
    )
    return result


def bench_serving_slo(
    d=24,
    rows_per_req=4,
    sweep=(250, 1000, 20000),
    phase_s=0.5,
    low_qps=40,
    low_n=30,
    deadline_ms=100.0,
    n_tenants=6,
    tenant_requests=240,
    tenant_d=512,
    in_budget=lambda: True,
):
    """The open-loop serving-SLO workload (ISSUE 19 / ROADMAP item 3),
    asserted in-process:

    1. **Bit-identity across batching modes** — the same request set
       served per-request, fixed-batch, and continuously-batched must
       produce bit-identical outputs per request (coalescing + padding
       only ever adds copies of real rows to row-wise kernels).
    2. **Continuous beats fixed where it should** — at low offered QPS
       continuous batching's p99 (flush on the forming budget) must beat
       fixed batching's (wait for a full bucket), and its goodput under a
       deadline must too; at saturation its goodput must be at least
       fixed's (both form full buckets there).
    3. **Open-loop saturation sweep** — arrivals follow a fixed schedule
       independent of completions (queueing delay stays honest, per the
       Spark perf-study methodology): offered QPS sweeps to saturation,
       reporting goodput (ok-within-deadline results/s), the saturation
       knee, per-stage p50/p99/p999, and the deadline-miss split.
    4. **Multi-tenant HBM paging, zero recompiles** — `n_tenants` models
       whose combined constants exceed `config.model_store_bytes` serve
       round-robin from ONE server through a `ModelStore`: the jit
       compile counter must stay flat across steady-state paging (model
       tensors are runtime operands), `hbm.live.model` must never exceed
       the budget, and the store's ledger parity must hold at the end.
    """
    import jax

    from flink_ml_tpu import config, flow
    from flink_ml_tpu.data.modelstore import ModelStore
    from flink_ml_tpu.models.classification.onlinelogisticregression import (
        OnlineLogisticRegressionModel,
    )
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.obs import memledger, tracing
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.serving import MicroBatchServer, ServerOverloaded
    from flink_ml_tpu.table import Table
    from flink_ml_tpu.utils import metrics

    rng = np.random.default_rng(19)
    t_start = time.perf_counter()
    tracing.install_jax_hooks()

    def scaler_pipeline():
        scaler = StandardScalerModel()
        scaler.mean = rng.standard_normal(d)
        scaler.std = np.abs(rng.standard_normal(d)) + 0.1
        scaler.set_input_col("features").set_output_col("scaled")
        norm = Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm")
        return PipelineModel([scaler, norm])

    pm = scaler_pipeline()
    feature = lambda rows: Table(
        {"features": rng.standard_normal((rows, d), dtype=np.float32)}
    )

    # warm every bucket shape the phases touch (compiles are a fixed cost
    # paid once per (plan, bucket); the SLO phases measure steady state)
    for rows in (8, 32):
        list(MicroBatchServer(pm, buckets=(8, 32)).serve(iter([feature(rows)])))

    # -- 1. bit-identity: request vs fixed vs continuous -------------------
    requests = [feature(int(r)) for r in rng.integers(1, 9, size=24)]

    def serve_all(server, batches):
        outputs = {}

        def collect():
            for r in server.results():
                outputs[r.seq] = r

        worker = flow.spawn(collect, name="slo.collect")
        seqs = [server.submit(b) for b in batches]
        server.close()
        worker.join(timeout=120.0)
        assert not worker.is_alive(), "collector wedged"
        return [outputs[s] for s in seqs]

    modes = {
        "request": MicroBatchServer(pm, buckets=(8, 32), batching="request", admission=64),
        "fixed": MicroBatchServer(
            pm, buckets=(8, 32), batching="fixed", form_rows=8, admission=64
        ),
        "continuous": MicroBatchServer(
            pm, buckets=(8, 32), batching="continuous", form_rows=32, admission=64
        ),
    }
    per_mode = {name: serve_all(s, requests) for name, s in modes.items()}
    for name in ("fixed", "continuous"):
        for ref, got, batch in zip(per_mode["request"], per_mode[name], requests):
            assert ref.status == got.status == "ok"
            assert got.table.num_rows == batch.num_rows
            assert np.array_equal(
                np.asarray(ref.table.column("norm")), np.asarray(got.table.column("norm"))
            ), f"{name} batching changed results vs the per-request path"

    # -- open-loop load phases ---------------------------------------------
    def run_phase(server, qps, duration_s, rows, tenant_fn=None, phase_deadline_ms=None):
        """Open-loop: arrivals at t0 + i/qps regardless of completions.
        Returns offered/goodput rates and client-observed latencies."""
        recv: dict = {}
        latencies: dict = {}
        sent: dict = {}

        def collect():
            for r in server.results():
                recv[r.seq] = r.status
                if r.seq in sent:
                    latencies[r.seq] = (time.monotonic() - sent[r.seq]) * 1000.0

        worker = flow.spawn(collect, name="slo.collect")
        payload = [feature(rows) for _ in range(8)]  # reuse: submit stays cheap
        interval = 1.0 / qps
        t0 = time.monotonic()
        i = offered = rejects = 0
        while True:
            target = t0 + i * interval
            if target > t0 + duration_s:
                break
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                now = time.monotonic()
                seq = server.submit(
                    payload[i % len(payload)],
                    deadline_ms=phase_deadline_ms,
                    tenant=None if tenant_fn is None else tenant_fn(i),
                )
                sent[seq] = now
                offered += 1
            except ServerOverloaded:
                rejects += 1
            i += 1
        server.close()
        worker.join(timeout=300.0)
        assert not worker.is_alive(), "collector wedged"
        elapsed = time.monotonic() - t0
        ok = sum(1 for s in recv.values() if s == "ok")
        late = sum(1 for s in recv.values() if s == "late")
        expired = sum(1 for s in recv.values() if s == "expired")
        lat = sorted(latencies.values())
        p = lambda q: lat[min(len(lat) - 1, int(q * len(lat)))] if lat else 0.0
        return {
            "offeredQps": i / elapsed,
            "goodputQps": ok / elapsed,
            "ok": ok,
            "late": late,
            "expired": expired,
            "rejected": rejects,
            "p50Ms": p(0.50),
            "p99Ms": p(0.99),
        }

    # -- 2. low offered QPS: the forming budget must bound latency ----------
    low = {}
    for name, kwargs in (
        ("fixed", dict(batching="fixed", form_rows=8)),
        ("continuous", dict(batching="continuous", form_rows=8)),
    ):
        server = MicroBatchServer(pm, buckets=(8,), admission=64, **kwargs)
        low[name] = run_phase(
            server, low_qps, low_n / low_qps, rows=1, phase_deadline_ms=deadline_ms
        )
    assert low["continuous"]["p99Ms"] < low["fixed"]["p99Ms"], (
        f"continuous p99 {low['continuous']['p99Ms']:.1f}ms must beat fixed "
        f"{low['fixed']['p99Ms']:.1f}ms at {low_qps} offered QPS"
    )
    assert low["continuous"]["goodputQps"] > low["fixed"]["goodputQps"], (
        "a full-bucket wait past the deadline must cost fixed batching goodput"
    )

    # -- 3. saturation sweep, both modes ------------------------------------
    sweeps = {"fixed": [], "continuous": []}
    health = None
    for qps in sweep:
        for name in ("fixed", "continuous"):
            if not in_budget():
                break
            server = MicroBatchServer(
                pm,
                buckets=(8, 32),
                batching=name,
                form_rows=32,
                admission=64,
                in_flight=2,
            )
            r = run_phase(server, qps, phase_s, rows=rows_per_req, phase_deadline_ms=deadline_ms)
            r["targetQps"] = qps
            sweeps[name].append(r)
            if name == "continuous":
                health = server.health()  # per-stage SLO surface
    cont_sweep, fixed_sweep = sweeps["continuous"], sweeps["fixed"]
    if cont_sweep and fixed_sweep:
        sat_cont = max(r["goodputQps"] for r in cont_sweep)
        sat_fixed = max(r["goodputQps"] for r in fixed_sweep)
        # 0.8 margin, not parity: both modes form full buckets at
        # saturation so the true ratio is ~1.0, but the 0.5s sweep phases
        # make the measured ratio noisy under scheduler jitter (observed
        # spread on a busy host reaches ~0.9) — the assert guards the
        # collapse mode (per-request flushing ~0.6x), not the noise floor
        assert sat_cont >= 0.8 * sat_fixed, (
            f"continuous saturated goodput {sat_cont:.0f}/s fell below fixed "
            f"{sat_fixed:.0f}/s — coalescing must not cost capacity"
        )
    else:  # sweep cut short by the budget: report the low-QPS phase rates
        sat_cont = low["continuous"]["goodputQps"]
        sat_fixed = low["fixed"]["goodputQps"]
    # the knee: the highest offered rate the server still served ~fully
    knee = 0.0
    for r in cont_sweep:
        if r["goodputQps"] >= 0.85 * r["offeredQps"]:
            knee = max(knee, r["offeredQps"])

    # -- 4. multi-tenant paging: N models, budget for ~3, zero recompiles ---
    tenants = [f"tenant{i}" for i in range(n_tenants)]
    probe_store = ModelStore(budget_bytes=None)

    def tenant_model(seed):
        trng = np.random.default_rng(seed)
        scaler = StandardScalerModel()
        scaler.mean = trng.standard_normal(tenant_d)
        scaler.std = np.abs(trng.standard_normal(tenant_d)) + 0.1
        scaler.set_input_col("features").set_output_col("features")
        olr = OnlineLogisticRegressionModel()
        olr.publish_model_arrays((trng.standard_normal(tenant_d),), 0)
        olr.set_features_col("features").set_prediction_col("pred")
        return PipelineModel([scaler, olr])

    tenant_models = {t: tenant_model(100 + i) for i, t in enumerate(tenants)}
    probe_store.register(tenants[0], tenant_models[tenants[0]])
    per_model = probe_store.estimated_nbytes(tenants[0])
    budget = int(per_model * 3.3)  # room for 3 of n_tenants residents
    assert n_tenants * per_model > budget, "the paging phase must exceed the budget"
    store = ModelStore(budget_bytes=budget)
    for t in tenants:
        store.register(t, tenant_models[t], quota=16)
    server = MicroBatchServer(
        store=store,
        buckets=(8, 32),
        batching="continuous",
        form_rows=32,
        admission=64,
    )
    tfeature = lambda rows: Table(
        {"features": rng.standard_normal((rows, tenant_d), dtype=np.float32)}
    )

    def serve_tenants(count, start=0):
        """Round-robin tenant requests; every submit samples the model
        ledger so the budget claim covers the whole phase, not endpoints."""
        outputs = {}
        peak = 0

        def collect():
            for r in server.results():
                outputs[r.seq] = r

        worker = flow.spawn(collect, name="slo.tenants")
        for i in range(count):
            while True:  # closed-loop pacing: this phase measures paging
                try:
                    server.submit(
                        tfeature(rows_per_req), tenant=tenants[(start + i) % n_tenants]
                    )
                    break
                except ServerOverloaded:
                    time.sleep(0.002)
            peak = max(peak, memledger.live_bytes("model"))
        server.close()
        worker.join(timeout=300.0)
        assert not worker.is_alive(), "tenant collector wedged"
        peak = max(peak, memledger.live_bytes("model"))
        return outputs, peak

    # warmup: every tenant's fused plan compiles ONCE per bucket shape
    # (first touch, through the paging store); the steady phase below then
    # pages with the compile counter pinned
    for t in tenants:
        list(
            MicroBatchServer(store.acquire(t), buckets=(8, 32)).serve(
                iter([tfeature(8), tfeature(32)])
            )
        )
    outputs, _ = serve_tenants(n_tenants * 2)
    assert all(r.status == "ok" for r in outputs.values())
    server = MicroBatchServer(
        store=store, buckets=(8, 32), batching="continuous", form_rows=32, admission=64
    )
    compiles_before = metrics.get_counter("jit.compiles", 0)
    page_ins_before = metrics.get_counter("modelstore.pageIn", 0)
    t_paged = time.perf_counter()
    outputs, peak_model_bytes = serve_tenants(tenant_requests, start=1)
    paged_s = time.perf_counter() - t_paged
    recompiles = metrics.get_counter("jit.compiles", 0) - compiles_before
    page_ins = metrics.get_counter("modelstore.pageIn", 0) - page_ins_before
    assert recompiles == 0, f"{recompiles} recompiles during steady-state paging"
    assert len(outputs) == tenant_requests and all(
        r.status == "ok" for r in outputs.values()
    ), "every tenant request must retire ok"
    assert peak_model_bytes <= budget, (
        f"hbm.live.model peaked at {peak_model_bytes} over the {budget} budget"
    )
    assert page_ins > 0, "the round-robin phase must actually page"
    store.check_ledger_parity()
    jax.block_until_ready([])

    offered_top = max((r["offeredQps"] for r in cont_sweep), default=float(low_qps))
    metrics.set_gauge("serving.offeredQps", offered_top)
    metrics.set_gauge("serving.goodputQps", sat_cont)
    metrics.set_gauge("serving.saturationQps", knee)

    result = {
        "offeredQps": offered_top,
        "goodputQps": sat_cont,
        "saturationQps": knee,
        "fixedGoodputQps": sat_fixed,
        "lowQps": {
            "offered": low_qps,
            "continuousP99Ms": low["continuous"]["p99Ms"],
            "fixedP99Ms": low["fixed"]["p99Ms"],
            "continuousGoodputQps": low["continuous"]["goodputQps"],
            "fixedGoodputQps": low["fixed"]["goodputQps"],
        },
        "sweep": {name: rs for name, rs in sweeps.items()},
        "deadlineMissLate": sum(r["late"] for r in cont_sweep),
        "deadlineMissExpired": sum(r["expired"] for r in cont_sweep),
        "rejected": sum(r["rejected"] for r in cont_sweep),
        "stageLatencyMs": health.stageLatencyMs if health else None,
        # the multi-tenant paging phase
        "tenants": n_tenants,
        "modelStoreBudgetBytes": budget,
        "perModelBytes": int(per_model),
        "pageInCount": int(page_ins),
        "pageInQps": page_ins / paged_s if paged_s else 0.0,
        "peakModelBytes": int(peak_model_bytes),
        "modelStore": store.stats,
        "recompileCount": int(recompiles),  # asserted 0
        "bitIdentical": True,  # asserted above
        "peakHbmBytes": int(memledger.peak_bytes()),
        "wallMs": (time.perf_counter() - t_start) * 1000.0,
    }
    log(
        f"servingSlo: knee {knee:.0f} req/s of {offered_top:.0f} offered, goodput "
        f"{sat_cont:.0f}/s continuous vs {sat_fixed:.0f}/s fixed; low-QPS p99 "
        f"{low['continuous']['p99Ms']:.1f}ms vs {low['fixed']['p99Ms']:.1f}ms; "
        f"{n_tenants} tenants in a {budget / 1e3:.0f}KB budget paged {page_ins}x "
        f"({result['pageInQps']:.0f}/s) with 0 recompiles, peak model bytes "
        f"{peak_model_bytes}"
    )
    return result


def bench_multichip_collectives(device_counts=(2, 8), in_budget=lambda: True):
    """The comm-layer workload (ISSUE 4): per-device-count collective
    traffic and wall time from scripts/bench_collectives.py — bucketed
    all-reduce (chunk count + chunked vs monolithic wall), the SparCML
    index-value gradient reduce at the sparseWideLR shape (sparse wire
    bytes vs dense-equivalent — the traffic-proportionality number), and
    a dense SGD fit with the overlap schedule off vs on (bit-identity
    asserted in-process). Each device count needs its own jax backend
    (xla_force_host_platform_device_count must win before jax initializes),
    hence one subprocess per N. The children force the CPU platform
    (scripts/bench_collectives.py), so they never contend for the chip
    this process holds — and every number they print is a VIRTUAL CPU
    DEVICE number, labelled `substrate: virtual_cpu_devices` per run: it
    says what the collectives count and move, not how fast a chip is."""
    import subprocess

    substrate = "virtual_cpu_devices"
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts", "bench_collectives.py"
    )
    runs = {}
    for n in device_counts:
        if n < 2:
            continue  # collectives need a second participant
        if not in_budget():
            runs[str(n)] = {"skipped": "budget"}
            continue
        proc = subprocess.run(
            [sys.executable, script, "--devices", str(n)],
            capture_output=True,
            text=True,
            timeout=240,
        )
        if proc.returncode != 0:
            tail = "; ".join(proc.stderr.strip().splitlines()[-3:])
            raise RuntimeError(f"bench_collectives --devices {n}: exit {proc.returncode}: {tail}")
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        r["substrate"] = substrate
        runs[str(n)] = r
        log(
            f"multichipCollectives[{n}] ({substrate}): "
            f"{r['denseAllReduce']['chunkCount']} buckets, "
            f"chunked {r['denseAllReduce']['chunkedMs']:.2f}ms vs mono "
            f"{r['denseAllReduce']['monolithicMs']:.2f}ms; sparse ratio "
            f"{r['sparseGradReduce']['sparseRatio']:.4f}; overlap SGD "
            f"{r['overlapSgd']['overlapMs']:.0f}ms vs eager {r['overlapSgd']['eagerMs']:.0f}ms"
        )
    return {"substrate": substrate, "runs": runs}


def main(argv):
    from flink_ml_tpu import config

    config.enable_compilation_cache()
    device = device_facts()
    budget = float(os.environ.get("BENCH_BUDGET_S", "420"))
    deadline = time.monotonic() + budget
    logreg_rows = 10_000_000
    if "--logreg-rows" in argv:
        try:
            logreg_rows = int(argv[argv.index("--logreg-rows") + 1])
        except (IndexError, ValueError):
            log("--logreg-rows needs an integer; using default")

    def in_budget(reserve=30.0):
        return time.monotonic() < deadline - reserve

    details = {}
    headline = {"value": None, "vs_baseline": None, "vs_baseline_source": None}

    def logreg():
        result = bench_logreg(logreg_rows, in_budget)
        headline["value"] = result["throughputPerChip"]
        return result

    def logreg_trace():  # reuses the executables the warm runs just compiled
        stats = bench_logreg_trace(logreg_rows)
        fit = details.get("logisticregression")
        if "inputThroughput" in fit and isinstance(
            stats.get("trainLoopMFU_trace"), float
        ):
            fit["trainLoopMFU"] = stats["trainLoopMFU_trace"]
            fit["trainLoopMFUSource"] = "profiler_trace"
        return stats

    def cpu_baseline():
        result = bench_cpu_baseline(logreg_rows)
        fit = details.get("logisticregression")
        if "inputThroughput" in fit:
            # job-level ratio: total TPU throughput vs the whole-host
            # CPU run of the same job (NOT per-chip vs host)
            headline["vs_baseline"] = (
                fit["inputThroughput"] / result["inputThroughput"]
            )
            headline["vs_baseline_source"] = "numpy_cpu_same_job_total_throughput"
        return result

    # (details key, stage, seconds of budget that must remain to start it;
    # None = always runs). cpuBaseline's reserve covers its worst observed
    # cost (~65s) with slack, so the line beats an external harness timeout.
    stages = [
        ("logisticregression", logreg, None),
        ("logisticregressionTrace", logreg_trace, 30.0),
        (
            "logisticregressionAmortized",
            lambda: bench_logreg_amortized(logreg_rows, in_budget=in_budget),
            60.0,
        ),
        ("lossParity", bench_loss_parity, 30.0),
        ("cpuBaseline", cpu_baseline, 150.0),
        ("sparseWideLR", bench_wide_sparse_lr, 30.0),
        ("sparse2dMesh", bench_sparse_2d_mesh, 30.0),
        ("kmeans", bench_kmeans, 30.0),
        ("pipelineServing", bench_pipeline_serving, 30.0),
        ("inputPipeline", bench_input_pipeline, 30.0),
        ("wholeFitDispatch", bench_whole_fit_dispatch, 30.0),
        ("fleetSweep", lambda: bench_fleet_sweep(in_budget=in_budget), 30.0),
        ("checkpointResume", bench_checkpoint_resume, 30.0),
        ("multiHostCheckpoint", bench_multihost_checkpoint, 30.0),
        ("elasticRecovery", bench_elastic_recovery, 30.0),
        ("overloadSoak", bench_overload_soak, 30.0),
        ("hotSwapSoak", bench_hot_swap_soak, 30.0),
        ("servingSlo", lambda: bench_serving_slo(in_budget=in_budget), 30.0),
        (
            "multichipCollectives",
            lambda: bench_multichip_collectives(in_budget=in_budget),
            30.0,
        ),
    ]
    flag_skips = {"lossParity": "--skip-parity", "cpuBaseline": "--skip-cpu"}
    failed = []
    try:
        for name, stage, reserve in stages:
            if flag_skips.get(name) in argv:
                details[name] = {"skipped": flag_skips[name]}
            elif reserve is not None and not in_budget(reserve):
                details[name] = {"skipped": "budget"}
            else:
                try:
                    details[name] = stage()
                except Exception as e:  # the line must still print; exit says it failed
                    log(f"{name} stage failed:\n{traceback.format_exc()}")
                    details[name] = {"failed": repr(e)}
                    failed.append(name)

        # recorded separately by scripts/bench_sweep.py; attach summary
        sweep_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks", "SWEEP.json"
        )
        if os.path.exists(sweep_path):
            with open(sweep_path) as f:
                details["sweep"] = {
                    "file": "benchmarks/SWEEP.json",
                    "meta": json.load(f)["meta"],
                }
    finally:
        value, vs_baseline = headline["value"], headline["vs_baseline"]
        print(
            json.dumps(
                {
                    "metric": "logisticregression_train_throughput",
                    "value": round(value, 2) if value is not None else None,
                    "unit": "records/s/chip",
                    "vs_baseline": round(vs_baseline, 2) if vs_baseline is not None else None,
                    "vs_baseline_source": headline["vs_baseline_source"],
                    "device": device,
                    "failedStages": failed,
                    "details": details,
                }
            ),
            flush=True,
        )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
