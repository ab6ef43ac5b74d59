"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once through the entry points a user calls, at the
full width of the north-star configuration (the reference's
conf/logisticregression-benchmark.json: 10M x 100, maxIter 20, batch
100k, device-born input), in ONE process (a chip belongs to one process
at a time), with every phase fatal:

  train      run_benchmark on the shipped config (fit + transform), the
             same fit driven directly for finite outputs of the expected
             shape, the engine's loss against the numpy
             reference-semantics SGD at 200k rows, and LogisticRegression,
             LinearSVC and LinearRegression at 1M x 100 on one shard as
             the estimator fits them (on the chip: the one-read dense
             epoch, ops/dense_epoch.py) against the reduce form
  loops      the other training loops at 1M rows — the stream loop's
             per-epoch DrainQueue path with donated carries and the
             checkpointed chunked path, both under whole_fit "off",
             against the whole-fit coefficients — and a KMeans fit of
             conf/kmeans-benchmark.json (the donating Lloyd loop)
  sparse     wide sparse LR at 1M rows, dim 1e6, 39 nnz on the lax
             gather/scatter path, one epoch checked against numpy at
             full width
  online     (one device only) OnlineLogisticRegression over a short
             device-born sparse stream, dim 2e6, 39 nnz, batches of 4,096:
             a version a batch, nothing read back while they are folded,
             the coefficient against numpy's FTRL-Proximal in float64
  serve      Pipeline([StandardScaler, LogisticRegression]) at d=100
             behind MicroBatchServer in continuous mode, equal to
             PipelineModel.transform; then the same from a populated AOT
             program bank with zero traces
  one_device (several devices only) the loops phase's table fitted on a
             one-device mesh against its default-mesh fit; then one pass
             over the same rows as a device table sharded by rows, which
             walks the shares and lays nothing out, against the same

On several devices every phase runs on the default mesh, the sparse
phase adds one fit on mesh.create_mesh_2d(2), and the train phase checks
that the batched training arrays span every device and that no device's
peak memory exceeds twice the mean.

There is no CPU path: `main` refuses to start unless jax reports a TPU,
and exits non-zero without printing a result. Standard output is two
lines. The first is the report: versions, compile-cache directory,
per-phase wall times, compile and persistent-cache counts, memory and
every phase's summary. Its wall times are smoke timings for the
builder's eyes — compile included, one run, no warm-up — NOT metrics.
The LAST line is the result the driver reads, and holds nothing else:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
with the device as jax reports it.

Usage: python chip_smoke.py
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Cross-path agreement bound for coefficients trained by two different
# compiled programs over the same batch schedule (whole-fit vs stream vs
# checkpointed chunks, 1D vs 2D mesh): the math per epoch is identical,
# the f32 accumulation order inside each program's contractions is not.
CROSS_PATH_RTOL = 1e-4
# Default-mesh fit vs one-device fit of the same table (ISSUE 21).
ONE_DEVICE_RTOL = 1e-5
# Engine loss vs the float64 numpy reference-semantics SGD (the last
# recorded chip run gave 3.4e-5).
LOSS_PARITY_RTOL = 1e-4
# A one-shard dense fit whose epochs read their batch once (the Pallas
# kernel of ops/dense_epoch.py) vs the same fit on the reduce form: the
# same float32 products, summed in another order.
DENSE_FORMS_RTOL = 1e-5
# The loss-parity check's problem: the north-star hyperparameters
# (conf/logisticregression-benchmark.json) and the table's seed.
DIM = 100
MAX_ITER = 20
BATCH = 100_000
LEARNING_RATE = 0.1
TOL = 1e-6
PARITY_SEED = 7


def check(cond, message: str) -> None:
    """A failed check is fatal — raised, not asserted, so `-O` keeps it."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {message}")


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


def rel_diff(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def memory_by_device():
    """Per device (bytes in use, peak) as the backend reports them; the
    CPU backend reports none."""
    import jax

    out = {}
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        out[str(dev.id)] = {
            "bytesInUse": stats.get("bytes_in_use"),
            "peakBytesInUse": stats.get("peak_bytes_in_use"),
        }
    return out


def _counters():
    from flink_ml_tpu.utils import metrics

    return metrics.snapshot()


def _counter_delta(before, name: str) -> float:
    from flink_ml_tpu.utils import metrics

    delta = metrics.snapshot_delta(before, metrics.snapshot())
    return float(delta["counters"].get(name, 0))


def _all_finite(x) -> bool:
    import jax.numpy as jnp

    return bool(jnp.all(jnp.isfinite(jnp.asarray(x))))


def device_facts():
    """The device as jax reports it — stamped into the report and the result."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


# ---------------------------------------------------------------------------
# train: the north-star fit at full width
# ---------------------------------------------------------------------------

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


def loss_parity(num_rows):
    """The same learnable problem through the engine and through the float64
    numpy reference-semantics loop: (engine loss, reference loss, rel diff)."""
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD

    rng = np.random.default_rng(PARITY_SEED)
    X = rng.random((num_rows, DIM), dtype=np.float32)
    truth = rng.random(DIM, dtype=np.float32) - 0.5
    y = (X @ truth > 0).astype(np.float32)
    w = rng.random(num_rows, dtype=np.float32)
    batch = min(BATCH, num_rows)

    sgd = SGD(
        max_iter=MAX_ITER,
        learning_rate=LEARNING_RATE,
        global_batch_size=batch,
        tol=TOL,
    )
    _, loss, _ = sgd.optimize(np.zeros(DIM, np.float32), X, y, w, BINARY_LOGISTIC_LOSS)
    _, ref_loss = _numpy_reference_sgd(
        X.astype(np.float64),
        y.astype(np.float64),
        w.astype(np.float64),
        MAX_ITER,
        batch,
        LEARNING_RATE,
        TOL,
    )
    rel = abs(loss - ref_loss) / max(abs(ref_loss), 1e-30)
    log(f"loss parity: engine {loss:.6f} vs reference-semantics {ref_loss:.6f} (rel {rel:.2e})")
    return loss, ref_loss, rel


def dense_forms(rows, batch, dim=DIM):
    """The three dense linear estimators on ONE shard, as a user fits them,
    against `_sgd_train_flat` on the reduce form over the same rows; and the
    two loss functions called directly on one batch that starts off the
    lanes. On the chip the estimator's fit must have taken the one-read
    kernel (`dense_epoch.one_pass` ticks); anywhere else it keeps the reduce
    form, and the direct call runs the kernel's own code interpreted."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models import _linear
    from flink_ml_tpu.models.classification.linearsvc import LinearSVC
    from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
    from flink_ml_tpu.models.regression.linearregression import LinearRegression
    from flink_ml_tpu.ops import dense_epoch, losses, optimizer
    from flink_ml_tpu.parallel import mesh as mesh_lib
    from flink_ml_tpu.table import Table

    host = _learnable_table(rows, dim, PARITY_SEED)
    device = jax.devices()[0]
    X = jax.device_put(host.column("features"), device)
    y = jax.device_put(host.column("label"), device)
    table = Table({"features": X, "label": y})
    on_chip = mesh_lib.on_tpu(X)
    n = jnp.asarray(rows, jnp.int32)
    out = {}
    with mesh_lib.use_mesh(mesh_lib.create_mesh(devices=[device])):
        for estimator, loss_func in (
            (LogisticRegression, losses.BINARY_LOGISTIC_LOSS),
            (LinearSVC, losses.HINGE_LOSS),
            (LinearRegression, losses.LEAST_SQUARE_LOSS),
        ):
            name = estimator.__name__
            stage = (
                estimator().set_max_iter(MAX_ITER).set_learning_rate(LEARNING_RATE)
                .set_global_batch_size(batch).set_tol(TOL)
            )
            before = _counters()
            coefficient = np.asarray(stage.fit(table).coefficient)
            taken = {
                form: _counter_delta(before, "dense_epoch." + form) for form in ("one_pass", "reduce")
            }
            check(
                taken == ({"one_pass": 1, "reduce": 0} if on_chip else {"one_pass": 0, "reduce": 1}),
                f"{name}: the dense epoch's form was counted {taken} on {device.platform}",
            )
            t0 = time.perf_counter()
            np.asarray(stage.fit(table).coefficient)
            fit_ms = 1e3 * (time.perf_counter() - t0)
            packed = optimizer._sgd_train_flat(
                X, y, jnp.zeros((0,), jnp.float32), jnp.zeros((dim,), jnp.float32), loss_func,
                batch, False, n, _linear._optimizer_for(stage)._hyper(), False, False, False,
            )
            _, reduce_form, _, epochs = optimizer.unpack_train_result(np.asarray(packed), dim)
            check(epochs == MAX_ITER, f"{name}: {epochs} epochs on the reduce form")
            check(_all_finite(coefficient) and np.any(coefficient != 0), f"{name}: coefficient {coefficient[:4]}")
            coef_rel = float(np.max(np.abs(coefficient - reduce_form)) / np.max(np.abs(reduce_form)))
            check(coef_rel <= DENSE_FORMS_RTOL, f"{name}: one read vs reduce form coefficients: rel {coef_rel:.2e}")
            # one epoch's sums by either loss function, called directly, on
            # a batch that starts off the lanes unless the batch is whole ones
            start = 3 * batch
            coeff = jnp.asarray(reduce_form, jnp.float32)
            one_read = jax.jit(
                lambda X, y, c: dense_epoch.one_pass(
                    loss_func.pointwise, X.T, y, None, c, jnp.int32(start), n, batch, device.platform != "tpu"
                )
            )(X, y, coeff)
            reduced = jax.jit(
                lambda X, y, c: loss_func(
                    X[start:start + batch], y[start:start + batch], jnp.ones((batch,), jnp.float32), c
                )
            )(X, y, coeff)
            sums_rel = max(rel_diff(np.atleast_1d(a), np.atleast_1d(b)) for a, b in zip(one_read, reduced))
            check(sums_rel <= DENSE_FORMS_RTOL, f"{name}: one read vs reduce form sums of a batch: rel {sums_rel:.2e}")
            out[name] = {"onePass": bool(taken["one_pass"]), "fitMs": fit_ms, "coefRel": coef_rel, "sumsRel": sums_rel}
    return out


def phase_train(rows=None, batch=None, parity_rows=200_000, forms_rows=1_000_000):
    import jax

    from flink_ml_tpu.benchmark import runner
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.parallel import mesh as mesh_lib
    from flink_ml_tpu.utils import read_write

    config = runner.load_config(
        os.path.join(REPO, "conf", "logisticregression-benchmark.json")
    )
    (name,) = [k for k in config if k != "version"]
    entry = config[name]
    if rows is not None:  # the tier-1 test's toy size; the chip runs the file as shipped
        entry["inputData"]["paramMap"]["numValues"] = rows
        entry["stage"]["paramMap"]["globalBatchSize"] = batch
    rows = entry["inputData"]["paramMap"]["numValues"]
    dim = entry["inputData"]["paramMap"]["vectorDim"]

    result = runner.run_benchmark(name, entry)
    check(result["wholeFitCount"] == 1, f"wholeFitCount {result['wholeFitCount']} != 1")
    check(
        result["wholeFitFallbacks"] == 0,
        f"wholeFitFallbacks {result['wholeFitFallbacks']} != 0",
    )
    check(result["inputRecordNum"] == rows, "input rows != configured rows")
    check(
        result["outputRecordNum"] == rows,
        f"output rows {result['outputRecordNum']} != input rows {rows}",
    )

    # the same fit driven directly: run_benchmark keeps neither the model
    # nor the outputs, and what comes out must be finite and of the
    # expected shape at full width
    stage = read_write.instantiate_with_params(entry["stage"])
    (table,) = runner.instantiate_generator(entry["inputData"]).get_data()
    jax.block_until_ready(table.column("features"))
    mem_after_datagen = memory_by_device()
    model = stage.fit(table)
    mem_after_fit = memory_by_device()
    out = model.transform(table)[0]
    pred = out.column(stage.get_prediction_col())
    raw = out.column(stage.get_raw_prediction_col())
    check(model.coefficient.shape == (dim,), f"coefficient shape {model.coefficient.shape}")
    check(_all_finite(model.coefficient), "non-finite coefficient")
    check(tuple(pred.shape) == (rows,), f"prediction shape {tuple(pred.shape)}")
    check(tuple(raw.shape) == (rows, 2), f"rawPrediction shape {tuple(raw.shape)}")
    check(_all_finite(raw), "non-finite rawPrediction")
    check(bool(((pred == 0.0) | (pred == 1.0)).all()), "prediction outside {0, 1}")

    n_devices = len(jax.devices())
    spans_all = None
    if n_devices > 1:
        # every device holds a share of the batched training arrays
        sgd = SGD(global_batch_size=stage.get_global_batch_size())
        X_b, y_b, w_b = sgd._batchify(
            mesh_lib.default_mesh(),
            table.column("features"),
            table.column("label"),
            None,
        )
        all_devices = set(jax.devices())
        spans_all = all(
            set(a.sharding.device_set) == all_devices
            for a in jax.tree_util.tree_leaves((X_b, y_b, w_b))
        )
        check(spans_all, "a batched training array does not span every device")
        del X_b, y_b, w_b
        peaks = [m["peakBytesInUse"] for m in memory_by_device().values()]
        if all(p is not None for p in peaks):  # the CPU backend reports none
            check(
                max(peaks) <= 2.0 * (sum(peaks) / len(peaks)),
                f"a device's peak memory exceeds twice the mean: {peaks}",
            )
    del table, out, pred, raw

    # loss parity on a learnable problem: the engine vs the float64 numpy
    # reference-semantics SGD on the same schedule
    loss, ref_loss, loss_rel = loss_parity(parity_rows)
    check(math.isfinite(loss), f"non-finite loss {loss}")
    # zero coefficients score every row at log 2: a loss below it has fallen
    check(loss < math.log(2.0), f"loss {loss} did not fall below log 2")
    check(
        loss_rel <= LOSS_PARITY_RTOL,
        f"loss {loss} vs reference {ref_loss}: rel {loss_rel:.2e} > {LOSS_PARITY_RTOL}",
    )
    forms = dense_forms(forms_rows, stage.get_global_batch_size())
    return {
        "rows": rows,
        "dim": dim,
        "denseForms": forms,
        "benchmarkTotalTimeMs": result["totalTimeMs"],
        "benchmarkPhaseTimesMs": result["phaseTimesMs"],
        "hostSyncCount": result["hostSyncCount"],
        "loss": loss,
        "referenceLoss": ref_loss,
        "lossRelDiff": loss_rel,
        "memoryAfterDatagen": mem_after_datagen,
        "memoryAfterFit": mem_after_fit,
        "batchesSpanEveryDevice": spans_all,
    }


# ---------------------------------------------------------------------------
# loops: the training loops the whole-fit default never reaches
# ---------------------------------------------------------------------------

def _learnable_table(rows, dim, seed):
    from flink_ml_tpu.table import Table

    rng = np.random.default_rng(seed)
    X = rng.random((rows, dim), dtype=np.float32)
    truth = rng.random(dim, dtype=np.float32) - 0.5
    y = (X @ truth > 0).astype(np.float32)
    return Table({"features": X, "label": y})


def _logreg(batch, max_iter=20):
    from flink_ml_tpu.models.classification.logisticregression import LogisticRegression

    return (
        LogisticRegression()
        .set_max_iter(max_iter)
        .set_learning_rate(0.1)
        .set_global_batch_size(batch)
        .set_tol(1e-6)
    )


def _numpy_lloyd(X, init, max_iter):
    centroids = init.copy()
    for _ in range(max_iter):
        d2 = ((X[:, None, :] - centroids[None, :, :]) ** 2).sum(axis=2)
        assign = d2.argmin(axis=1)
        for j in range(centroids.shape[0]):
            members = X[assign == j]
            if len(members):
                centroids[j] = members.mean(axis=0)
    return centroids


def phase_loops(rows=1_000_000, batch=100_000, dim=100, kmeans_rows=None):
    import jax

    from flink_ml_tpu import config
    from flink_ml_tpu.benchmark import runner
    from flink_ml_tpu.models.clustering.kmeans import KMeans
    from flink_ml_tpu.table import StreamTable, Table
    from flink_ml_tpu.utils import read_write

    table = _learnable_table(rows, dim, seed=11)
    before = _counters()
    whole = _logreg(batch).fit(table).coefficient
    check(_counter_delta(before, "dispatch.whole_fit") == 1, "in-memory fit was not whole-fit")

    X, y = table.column("features"), table.column("label")
    stream = StreamTable.from_batches(
        [
            Table({"features": X[i : i + batch], "label": y[i : i + batch]})
            for i in range(0, rows, batch)
        ]
    )
    ckpt_dir = tempfile.mkdtemp(prefix="chip-smoke-ckpt.")
    try:
        with config.whole_fit_mode("off"):
            before = _counters()
            streamed = _logreg(batch).fit(stream).coefficient
            stream_syncs = _counter_delta(before, "iteration.host_sync")
            check(
                _counter_delta(before, "dispatch.whole_fit") == 0,
                "stream fit under whole_fit off still took the resident path",
            )
            check(stream_syncs > 1, "stream fit did not drain per-epoch chunks")
            with config.iteration_checkpointing(ckpt_dir, interval=5):
                before = _counters()
                chunked = _logreg(batch).fit(table).coefficient
                snapshots = _counter_delta(before, "checkpoint.count")
                check(snapshots >= 1, "checkpointed fit wrote no snapshot")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    stream_rel, chunk_rel = rel_diff(streamed, whole), rel_diff(chunked, whole)
    check(_all_finite(whole), "non-finite whole-fit coefficient")
    check(stream_rel <= CROSS_PATH_RTOL, f"stream vs whole-fit coefficients: rel {stream_rel:.2e}")
    check(chunk_rel <= CROSS_PATH_RTOL, f"chunked vs whole-fit coefficients: rel {chunk_rel:.2e}")

    # KMeans: the donating Lloyd loop on the shipped config
    kconfig = runner.load_config(os.path.join(REPO, "conf", "kmeans-benchmark.json"))
    (kname,) = [k for k in kconfig if k != "version"]
    kentry = kconfig[kname]
    if kmeans_rows is not None:
        kentry["inputData"]["paramMap"]["numValues"] = kmeans_rows
    krows = kentry["inputData"]["paramMap"]["numValues"]
    kresult = runner.run_benchmark(kname, kentry)
    check(kresult["outputRecordNum"] == krows, "kmeans output rows != input rows")
    kmeans = read_write.instantiate_with_params(kentry["stage"])
    (ktable,) = runner.instantiate_generator(kentry["inputData"]).get_data()
    kmodel = kmeans.fit(ktable)
    # the fit donates ITS staged copy: the caller's column must still be readable
    kpred = kmodel.transform(ktable)[0].column(kmeans.get_prediction_col())
    k = kmeans.get_k()
    check(kmodel.centroids.shape == (k, ktable.column("features").shape[1]), "centroid shape")
    check(_all_finite(kmodel.centroids), "non-finite centroids")
    check(int(round(float(kmodel.weights.sum()))) == krows, "cluster counts do not sum to the rows")
    kpred_host = np.asarray(jax.device_get(kpred))
    check(kpred_host.shape == (krows,), f"kmeans prediction shape {kpred_host.shape}")
    check(bool(((kpred_host >= 0) & (kpred_host < k)).all()), "kmeans prediction outside [0, k)")

    # ... and against a numpy Lloyd on a small input with the same init
    rng = np.random.RandomState(5)
    Xs = np.concatenate(
        [rng.randn(700, 8) + c for c in (-4.0, 0.0, 4.0)]
    ).astype(np.float32)
    small = KMeans().set_k(3).set_max_iter(6).set_seed(9)
    got = small.fit(Table({"features": Xs})).centroids
    init = Xs[np.random.RandomState(9).choice(len(Xs), size=3, replace=False)]
    want = _numpy_lloyd(Xs.astype(np.float64), init.astype(np.float64), 6)
    kmeans_rel = rel_diff(got, want)
    check(kmeans_rel <= 1e-4, f"kmeans vs numpy Lloyd centroids: rel {kmeans_rel:.2e}")
    return {
        "rows": rows,
        "streamVsWholeFitRel": stream_rel,
        "chunkedVsWholeFitRel": chunk_rel,
        "streamHostSyncs": stream_syncs,
        "checkpointSnapshots": snapshots,
        "kmeansRows": krows,
        "kmeansVsNumpyRel": kmeans_rel,
    }, table, whole  # the table and its default-mesh fit feed phase_one_device


# ---------------------------------------------------------------------------
# sparse: the wide model on the lax gather/scatter path
# ---------------------------------------------------------------------------

def phase_sparse(rows=1_000_000, dim=1_000_000, nnz=39, batch=100_000):
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.parallel import mesh as mesh_lib
    from flink_ml_tpu.table import SparseBatch, Table

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    indices = jax.random.randint(k1, (rows, nnz), 0, dim, dtype=jnp.int32)
    values = jax.random.uniform(k2, (rows, nnz), dtype=jnp.float32)
    y = (jax.random.uniform(k3, (rows,)) > 0.5).astype(jnp.float32)
    table = Table({"features": SparseBatch(dim, indices, values), "label": y})

    # ONE epoch from zero coefficients has a closed form: every margin is
    # 0, so coeff = -(lr / B) * scatter_add(values * (0.5 - y)) over the
    # first batch — the gather/scatter checked against numpy at full width
    one = _logreg(batch, max_iter=1).fit(table).coefficient
    idx_h, val_h, y_h = (
        np.asarray(jax.device_get(a[:batch])) for a in (indices, values, y)
    )
    grad = np.zeros(dim, np.float64)
    np.add.at(grad, idx_h, val_h.astype(np.float64) * (0.5 - y_h.astype(np.float64))[:, None])
    want = -(0.1 / batch) * grad
    one_rel = rel_diff(one, want)
    check(one_rel <= 1e-5, f"one sparse epoch vs numpy closed form: rel {one_rel:.2e}")

    epochs = 5
    model = _logreg(batch, max_iter=epochs).fit(table)
    check(model.coefficient.shape == (dim,), f"sparse coefficient shape {model.coefficient.shape}")
    check(_all_finite(model.coefficient), "non-finite sparse coefficient")
    check(float(np.abs(model.coefficient).max()) > 0.0, "sparse fit left the model at zero")
    out = model.transform(table)[0]
    raw = out.column(model.get_raw_prediction_col())
    check(tuple(raw.shape) == (rows, 2), f"sparse rawPrediction shape {tuple(raw.shape)}")
    check(_all_finite(raw), "non-finite sparse rawPrediction")

    result = {"rows": rows, "dim": dim, "nnz": nnz, "epochs": epochs, "oneEpochVsNumpyRel": one_rel}
    if len(jax.devices()) > 1:
        # the feature-sharded (data x model) layout: same fit, 2D mesh
        with mesh_lib.use_mesh(mesh_lib.create_mesh_2d(2)):
            sharded = _logreg(batch, max_iter=epochs).fit(table).coefficient
        mesh2d_rel = rel_diff(sharded, model.coefficient)
        check(mesh2d_rel <= CROSS_PATH_RTOL, f"2D-mesh vs 1D sparse coefficients: rel {mesh2d_rel:.2e}")
        result["mesh2dVs1dRel"] = mesh2d_rel
    return result


# ---------------------------------------------------------------------------
# online: the unbounded loop over a sparse stream, state on the device
# ---------------------------------------------------------------------------

def _numpy_ftrl(batches, dim, alpha=0.1, beta=0.1):
    """FTRL-Proximal with reg 0 as OnlineLogisticRegression ships it, float64:
    per coordinate the mean gradient over the rows that hold it, and the
    update only where a row does."""
    w, z, n = np.zeros(dim), np.zeros(dim), np.zeros(dim)
    for idx, val, y in batches:
        p = 1.0 / (1.0 + np.exp(-np.sum(val * w[idx], axis=1)))
        grad, count = np.zeros(dim), np.zeros(dim)
        np.add.at(grad, idx, val * (p - y)[:, None])
        np.add.at(count, idx, 1.0)
        held = count > 0
        g = grad[held] / count[held]
        sigma = (np.sqrt(n[held] + g * g) - np.sqrt(n[held])) / alpha
        z[held] += g - sigma * w[held]
        n[held] += g * g
        w[held] = -z[held] / ((beta + np.sqrt(n[held])) / alpha)
    return w


def phase_online(dim=2_000_000, nnz=39, batch=4096, batches=8):
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.linalg import DenseVector
    from flink_ml_tpu.models.classification.onlinelogisticregression import OnlineLogisticRegression
    from flink_ml_tpu.table import SparseBatch, StreamTable, Table

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    rows = batch * batches
    # a tenth of the ids from 64 hot coordinates: repeats inside a batch
    hot = jax.random.uniform(k1, (rows, nnz)) < 0.1
    indices = jnp.where(
        hot, jax.random.randint(k2, (rows, nnz), 0, 64), jax.random.randint(k1, (rows, nnz), 0, dim)
    ).astype(jnp.int32)
    values = jax.random.uniform(k2, (rows, nnz), dtype=jnp.float32)
    y = (jax.random.uniform(k3, (rows,)) > 0.5).astype(jnp.float32)
    cut = [tuple(a[k * batch : (k + 1) * batch] for a in (indices, values, y)) for k in range(batches)]
    stream = StreamTable.from_batches(
        [Table({"features": SparseBatch(dim, idx, val), "label": lab}) for idx, val, lab in cut]
    )
    model = (
        OnlineLogisticRegression()
        .set_global_batch_size(batch)
        .set_initial_model_data(Table({"coefficient": [DenseVector(np.zeros(dim))]}))
        .fit(stream)
    )
    before = _counters()
    versions = [model.process_updates(max_batches=1) for _ in range(batches)]
    check(versions == list(range(1, batches + 1)), f"a version a global batch: {versions}")
    check(_counter_delta(before, "online.versions") == batches, "online.versions counts every version")
    check(_counter_delta(before, "iteration.host_sync") == 0, "a host sync while batches were folded")
    check(_counter_delta(before, "readback.count") == 0, "a readback while batches were folded")
    check(_counter_delta(before, "h2d.bytes") == 0, "a device-born batch was uploaded")
    check(isinstance(model.model_arrays()[0], jax.Array), "the published coefficient left the device")
    coefficient = model.coefficient
    check(coefficient.shape == (dim,) and _all_finite(coefficient), "online coefficient shape or values")
    host = [(np.asarray(idx), np.asarray(val, np.float64), np.asarray(lab, np.float64)) for idx, val, lab in cut]
    want = _numpy_ftrl(host, dim)
    rel = rel_diff(coefficient, want)
    check(rel <= CROSS_PATH_RTOL, f"online FTRL vs numpy float64: rel {rel:.2e}")
    return {"dim": dim, "nnz": nnz, "batch": batch, "versions": versions[-1], "vsNumpyRel": rel}


# ---------------------------------------------------------------------------
# serve: continuous batching, then the same from a populated program bank
# ---------------------------------------------------------------------------

def phase_serve(dim=100, fit_rows=100_000, n_requests=36):
    from flink_ml_tpu import config, flow
    from flink_ml_tpu.pipeline import Pipeline
    from flink_ml_tpu.models.feature.standardscaler import StandardScaler
    from flink_ml_tpu.serving import MicroBatchServer
    from flink_ml_tpu.table import Table

    buckets = (8, 32, 128)
    train = _learnable_table(fit_rows, dim, seed=13)
    model = Pipeline(
        [
            StandardScaler().set_input_col("features").set_output_col("scaled"),
            _logreg(min(fit_rows, 10_000)).set_features_col("scaled"),
        ]
    ).fit(train)

    rng = np.random.default_rng(17)
    # mixed row counts from a few sizes: the eager reference below compiles
    # once per distinct shape, the server once per bucket
    requests = [
        Table({"features": rng.random((int(r), dim), dtype=np.float32)})
        for r in rng.choice([1, 3, 8, 20, 33, 64], size=n_requests)
    ]
    reference = [model.transform(r)[0] for r in requests]

    def serve_all():
        server = MicroBatchServer(
            model, buckets=buckets, batching="continuous", form_rows=buckets[-1],
            admission=2 * n_requests,
        )
        results = {}

        def collect():
            for r in server.results():
                results[r.seq] = r

        worker = flow.spawn(collect, name="chip-smoke.collect")
        seqs = [server.submit(r) for r in requests]
        server.close()
        worker.join(timeout=300.0)
        check(not worker.is_alive(), "serving result collector did not finish")
        return [results[s] for s in seqs]

    def compare(served, label):
        worst = 0.0
        for got, want, request in zip(served, reference, requests):
            check(got.status == "ok", f"{label}: request status {got.status}")
            check(got.table.num_rows == request.num_rows, f"{label}: served row count")
            raw_got = np.asarray(got.table.column("rawPrediction"), np.float64)
            raw_want = np.asarray(want.column("rawPrediction"), np.float64)
            check(np.isfinite(raw_got).all(), f"{label}: non-finite rawPrediction")
            worst = max(worst, float(np.abs(raw_got - raw_want).max()))
            decided = np.abs(raw_want[:, 1] - 0.5) > 1e-4
            check(
                np.array_equal(
                    np.asarray(got.table.column("prediction"))[decided],
                    np.asarray(want.column("prediction"))[decided],
                ),
                f"{label}: served prediction differs from PipelineModel.transform",
            )
        check(worst <= 1e-5, f"{label}: rawPrediction differs from transform by {worst:.2e}")
        return worst

    plain_diff = compare(serve_all(), "continuous")

    bank_dir = tempfile.mkdtemp(prefix="chip-smoke-bank.")
    try:
        with config.program_bank_mode(bank_dir):  # populate: AOT-compile + back-fill
            warm = MicroBatchServer(model, buckets=buckets).warmup(requests[0])
        with config.program_bank_mode(bank_dir):  # a bank freshly warm-loaded from disk
            before = _counters()
            banked = serve_all()
            traces = _counter_delta(before, "jit.traces")
            bank_hits = _counter_delta(before, "bank.hits")
    finally:
        shutil.rmtree(bank_dir, ignore_errors=True)
    check(traces == 0, f"banked serve traced {traces:.0f} kernels")
    check(bank_hits >= 1, "banked serve never hit the bank")
    banked_diff = compare(banked, "banked")
    return {
        "requests": n_requests,
        "rows": int(sum(r.num_rows for r in requests)),
        "maxAbsDiffVsTransform": plain_diff,
        "bankPrograms": warm["programs"],
        "bankedTraces": traces,
        "bankHits": bank_hits,
        "bankedMaxAbsDiffVsTransform": banked_diff,
    }


# ---------------------------------------------------------------------------
# one_device: the default-mesh fit against one device (several devices only)
# ---------------------------------------------------------------------------

def phase_one_device(table, default_mesh_coefficient, batch=100_000):
    import jax

    from flink_ml_tpu.parallel import mesh as mesh_lib

    mesh1 = mesh_lib.create_mesh(devices=jax.devices()[:1])
    with mesh_lib.use_mesh(mesh1):
        one = _logreg(batch).fit(table).coefficient
    rel = rel_diff(one, default_mesh_coefficient)
    check(rel <= ONE_DEVICE_RTOL, f"one-device vs default-mesh coefficients: rel {rel:.2e}")
    # a fit that reads no batch twice, over a device table sharded by rows in
    # whole batches (two a share), trains every batch where it lies
    from flink_ml_tpu.table import Table

    mesh = mesh_lib.default_mesh()
    shards = mesh_lib.num_data_shards(mesh)
    X, y = table.column("features"), table.column("label")
    walk_batch = X.shape[0] // (2 * shards)
    rows = 2 * shards * walk_batch
    by_rows = lambda a: jax.device_put(a[:rows], mesh_lib.data_sharding(mesh, a.ndim))  # noqa: E731
    one_pass = _logreg(walk_batch, max_iter=2 * shards)
    before = _counters()
    walked = one_pass.fit(Table({"features": by_rows(X), "label": by_rows(y)})).coefficient
    check(_counter_delta(before, "layout.walk") == 1, "a one-pass fit of a row-sharded device table did not walk")
    check(_counter_delta(before, "layout.walk.legs") == shards, "the walk did not visit every share")
    with mesh_lib.use_mesh(mesh1):
        one = one_pass.fit(Table({"features": X[:rows], "label": y[:rows]})).coefficient
    walk_rel = rel_diff(walked, one)
    check(walk_rel <= ONE_DEVICE_RTOL, f"walked vs one-device coefficients: rel {walk_rel:.2e}")
    return {"oneDeviceVsDefaultMeshRel": rel, "walkedVsOneDeviceRel": walk_rel}


# ---------------------------------------------------------------------------

def result_line(device) -> str:
    """The last stdout line: exactly `ok` and the device as jax reports
    it (platform, kind, count) — the driver refuses any other key."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"]),
            },
        }
    )


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: jax reports platform {devices[0].platform!r}, not 'tpu' — "
            "this script only runs on the chip",
            file=sys.stderr,
        )
        return 2

    import jaxlib

    from flink_ml_tpu import config
    from flink_ml_tpu.obs import tracing
    from flink_ml_tpu.utils import metrics

    cache_dir = config.enable_compilation_cache()
    tracing.install_jax_hooks()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    device = device_facts()
    log(
        f"device {device}; jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu_version}; compile cache {cache_dir}"
    )

    timings, phases = {}, {}

    def run(name, fn, *args):
        log(f"phase {name} ...")
        t0 = time.perf_counter()
        out = fn(*args)
        timings[name] = round(time.perf_counter() - t0, 2)
        # a phase returns its summary, or (summary, things a later phase reuses)
        phases[name] = out[0] if isinstance(out, tuple) else out
        log(f"phase {name} ok in {timings[name]} s: {json.dumps(phases[name], default=float)}")
        return out

    run("train", phase_train)
    _, loops_table, loops_coefficient = run("loops", phase_loops)
    run("sparse", phase_sparse)
    if len(devices) == 1:
        run("online", phase_online)
    run("serve", phase_serve)
    if len(devices) > 1:
        run("one_device", phase_one_device, loops_table, loops_coefficient)

    print(
        json.dumps(
            {
                "report": "chip_smoke",
                "device": device,
                "versions": {
                    "jax": jax.__version__,
                    "jaxlib": jaxlib.__version__,
                    "libtpu": libtpu_version,
                },
                "compileCacheDir": cache_dir,
                "smokeTimingsS": timings,
                "jitCompiles": int(metrics.snapshot()["counters"].get("jit.compiles", 0)),
                "persistentCacheHits": cache_events["hits"],
                "persistentCacheMisses": cache_events["misses"],
                "memory": memory_by_device(),
                "phases": phases,
            },
            default=float,
        ),
        flush=True,
    )
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
