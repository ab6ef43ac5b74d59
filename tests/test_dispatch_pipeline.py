"""Dispatch-pipeline battery: epoch chunking must be invisible.

The chunked loops (docs/performance.md) fuse K epochs per device program,
drain convergence scalars through a bounded-depth queue, and donate
carries between chunks — but the tol check still runs at every epoch
inside the chunk program, so the final carry, stop epoch, and stop
criteria must be BIT-IDENTICAL to the unchunked (K=1) loop for any K.
These tests pin that guarantee, and the host-sync budget the pipeline
exists to enforce.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu import config
from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS, SPARSE_BINARY_LOGISTIC_LOSS
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import dispatch
from flink_ml_tpu.parallel.iteration import iterate_bounded
from flink_ml_tpu.table import Table
from flink_ml_tpu.utils import metrics

K_VALUES = [1, 4, 32, "maxIter"]


@pytest.fixture
def chunk_size():
    """Restore the process-wide chunk knob after each test."""
    yield None
    config.iteration_chunk_size = None


def _dense_problem(n=400, d=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ np.linspace(1, -1, d) > 0).astype(np.float32)
    return X, y


def _sparse_problem(n=96, d=12, seed=7):
    rng = np.random.RandomState(seed)
    nnz = 4
    indices = np.stack([rng.choice(d, nnz, replace=False) for _ in range(n)]).astype(
        np.int32
    )
    values = rng.randn(n, nnz).astype(np.float32)
    w_true = np.linspace(1, -1, d)
    dense = np.zeros((n, d), np.float32)
    np.put_along_axis(dense, indices, values, axis=1)
    y = (dense @ w_true > 0).astype(np.float32)
    return (indices, values), y


def _fit_chunked(X, y, loss, d, tmp_path, k, max_iter=40, tol=0.0):
    """One checkpointed (= chunked host-driven) SGD fit at chunk size k."""
    config.iteration_chunk_size = max_iter if k == "maxIter" else k
    sgd = SGD(
        max_iter=max_iter,
        global_batch_size=100,
        tol=tol,
        checkpoint_dir=str(tmp_path / f"ck_{k}"),
    )
    return sgd.optimize(np.zeros(d), X, y, None, loss)


class TestChunkParity:
    """Chunked vs unchunked: K=1 IS the old per-epoch loop; every other K
    must reproduce it bit for bit, including the stop epoch."""

    def test_sgd_dense_all_chunk_sizes(self, tmp_path, chunk_size):
        X, y = _dense_problem()
        base = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, 1)
        assert base[2] == 40
        for k in K_VALUES[1:]:
            got = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, k)
            np.testing.assert_array_equal(got[0], base[0])
            assert got[1] == base[1]
            assert got[2] == base[2]

    def test_sgd_dense_tol_fires_mid_chunk(self, tmp_path, chunk_size):
        """Stop epoch when tol fires INSIDE a chunk: identical for any K —
        the chunk program's while condition checks tol every epoch, it
        does not overshoot to the chunk boundary."""
        X, y = _dense_problem()
        # the criteria value at epoch 10 becomes tol: the full run then
        # stops at the first epoch at or below it — mid-run by construction
        probe = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, 1, max_iter=10)
        tol = float(probe[1])
        base = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, 1, tol=tol)
        assert 0 < base[2] < 40, "tol must fire mid-run for this test to bite"
        for k in K_VALUES[1:]:
            got = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, k, tol=tol)
            np.testing.assert_array_equal(got[0], base[0])
            assert got[2] == base[2], f"stop epoch diverged at K={k}"

    def test_sgd_sparse_all_chunk_sizes(self, tmp_path, chunk_size):
        Xs, y = _sparse_problem()
        base = _fit_chunked(Xs, y, SPARSE_BINARY_LOGISTIC_LOSS, 12, tmp_path, 1)
        for k in K_VALUES[1:]:
            got = _fit_chunked(Xs, y, SPARSE_BINARY_LOGISTIC_LOSS, 12, tmp_path, k)
            np.testing.assert_array_equal(got[0], base[0])
            assert got[2] == base[2]

    def test_checkpoint_resume_matches_uninterrupted(self, tmp_path, chunk_size):
        """Kill mid-training, resume with a different chunk size: the
        resumed run must land on the uninterrupted run's exact result."""
        X, y = _dense_problem()
        full = _fit_chunked(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, 1)

        ck = str(tmp_path / "resume")
        config.iteration_chunk_size = 4
        SGD(
            max_iter=13, global_batch_size=100, tol=0.0, checkpoint_dir=ck
        ).optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
        config.iteration_chunk_size = 32
        got = SGD(
            max_iter=40, global_batch_size=100, tol=0.0, checkpoint_dir=ck
        ).optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
        np.testing.assert_array_equal(got[0], full[0])
        assert got[2] == 40

    def test_chunk_ends_clamp_to_checkpoint_boundaries(self, tmp_path, chunk_size):
        """checkpoint_interval=5 with K=32: snapshots still land at the
        exact epoch cadence (chunk ends clamp to boundaries)."""
        from flink_ml_tpu.parallel.iteration import load_iteration_checkpoint

        X, y = _dense_problem()
        ck = str(tmp_path / "cadence")
        config.iteration_chunk_size = 32
        SGD(
            max_iter=12,
            global_batch_size=100,
            tol=0.0,
            checkpoint_dir=ck,
            checkpoint_interval=5,
        ).optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
        carry_like = (jnp.zeros(8), jnp.zeros(8), jnp.asarray(0.0), jnp.asarray(0))
        restored = load_iteration_checkpoint(ck, carry_like)
        assert restored is not None
        assert restored[1] == 10  # last multiple of 5 <= 12


class TestIterateBoundedChunked:
    """The generic iteration runtime: host-driven chunked loop vs the pure
    on-device while_loop, Lloyd-style body included."""

    @staticmethod
    def _lloyd_body(X):
        def body(carry, epoch):
            centroids = carry
            d2 = ((X[:, None, :] - centroids[None, :, :]) ** 2).sum(-1)
            assign = jnp.argmin(d2, axis=1)
            one_hot = jax.nn.one_hot(assign, centroids.shape[0], dtype=X.dtype)
            counts = one_hot.sum(0)
            sums = one_hot.T @ X
            new = jnp.where(
                counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-30),
                centroids,
            )
            shift = jnp.max(jnp.abs(new - centroids))
            return new, shift

        return body

    def test_lloyd_body_chunked_matches_on_device(self, tmp_path, chunk_size):
        rng = np.random.RandomState(0)
        X = jnp.asarray(rng.randn(60, 3).astype(np.float32))
        init = X[:4]
        body = self._lloyd_body(X)
        on_device = iterate_bounded(body, init, max_iter=25, tol=1e-4)
        assert 0 < on_device.num_epochs <= 25
        for k in [1, 4, 32, 25]:
            res = iterate_bounded(
                body, init, max_iter=25, tol=1e-4,
                checkpoint_dir=str(tmp_path / f"lloyd_{k}"), chunk_size=k,
            )
            np.testing.assert_array_equal(
                np.asarray(res.carry), np.asarray(on_device.carry)
            )
            assert res.num_epochs == on_device.num_epochs

    def test_listener_still_sees_every_epoch(self, tmp_path):
        """A listener forces per-epoch dispatch (K=1) — the listener
        contract exposes every (epoch, carry) pair, chunking must not
        swallow callbacks."""
        from flink_ml_tpu.parallel.iteration import IterationListener

        seen = []

        class Rec(IterationListener):
            def on_epoch_watermark_incremented(self, epoch, carry):
                seen.append(epoch)

            def on_iteration_terminated(self, carry):
                seen.append("end")

        body = lambda c, e: (c + 1.0, jnp.asarray(1.0, jnp.float32))
        res = iterate_bounded(body, jnp.zeros(2), max_iter=5, tol=None, listener=Rec())
        assert seen == [1, 2, 3, 4, 5, "end"]
        assert res.num_epochs == 5

    def test_lloyd_fit_donates_nothing_and_packs_the_train_loop(self):
        """KMeans' fit program (`_lloyd_fit`) is the train loop with its
        result packed [centroids.ravel | counts], and it donates nothing: a
        table trained in place is the caller's and stays alive. (The
        donating variant went with the staged copy it consumed.)"""
        from flink_ml_tpu.models.clustering.kmeans import (
            _lloyd_fit,
            _lloyd_train_impl,
        )

        rng = np.random.RandomState(1)
        X = jnp.asarray(rng.randn(50, 4).astype(np.float32))
        init = X[:3]
        mi = jnp.asarray(10, jnp.int32)
        c, n = jax.jit(_lloyd_train_impl, static_argnums=(4, 5))(
            X, None, init, mi, "euclidean", None
        )
        packed = _lloyd_fit(X, None, init, mi, "euclidean", None)
        assert not X.is_deleted() and not init.is_deleted()
        np.testing.assert_array_equal(np.asarray(packed[:12]).reshape(3, 4), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(packed[12:]), np.asarray(n))


class TestHostSyncBudget:
    """The acceptance metric: a maxIter=200 LR fit must not sync O(200)
    times. Fused path: exactly 1. Chunked checkpointed path: the
    convergence drains stay within ceil(200/K) + dispatch_depth."""

    MAX_ITER = 200

    def _delta(self, fn):
        before = metrics.snapshot()
        fn()
        return metrics.snapshot_delta(before, metrics.snapshot())["counters"]

    def test_fused_lr_fit_is_one_sync(self):
        from flink_ml_tpu.models.classification.logisticregression import (
            LogisticRegression,
        )

        X, y = _dense_problem(n=600)
        t = Table({"features": X.astype(np.float64), "label": y.astype(np.float64)})
        lr = (
            LogisticRegression()
            .set_max_iter(self.MAX_ITER)
            .set_global_batch_size(200)
            .set_reg(0.01)
        )
        counters = self._delta(lambda: lr.fit(t))
        k = config.iteration_chunk_for(self.MAX_ITER)
        budget = math.ceil(self.MAX_ITER / k) + 2
        assert counters.get("iteration.host_sync", 0) == 1 <= budget

    def test_chunked_lr_fit_within_budget(self, tmp_path, chunk_size):
        # whole_fit off: this pins the CHUNKED path's drain budget (the
        # fit-end-only snapshot cadence would otherwise go resident)
        with config.whole_fit_mode("off"):
            for k in [4, 32, self.MAX_ITER]:
                config.iteration_chunk_size = k
                X, y = _dense_problem()
                sgd = SGD(
                    max_iter=self.MAX_ITER,
                    global_batch_size=100,
                    tol=0.0,
                    checkpoint_dir=str(tmp_path / f"budget_{k}"),
                    checkpoint_interval=self.MAX_ITER,  # snapshot only at the end
                )
                counters = self._delta(
                    lambda: sgd.optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
                )
                budget = math.ceil(self.MAX_ITER / k) + 2
                drains = counters.get("iteration.host_sync.drain", 0)
                assert drains <= budget, f"K={k}: {drains} drains > budget {budget}"
                # total syncs = drains + 1 end checkpoint + 1 packed fit readback
                assert counters.get("iteration.host_sync", 0) <= budget + 2

    def test_per_epoch_regression_guard(self, tmp_path, chunk_size):
        """K=1 (the old behavior) really is O(maxIter) — the counter
        measures what it claims, so a regression cannot hide in it.
        whole_fit off: the resident path would collapse this to 1."""
        config.iteration_chunk_size = 1
        X, y = _dense_problem()
        sgd = SGD(
            max_iter=50, global_batch_size=100, tol=0.0,
            checkpoint_dir=str(tmp_path / "k1"), checkpoint_interval=50,
        )
        with config.whole_fit_mode("off"):
            counters = self._delta(
                lambda: sgd.optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
            )
        assert counters.get("iteration.host_sync.drain", 0) == 50


class TestDispatchPrimitives:
    def test_chunk_for_adaptive(self):
        assert config.iteration_chunk_for(1) == 1
        assert config.iteration_chunk_for(8) == 1
        assert config.iteration_chunk_for(80) == 10
        assert config.iteration_chunk_for(200) == 25
        assert config.iteration_chunk_for(10_000) == 32  # clamped
        assert config.iteration_chunk_for(100, chunk_size=7) == 7
        assert config.iteration_chunk_for(5, chunk_size=64) == 5  # <= maxIter

    def test_chunk_for_respects_process_knob(self):
        config.iteration_chunk_size = 16
        try:
            assert config.iteration_chunk_for(200) == 16
        finally:
            config.iteration_chunk_size = None

    def test_next_boundary(self):
        assert dispatch.next_boundary(0, 5) == 5
        assert dispatch.next_boundary(4, 5) == 5
        assert dispatch.next_boundary(5, 5) == 10
        assert dispatch.next_boundary(7, None) is None
        assert dispatch.next_boundary(7, 0) is None

    def test_drain_queue_depth(self):
        q = dispatch.DrainQueue(2)
        entries = [
            dispatch.InFlight(i, i + 1, None, jnp.asarray([float(i + 1), 0.5]))
            for i in range(4)
        ]
        assert q.push(entries[0]) == []
        assert q.push(entries[1]) == []
        drained = q.push(entries[2])  # over depth: oldest comes back
        assert len(drained) == 1 and drained[0][1] == 1
        rest = q.drain_all()
        assert [e for _, e, _ in rest] == [2, 3]
        assert len(q) == 0

    def test_supports_donation_is_false_on_cpu(self):
        assert jax.default_backend() == "cpu"
        assert dispatch.supports_donation() is False

    def test_drain_accounting(self):
        before = metrics.snapshot()
        q = dispatch.DrainQueue(1)
        q.push(dispatch.InFlight(0, 1, None, jnp.asarray([1.0, 0.5])))
        q.drain_all()
        delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        assert delta.get("iteration.host_sync.drain", 0) == 1


# ---------------------------------------------------------------------------
# whole-fit resident programs (config.whole_fit, docs/performance.md)
# ---------------------------------------------------------------------------

def _counters(fn):
    before = metrics.snapshot()
    out = fn()
    return out, metrics.snapshot_delta(before, metrics.snapshot())["counters"]


def _stream_chunks(X, y, chunk=160):
    for i in range(0, X.shape[0], chunk):
        yield X[i : i + chunk], y[i : i + chunk], None


WHOLE_FIT_ITERS = [1, 7, 200]


class TestWholeFitParity:
    """The whole-fit resident path must be INVISIBLE: carries, stop
    epochs, and final packs bit-identical to the chunked/per-epoch
    reference (`whole_fit` off) for every covered loop, including
    tol-early-stop — while collapsing the fit to one dispatch + one
    packed readback."""

    def _ckpt_fit(self, X, y, loss, d, tmp_path, tag, max_iter, tol=0.0):
        sgd = SGD(
            max_iter=max_iter,
            global_batch_size=100,
            tol=tol,
            checkpoint_dir=str(tmp_path / tag),
            checkpoint_key=tag,
            checkpoint_interval=max_iter,  # fit-end boundary only
        )
        return sgd.optimize(np.zeros(d), X, y, None, loss)

    @pytest.mark.parametrize("max_iter", WHOLE_FIT_ITERS)
    def test_checkpointed_dense_sgd(self, tmp_path, max_iter):
        X, y = _dense_problem()
        with config.whole_fit_mode("off"):
            ref = self._ckpt_fit(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, "off", max_iter)
        got, counters = _counters(
            lambda: self._ckpt_fit(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, "on", max_iter)
        )
        np.testing.assert_array_equal(got[0], ref[0])
        assert got[1] == ref[1] and got[2] == ref[2] == max_iter
        assert counters.get("dispatch.whole_fit.sgd", 0) == 1
        assert counters.get("iteration.host_sync.drain", 0) == 0
        assert counters.get("iteration.host_sync.fit", 0) == 1

    @pytest.mark.parametrize("max_iter", WHOLE_FIT_ITERS)
    def test_checkpointed_sparse_sgd(self, tmp_path, max_iter):
        Xs, y = _sparse_problem()
        with config.whole_fit_mode("off"):
            ref = self._ckpt_fit(
                Xs, y, SPARSE_BINARY_LOGISTIC_LOSS, 12, tmp_path, "soff", max_iter
            )
        got = self._ckpt_fit(
            Xs, y, SPARSE_BINARY_LOGISTIC_LOSS, 12, tmp_path, "son", max_iter
        )
        np.testing.assert_array_equal(got[0], ref[0])
        assert got[2] == ref[2]

    def test_checkpointed_tol_early_stop(self, tmp_path):
        """tol fires mid-fit: the resident program's per-epoch convergence
        check must land on the chunked path's exact stop epoch."""
        X, y = _dense_problem()
        probe = self._ckpt_fit(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, "probe", 10)
        tol = float(probe[1])
        with config.whole_fit_mode("off"):
            ref = self._ckpt_fit(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, "toff", 40, tol)
        assert 0 < ref[2] < 40, "tol must fire mid-run for this test to bite"
        got = self._ckpt_fit(X, y, BINARY_LOGISTIC_LOSS, 8, tmp_path, "ton", 40, tol)
        np.testing.assert_array_equal(got[0], ref[0])
        assert got[1] == ref[1] and got[2] == ref[2]

    @pytest.mark.parametrize("max_iter", WHOLE_FIT_ITERS)
    def test_stream_sgd(self, max_iter):
        X, y = _dense_problem()
        sgd = lambda: SGD(max_iter=max_iter, global_batch_size=100, tol=0.0)
        with config.whole_fit_mode("off"):
            ref = sgd().optimize_stream(
                np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
            )
        got, counters = _counters(
            lambda: sgd().optimize_stream(
                np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
            )
        )
        np.testing.assert_array_equal(got[0], ref[0])
        assert got[1] == ref[1] and got[2] == ref[2] == max_iter
        assert got[3]["wholeFit"] is True
        assert counters.get("dispatch.whole_fit.stream", 0) == 1
        # THE acceptance pin: the whole out-of-core fit is one blocking
        # host<->device sync — one dispatch, one packed readback
        assert counters.get("iteration.host_sync", 0) == 1

    def test_stream_sgd_tol_early_stop(self):
        X, y = _dense_problem()
        with config.whole_fit_mode("off"):
            probe = SGD(max_iter=10, global_batch_size=100, tol=0.0).optimize_stream(
                np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
            )
            tol = float(probe[1])
            ref = SGD(max_iter=40, global_batch_size=100, tol=tol).optimize_stream(
                np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
            )
        assert 0 < ref[2] < 40
        got = SGD(max_iter=40, global_batch_size=100, tol=tol).optimize_stream(
            np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
        )
        np.testing.assert_array_equal(got[0], ref[0])
        assert got[1] == ref[1] and got[2] == ref[2]

    def test_stream_lloyd(self):
        from flink_ml_tpu.models.clustering.kmeans import KMeans
        from flink_ml_tpu.table import StreamTable

        rng = np.random.RandomState(0)
        X = rng.randn(320, 3).astype(np.float64)
        batches = [Table({"features": X[i : i + 64]}) for i in range(0, 320, 64)]
        km = lambda: (
            KMeans().set_k(4).set_seed(11).set_max_iter(7)
        )
        with config.whole_fit_mode("off"):
            ref = km().fit(StreamTable.from_batches(batches))
        got, counters = _counters(
            lambda: km().fit(StreamTable.from_batches(batches))
        )
        np.testing.assert_array_equal(got.centroids, ref.centroids)
        np.testing.assert_array_equal(got.weights, ref.weights)
        assert counters.get("dispatch.whole_fit.lloyd", 0) == 1
        assert counters.get("iteration.host_sync", 0) == 1

    def test_iterate_bounded_whole_fit(self, tmp_path):
        """The generic runtime: fit-end-only snapshot cadence goes
        resident (one dispatch + one drain), bit-identical to chunked."""
        body = TestIterateBoundedChunked._lloyd_body(
            jnp.asarray(np.random.RandomState(0).randn(60, 3).astype(np.float32))
        )
        init = jnp.zeros((4, 3))
        with config.whole_fit_mode("off"):
            ref = iterate_bounded(
                body, init, max_iter=25, tol=1e-4,
                checkpoint_dir=str(tmp_path / "off"), checkpoint_interval=25,
            )
        got, counters = _counters(
            lambda: iterate_bounded(
                body, init, max_iter=25, tol=1e-4,
                checkpoint_dir=str(tmp_path / "on"), checkpoint_interval=25,
            )
        )
        np.testing.assert_array_equal(np.asarray(got.carry), np.asarray(ref.carry))
        assert got.num_epochs == ref.num_epochs
        assert counters.get("dispatch.whole_fit.iterate", 0) == 1
        assert counters.get("iteration.host_sync.drain", 0) == 1


class TestWholeFitFallbacks:
    """Ineligible fits fall back to the chunked path, counted per reason
    (`dispatch.whole_fit_fallback.<reason>`) — and still compute the
    reference result."""

    def test_mid_fit_checkpoint_interval_falls_back(self, tmp_path):
        X, y = _dense_problem()
        sgd = SGD(
            max_iter=12, global_batch_size=100, tol=0.0,
            checkpoint_dir=str(tmp_path / "mid"), checkpoint_key="mid",
            checkpoint_interval=4,
        )
        _, counters = _counters(
            lambda: sgd.optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
        )
        assert counters.get("dispatch.whole_fit_fallback.checkpoint_interval", 0) == 1
        assert counters.get("dispatch.whole_fit.sgd", 0) == 0
        assert counters.get("iteration.host_sync.drain", 0) >= 1

    def test_stream_over_budget_falls_back(self):
        X, y = _dense_problem()
        with config.device_cache_budget(1024):  # stack ≫ 1KB
            got, counters = _counters(
                lambda: SGD(
                    max_iter=6, global_batch_size=100, tol=0.0
                ).optimize_stream(np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS)
            )
        assert counters.get("dispatch.whole_fit_fallback.device_cache_budget", 0) == 1
        assert "wholeFit" not in got[3]
        with config.whole_fit_mode("off"):
            ref = SGD(max_iter=6, global_batch_size=100, tol=0.0).optimize_stream(
                np.zeros(8), _stream_chunks(X, y), BINARY_LOGISTIC_LOSS
            )
        np.testing.assert_array_equal(got[0], ref[0])

    def test_ragged_kmeans_stream_falls_back(self):
        from flink_ml_tpu.models.clustering.kmeans import KMeans
        from flink_ml_tpu.table import StreamTable

        rng = np.random.RandomState(1)
        # 64-row and 200-row batches bucket to different row counts
        batches = [
            Table({"features": rng.randn(rows, 3).astype(np.float64)})
            for rows in (64, 200, 64)
        ]
        km = KMeans().set_k(3).set_seed(5).set_max_iter(4)
        _, counters = _counters(
            lambda: km.fit(StreamTable.from_batches(batches))
        )
        assert counters.get("dispatch.whole_fit_fallback.ragged_batches", 0) == 1
        assert counters.get("dispatch.whole_fit.lloyd", 0) == 0

    def test_listener_falls_back(self):
        from flink_ml_tpu.parallel.iteration import IterationListener

        seen = []

        class Rec(IterationListener):
            def on_epoch_watermark_incremented(self, epoch, carry):
                seen.append(epoch)

        body = lambda c, e: (c + 1.0, jnp.asarray(1.0, jnp.float32))
        _, counters = _counters(
            lambda: iterate_bounded(
                body, jnp.zeros(2), max_iter=3, tol=None, listener=Rec()
            )
        )
        assert seen == [1, 2, 3]
        assert counters.get("dispatch.whole_fit_fallback.listener", 0) == 1

    def test_off_mode_counts_nothing(self, tmp_path):
        X, y = _dense_problem()
        with config.whole_fit_mode("off"):
            _, counters = _counters(
                lambda: SGD(
                    max_iter=6, global_batch_size=100, tol=0.0,
                    checkpoint_dir=str(tmp_path / "off2"), checkpoint_key="o",
                ).optimize(np.zeros(8), X, y, None, BINARY_LOGISTIC_LOSS)
            )
        assert counters.get("dispatch.whole_fit", 0) == 0
        assert counters.get("dispatch.whole_fit_fallback", 0) == 0
