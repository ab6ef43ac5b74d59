"""Parity + accounting suite for the comm layer (parallel/collectives.py):
the chunked reduction must be BIT-identical to the monolithic psum —
chunking changes when bytes move, never the result (the contract
docs/performance.md §7 documents, the analogue of the reference's 32KB
AllReduceImpl chunks reassembling to the exact sum) — and the sparse one
equal to float rounding. A fit on several data shards, whose gradient XLA
reduces, must agree with the fit on one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.obs import tracing
from flink_ml_tpu.ops import optimizer
from flink_ml_tpu.parallel import collectives as coll
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics


def _mesh(n):
    return mesh_lib.create_mesh(("data",), devices=jax.devices()[:n])


def _tree(v):
    """Mixed pytree: multi-dim leaf + a nested (pair) tuple — exercises
    dtype grouping, flatten/unflatten, and the nested-leaf accounting."""
    return {"a": v[:, :1000].reshape(-1, 10, 100), "b": (v[:, 1000:1003], v[:, 1003:])}


def _flat(tree, rows):
    return np.concatenate(
        [np.asarray(leaf).reshape(rows, -1) for leaf in jax.tree_util.tree_leaves(tree)],
        axis=1,
    )


class TestChunkedParity:
    """all_reduce_sum_chunked == lax.psum, bitwise, for every chunk size
    and shard count."""

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    @pytest.mark.parametrize("chunk_bytes", [1024, 32 * 1024, None])
    def test_bit_identical_to_psum(self, ndev, chunk_bytes):
        mesh = _mesh(ndev)
        rng = np.random.default_rng(0)
        # wide dynamic range so any reassociation of the sum would show
        x = (
            rng.standard_normal((ndev, 4096)).astype(np.float32)
            * np.logspace(-6, 6, 4096, dtype=np.float32)
        )

        def run(fn):
            f = coll.shard_map_over(
                mesh, in_specs=P("data", None), out_specs=P("data", None)
            )(fn)
            return jax.jit(f)(x)

        whole = np.asarray(run(lambda v: lax.psum(v, "data")))
        chunked = run(
            lambda v: coll.all_reduce_sum_chunked(_tree(v), chunk_bytes=chunk_bytes)
        )
        np.testing.assert_array_equal(_flat(chunked, ndev), _flat(_tree(whole), ndev))

    def test_bucket_count_follows_chunk_bytes(self, mesh8):
        """1KB buckets over a 16KB payload really decompose (≥16 buckets),
        and the accounted chunk count reports the decomposition."""
        x = np.ones((8, 4096), np.float32)
        before = metrics.snapshot()
        f = coll.shard_map_over(mesh8, in_specs=P("data", None), out_specs=P("data", None))(
            lambda v: coll.all_reduce_sum_chunked(v, chunk_bytes=1024)
        )
        jax.block_until_ready(jax.jit(f)(x))
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        assert delta["counters"].get("collective.chunked.chunks", 0) >= 16
        assert delta["counters"].get("collective.chunked.bytes", 0) == 4096 * 4

    def test_heterogeneous_dtypes(self, mesh8):
        """f32 + i32 leaves group into per-dtype buckets and still match."""
        xf = np.arange(8 * 64, dtype=np.float32).reshape(8, 64)
        xi = np.arange(8 * 32, dtype=np.int32).reshape(8, 32)
        f = coll.shard_map_over(
            mesh8,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
        )(lambda a, b: coll.all_reduce_sum_chunked((a, b), chunk_bytes=128))
        out_f, out_i = jax.jit(f)(xf, xi)
        np.testing.assert_array_equal(np.asarray(out_f), np.tile(xf.sum(0), (8, 1)))
        np.testing.assert_array_equal(np.asarray(out_i), np.tile(xi.sum(0), (8, 1)))


class TestSparseParity:
    """sparse_all_reduce_sum == psum of the densified operand — the same
    addends, dropped padding indices included — to float rounding. Not
    bitwise across shards: XLA folds each shard's scatter-add into the
    running cross-shard sum, so where one shard holds duplicate indices
    (a + b) + acc becomes (acc + a) + b, a 1-2 ulp difference on this
    XLA's CPU backend (jax 0.9.0). One shard is the same expression on
    both sides and stays exact."""

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    def test_matches_densified_psum(self, ndev):
        mesh = _mesh(ndev)
        dim, m = 512, 64
        rng = np.random.default_rng(1)
        idx = rng.integers(0, dim, size=(ndev, m)).astype(np.int32)
        idx[:, -3:] = -1  # padding entries must drop on both paths
        val = rng.standard_normal((ndev, m)).astype(np.float32)
        in_specs = (P("data", None), P("data", None))

        dense = coll.shard_map_over(mesh, in_specs=in_specs, out_specs=P())(
            lambda i, v: lax.psum(
                jnp.zeros((dim,), jnp.float32).at[i[0]].add(v[0], mode="drop"), "data"
            )
        )
        sparse = coll.shard_map_over(mesh, in_specs=in_specs, out_specs=P())(
            lambda i, v: coll.sparse_all_reduce_sum(i[0], v[0], dim)
        )
        got = np.asarray(jax.jit(sparse)(idx, val))
        want = np.asarray(jax.jit(dense)(idx, val))
        if ndev == 1:
            np.testing.assert_array_equal(got, want)
        else:  # sums of O(1) normals: a few f32 ulp
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_wire_bytes_scale_with_nnz(self, mesh8):
        """The acceptance shape (dim=1M, nnz=39): traced sparse pair bytes
        must sit ≥10x below the dense-equivalent psum payload."""
        dim, rows, nnz = 1_000_000, 128, 39
        idx = np.zeros((8, rows * nnz), np.int32)
        val = np.zeros((8, rows * nnz), np.float32)
        before = metrics.snapshot()
        f = coll.shard_map_over(
            mesh8, in_specs=(P("data", None), P("data", None)), out_specs=P()
        )(lambda i, v: coll.sparse_all_reduce_sum(i[0], v[0], dim))
        jax.block_until_ready(jax.jit(f)(idx, val))
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        sparse_bytes = delta["counters"]["collective.sparse.bytes"]
        dense_equiv = delta["counters"]["collective.sparse.dense_equiv_bytes"]
        assert sparse_bytes * 10 <= dense_equiv
        assert 0 < metrics.snapshot()["gauges"]["collective.sparse_ratio"] < 1

    def test_threshold_routing(self):
        # sparseWideLR shape: pairs win by far
        assert coll.sparse_reduce_wins(128 * 39, 1_000_000, itemsize=4)
        # dense-ish gradient: pairs would exceed the dense payload
        assert not coll.sparse_reduce_wins(900, 1000, itemsize=4)


class TestShardedParity:
    """A fit on several data shards agrees with the fit on one. With more
    epochs than the table has batches the walk turns it away, so it takes
    GSPMD `_sgd_train`, whose gradient XLA reduces with a psum; the fit on
    one shard is `_sgd_train_flat`. The psum sums the shards' partial
    gradients in another order than one shard's contraction, so the two
    agree to float rounding."""

    def _fit(self, ndev, X, y, loss, d, monkeypatch, **kw):
        calls = []
        train = optimizer._sgd_train

        def spy(*args, **kwargs):
            calls.append(1)
            return train(*args, **kwargs)

        monkeypatch.setattr(optimizer, "_sgd_train", spy)
        mesh = _mesh(ndev)
        with mesh_lib.use_mesh(mesh):
            fitted = optimizer.SGD(**kw).optimize(
                np.zeros(d, np.float32), X, y, None, loss, mesh=mesh
            )
        assert len(calls) == int(ndev > 1)
        return fitted

    def _agree(self, ndev, X, y, loss, d, monkeypatch, **kw):
        c1, l1, e1 = self._fit(1, X, y, loss, d, monkeypatch, **kw)
        cn, ln, en = self._fit(ndev, X, y, loss, d, monkeypatch, **kw)
        np.testing.assert_allclose(cn, c1, rtol=1e-5)
        assert en == e1
        assert ln == pytest.approx(l1, rel=1e-6)
        return e1

    @staticmethod
    def _dense(seed):
        rng = np.random.RandomState(seed)
        X = rng.randn(256, 10).astype(np.float32)
        return X, (X @ np.linspace(1, -1, 10) > 0).astype(np.float32)

    @pytest.mark.parametrize("ndev", [2, 4, 8])
    @pytest.mark.parametrize("loss_name", ["binary_logistic", "least_square", "hinge"])
    def test_dense(self, ndev, loss_name, monkeypatch):
        from flink_ml_tpu.ops import losses

        loss = {
            "binary_logistic": losses.BINARY_LOGISTIC_LOSS,
            "least_square": losses.LEAST_SQUARE_LOSS,
            "hinge": losses.HINGE_LOSS,
        }[loss_name]
        X, y = self._dense(0)
        kw = dict(max_iter=12, global_batch_size=64, tol=0.0, reg=0.05, elastic_net=0.3)
        assert self._agree(ndev, X, y, loss, 10, monkeypatch, **kw) == 12

    @pytest.mark.parametrize("ndev", [2, 4, 8])
    def test_tol_early_stop(self, ndev, monkeypatch):
        from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS

        X, y = self._dense(3)
        kw = dict(max_iter=50, global_batch_size=64, tol=0.4)
        assert self._agree(ndev, X, y, BINARY_LOGISTIC_LOSS, 10, monkeypatch, **kw) < 50

    @pytest.mark.parametrize("ndev", [2, 4, 8])
    def test_sparse(self, ndev, monkeypatch):
        """Padded-CSR rows: each shard scatters its rows' gradient, and the
        psum adds the shards' vectors."""
        from flink_ml_tpu.ops.losses import SPARSE_BINARY_LOGISTIC_LOSS

        dim, n, nnz = 500, 256, 5
        rng = np.random.RandomState(1)
        indices = rng.randint(0, dim, size=(n, nnz)).astype(np.int32)
        indices[::7, -1] = -1  # padded-CSR empty slots
        values = rng.rand(n, nnz).astype(np.float32)
        y = (rng.rand(n) > 0.5).astype(np.float32)
        kw = dict(max_iter=10, global_batch_size=64, tol=0.0)
        self._agree(ndev, (indices, values), y, SPARSE_BINARY_LOGISTIC_LOSS, dim, monkeypatch, **kw)

    @pytest.mark.parametrize("ndev", [4, 8])
    def test_kmeans(self, ndev):
        """Lloyd's loop over the rows split on several shards: each shard's
        partial sums and counts, reduced, then the one update. Counts are
        exact; the centroids agree with one shard's to float rounding."""
        from flink_ml_tpu.models.clustering.kmeans import KMeans
        from flink_ml_tpu.table import Table

        rng = np.random.RandomState(0)
        X = np.concatenate([rng.randn(64, 6) + 3, rng.randn(64, 6) - 3]).astype(np.float64)

        def fit(n):
            with mesh_lib.use_mesh(_mesh(n)):
                return KMeans().set_k(3).set_seed(2).set_max_iter(7).fit(Table({"features": X}))

        one, many = fit(1), fit(ndev)
        np.testing.assert_array_equal(many.weights, one.weights)
        np.testing.assert_allclose(many.centroids, one.centroids, rtol=0, atol=1e-5)


class TestHostReduceCompileOnce:
    def test_compiles_once_per_mesh_shape_dtype(self, mesh8):
        """host_all_reduce_sum's jitted reducer is cached per (mesh, shape,
        dtype): repeated same-shape reduces re-enter the same executable
        (the round-5 bug rebuilt the closure per call and recompiled every
        time — ~10ms of XLA work per reduce in the host-driven loops)."""
        tracing.install_jax_hooks()
        shape = (37,)  # unlikely to collide with another test's executable
        partials = [np.full(shape, float(i), np.float32) for i in range(8)]
        out = coll.host_all_reduce_sum(mesh8, partials)  # warm: one compile
        np.testing.assert_array_equal(np.asarray(out), np.full(shape, 28.0))

        before = metrics.get_counter("jit.compiles")
        for _ in range(5):
            coll.host_all_reduce_sum(mesh8, partials)
        assert metrics.get_counter("jit.compiles") == before  # zero recompiles

        key = (mesh8, (8,) + shape, np.dtype(np.float32).str)
        assert key in coll._HOST_REDUCE_CACHE
        # a different shape is a different executable, not a cache hit
        coll.host_all_reduce_sum(mesh8, [p[:5] for p in partials])
        assert (mesh8, (8, 5), np.dtype(np.float32).str) in coll._HOST_REDUCE_CACHE


class TestAccounting:
    def test_payload_bytes_counts_nested_pairs(self):
        """A sparse (indices, values) tuple nested inside a gradient pytree
        contributes BOTH leaves (the round-5 `_account` undercounted these
        to zero: tree_leaves treated the inner tuple as one non-array)."""
        tree = {
            "dense": np.zeros((10,), np.float32),  # 40B
            "sparse": (np.zeros((6,), np.int32), np.zeros((6,), np.float32)),  # 48B
        }
        assert coll.payload_bytes(tree) == 40 + 48
        assert coll.payload_bytes([tree, tree]) == 2 * (40 + 48)

    def test_sparse_ratio_gauge(self):
        before_s = metrics.get_counter("collective.sparse.bytes")
        before_d = metrics.get_counter("collective.sparse.dense_equiv_bytes")
        tracing.account_collective(
            "sparse_allreduce", 100, 1, "data", dense_equiv_bytes=1000
        )
        assert metrics.get_counter("collective.sparse.bytes") == before_s + 100
        assert (
            metrics.get_counter("collective.sparse.dense_equiv_bytes")
            == before_d + 1000
        )
        ratio = metrics.snapshot()["gauges"]["collective.sparse_ratio"]
        assert ratio == (before_s + 100) / (before_d + 1000)
