"""Parity + accounting suite for the comm layer (parallel/collectives.py,
parallel/overlap.py): the chunked/ring/sparse reductions and the
overlap-scheduled training loops must be BIT-identical to the eager dense
path — chunking and scheduling change when bytes move, never the result
(the contract docs/performance.md §7 documents, the analogue of the
reference's 32KB AllReduceImpl chunks reassembling to the exact sum)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from flink_ml_tpu import config
from flink_ml_tpu.obs import tracing
from flink_ml_tpu.parallel import collectives as coll
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.parallel import overlap
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
    """all_reduce_sum_chunked == lax.psum, bitwise, for every chunk size,
    ring mode, and shard count."""

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    @pytest.mark.parametrize("chunk_bytes", [1024, 32 * 1024, None])
    @pytest.mark.parametrize("ring", [False, True])
    def test_bit_identical_to_psum(self, ndev, chunk_bytes, ring):
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
            lambda v: coll.all_reduce_sum_chunked(
                _tree(v), chunk_bytes=chunk_bytes, ring=ring
            )
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


class TestOverlapSgdParity:
    """Overlap-scheduled SGD (carry-delayed apply) bit-identical to the
    eager program — coefficients, final loss, stop epoch — wherever the
    reduction is the chunked one; to float rounding where the sparse
    index-value reduction engages (test_sparse)."""

    def _fit(self, mesh, X, y, loss, d, overlap_on, **kw):
        from flink_ml_tpu.ops.optimizer import SGD

        sgd = SGD(collective_overlap=overlap_on, **kw)
        return sgd.optimize(np.zeros(d, np.float32), X, y, None, loss, mesh=mesh)

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    @pytest.mark.parametrize("loss_name", ["binary_logistic", "least_square"])
    def test_dense(self, ndev, loss_name):
        from flink_ml_tpu.ops import losses

        loss = {
            "binary_logistic": losses.BINARY_LOGISTIC_LOSS,
            "least_square": losses.LEAST_SQUARE_LOSS,
        }[loss_name]
        mesh = _mesh(ndev)
        rng = np.random.RandomState(0)
        X = rng.randn(256, 10).astype(np.float32)
        y = (X @ np.linspace(1, -1, 10) > 0).astype(np.float32)
        kw = dict(max_iter=12, global_batch_size=64, tol=0.0, reg=0.05, elastic_net=0.3)
        with mesh_lib.use_mesh(mesh):
            c0, l0, e0 = self._fit(mesh, X, y, loss, 10, False, **kw)
            c1, l1, e1 = self._fit(mesh, X, y, loss, 10, True, **kw)
        np.testing.assert_array_equal(c0, c1)
        assert (l0, e0) == (l1, e1)

    @pytest.mark.parametrize("ndev", [2, 8])
    def test_tol_early_stop(self, ndev):
        from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS

        mesh = _mesh(ndev)
        rng = np.random.RandomState(3)
        X = rng.randn(256, 10).astype(np.float32)
        y = (X @ np.linspace(1, -1, 10) > 0).astype(np.float32)
        kw = dict(max_iter=50, global_batch_size=64, tol=0.4)
        with mesh_lib.use_mesh(mesh):
            c0, l0, e0 = self._fit(mesh, X, y, BINARY_LOGISTIC_LOSS, 10, False, **kw)
            c1, l1, e1 = self._fit(mesh, X, y, BINARY_LOGISTIC_LOSS, 10, True, **kw)
        assert e0 < 50  # the tol stop actually engaged
        np.testing.assert_array_equal(c0, c1)
        assert (l0, e0) == (l1, e1)

    @pytest.mark.parametrize("ndev", [1, 2, 8])
    def test_sparse(self, ndev):
        """Sparse losses: at 1-2 shards the gradient densifies onto the
        chunked path and the fit is bit-identical; at 8 shards the
        per-shard pair bytes beat the threshold and the index-value
        reduction engages, which matches the densified psum to float
        rounding only (TestSparseParity), so ten epochs agree to ~1e-7
        relative, pinned here at 1e-5."""
        from flink_ml_tpu.ops.losses import SPARSE_BINARY_LOGISTIC_LOSS

        mesh = _mesh(ndev)
        dim, n, nnz = 500, 256, 5
        rng = np.random.RandomState(1)
        indices = rng.randint(0, dim, size=(n, nnz)).astype(np.int32)
        indices[::7, -1] = -1  # padded-CSR empty slots
        values = rng.rand(n, nnz).astype(np.float32)
        y = (rng.rand(n) > 0.5).astype(np.float32)
        kw = dict(max_iter=10, global_batch_size=64, tol=0.0)
        with mesh_lib.use_mesh(mesh):
            c0, l0, e0 = self._fit(
                mesh, (indices, values), y, SPARSE_BINARY_LOGISTIC_LOSS, dim, False, **kw
            )
            c1, l1, e1 = self._fit(
                mesh, (indices, values), y, SPARSE_BINARY_LOGISTIC_LOSS, dim, True, **kw
            )
        assert e0 == e1
        if ndev <= 2:
            np.testing.assert_array_equal(c0, c1)
            assert l0 == l1
        else:
            np.testing.assert_allclose(c0, c1, rtol=1e-5, atol=1e-9)
            assert l0 == pytest.approx(l1, rel=1e-6)

    def test_sparse_pairs_route_engages(self, mesh8):
        """The trace-time router picks index-value pairs exactly when the
        pair bytes beat the threshold at the current shard count."""
        X_b = (
            np.zeros((4, 64, 5), np.int32),
            np.zeros((4, 64, 5), np.float32),
        )
        assert overlap.sgd_use_sparse_pairs(X_b, 500, mesh8)  # 8 shards: 320B < 1KB
        assert not overlap.sgd_use_sparse_pairs(X_b, 500, _mesh(2))  # 1280B > 1KB
        assert not overlap.sgd_use_sparse_pairs(X_b, 500, _mesh(1))  # nothing to reduce
        assert not overlap.sgd_use_sparse_pairs(np.zeros((4, 64, 5)), 500, mesh8)  # dense


class TestOverlapKMeans:
    def test_lloyd_bit_identical(self):
        from flink_ml_tpu.models.clustering.kmeans import KMeans
        from flink_ml_tpu.table import Table

        rng = np.random.RandomState(0)
        X = np.concatenate([rng.randn(64, 6) + 3, rng.randn(64, 6) - 3]).astype(np.float64)

        def fit():
            return KMeans().set_k(3).set_seed(2).set_max_iter(7).fit(Table({"features": X}))

        m0 = fit()
        with config.collective_overlap_mode(True):
            m1 = fit()
        np.testing.assert_array_equal(m0.centroids, m1.centroids)
        np.testing.assert_array_equal(m0.weights, m1.weights)


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
