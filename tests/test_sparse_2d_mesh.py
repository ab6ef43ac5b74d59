"""Feature-sharded (data × feature) 2D-mesh sparse training.

The beyond-HBM layout of PAPER §"sparseWideLR": batches shard over the
`data` axis, the coefficient and the SGD optimizer carry shard over the
`model` (feature) axis, gradients reduce over `data` only (SparCML pair
exchange — wire bytes ∝ nnz), and the forward pass all-gathers just the
ACTIVE feature slices over `model`. These tests pin:

1. the 2D mesh constructor + sharding-spec layer (`create_mesh_2d`,
   `data_model_sharding`, host-group alignment),
2. the snapshot host-mapping contract on 2D shards
   (`shard_axis_for_tag` × `host_slice_bounds`),
3. per-axis collective accounting — sparse reduce bytes attributed to
   `data`, activation psums to `model` (satellite: 2-axis accounting),
4. 1D-vs-2D parity (bitwise on a single feature shard; allclose across
   shards, where only the reduction order differs),
5. whole-fit residency: the entire 2D fit is ONE dispatch + ONE packed
   readback,
6. the acceptance: a model whose replicated residency exceeds
   `config.hbm_budget_bytes` trains on the 2D mesh while the replicated
   layout is refused at admission (`HbmBudgetExceeded`),
7. 2D feature-shard checkpoints round-trip through the multi-host
   snapshot coordinator, including elastic resume onto a different host
   count AND a different mesh factorization.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flink_ml_tpu import config
from flink_ml_tpu.ckpt import InjectedFault, coordinator, faults
from flink_ml_tpu.obs import memledger
from flink_ml_tpu.ops.losses import SPARSE_BINARY_LOGISTIC_LOSS
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import collectives
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics


def _sparse_problem(n=96, d=30, nnz=5, seed=0):
    """Ragged padded-CSR rows (-1 padding) + separable {0,1} labels."""
    rng = np.random.default_rng(seed)
    indices = np.full((n, nnz), -1, np.int32)
    values = np.zeros((n, nnz), np.float64)
    for i in range(n):
        k = rng.integers(1, nnz + 1)
        cols = rng.choice(d, size=k, replace=False)
        cols.sort()
        indices[i, :k] = cols
        values[i, :k] = rng.random(k)
    truth = rng.random(d) - 0.5
    dense = np.zeros((n, d))
    np.add.at(dense, (np.arange(n)[:, None], np.clip(indices, 0, d - 1)),
              np.where(indices >= 0, values, 0.0))
    y = (dense @ truth > 0).astype(np.float64)
    return indices, values, y


def _fit(mesh, indices, values, y, d, max_iter=6, **kw):
    kw.setdefault("global_batch_size", 32)
    kw.setdefault("tol", 0.0)
    with mesh_lib.use_mesh(mesh):
        return SGD(max_iter=max_iter, shard_features=True, **kw).optimize(
            np.zeros(d), (indices, values), y, None,
            SPARSE_BINARY_LOGISTIC_LOSS, mesh=mesh,
        )


# ---------------------------------------------------------------------------
# mesh constructor + sharding specs
# ---------------------------------------------------------------------------

class TestCreateMesh2D:
    def test_factorizes_model_innermost(self):
        m = mesh_lib.create_mesh_2d(2)
        assert dict(m.shape) == {"data": 4, "model": 2}
        # model-minor: flat mesh order IS the device order, so contiguous
        # host slabs own whole data rows
        assert list(m.devices.flat) == jax.devices()
        assert mesh_lib.num_model_shards(m) == 2
        assert mesh_lib.num_data_shards(m) == 4

    def test_rejects_non_dividing_model_shards(self):
        with pytest.raises(ValueError, match="does not divide"):
            mesh_lib.create_mesh_2d(3)
        with pytest.raises(ValueError, match="must be >= 1"):
            mesh_lib.create_mesh_2d(0)

    def test_host_alignment_validation(self):
        # 4 hosts x 2 devices, model_shards=2: every slab holds whole rows
        m = mesh_lib.create_mesh_2d(2, num_hosts=4)
        assert dict(m.shape) == {"data": 4, "model": 2}
        # 3 hosts over 8 devices -> slabs of 3/3/2; a 4-wide model row
        # would straddle host boundaries
        with pytest.raises(ValueError, match="straddle"):
            mesh_lib.create_mesh_2d(4, num_hosts=3)

    def test_data_model_sharding_specs(self):
        m2 = mesh_lib.create_mesh_2d(2)
        assert mesh_lib.data_model_sharding(m2).spec == P("data", "model")
        assert mesh_lib.data_model_sharding(m2, ndim=3).spec == P(
            "data", None, "model"
        )
        with pytest.raises(ValueError, match="ndim >= 2"):
            mesh_lib.data_model_sharding(m2, ndim=1)
        # no model axis -> falls back to plain data layout / replication
        m1 = mesh_lib.create_mesh(("data",))
        assert mesh_lib.data_model_sharding(m1).spec == P("data", None)
        assert mesh_lib.model_sharding(m1).spec == P()
        assert mesh_lib.model_sharding(m2).spec == P("model")

    def test_host_groups_own_whole_data_rows(self):
        m = mesh_lib.create_mesh_2d(2)
        groups = mesh_lib.host_groups(m, 4)
        for i, group in enumerate(groups):
            assert group == list(m.devices[i])  # host i == data row i


# ---------------------------------------------------------------------------
# satellite: snapshot host-mapping on 2D shards
# ---------------------------------------------------------------------------

class TestHostMapping2D:
    def test_shard_axis_for_tag_2d(self):
        assert mesh_lib.shard_axis_for_tag("data", 2) == 0
        assert mesh_lib.shard_axis_for_tag("model", 2) == 1
        assert mesh_lib.shard_axis_for_tag("model", 1) == 0
        assert mesh_lib.shard_axis_for_tag("model", 3) == 2
        assert mesh_lib.shard_axis_for_tag("replicated", 2) is None
        assert mesh_lib.shard_axis_for_tag("host", 2) is None
        assert mesh_lib.shard_axis_for_tag("model", 0) is None

    def test_host_slice_bounds_array_split_semantics(self):
        assert mesh_lib.host_slice_bounds(30, 4) == [
            (0, 8), (8, 16), (16, 23), (23, 30)
        ]
        # hosts may outnumber elements: trailing slices are empty
        assert mesh_lib.host_slice_bounds(3, 5) == [
            (0, 1), (1, 2), (2, 3), (3, 3), (3, 3)
        ]
        with pytest.raises(ValueError):
            mesh_lib.host_slice_bounds(8, 0)

    def test_model_tag_slices_reassemble_2d_leaf(self):
        """A rank-2 model-tagged leaf (e.g. a future multi-class coeff
        matrix) splits along its TRAILING dim; concatenating every host's
        slice along `shard_axis_for_tag` reconstructs the array exactly."""
        arr = np.arange(6 * 30, dtype=np.float32).reshape(6, 30)
        axis = mesh_lib.shard_axis_for_tag("model", arr.ndim)
        assert axis == 1
        parts = [
            arr.take(range(lo, hi), axis=axis)
            for lo, hi in mesh_lib.host_slice_bounds(arr.shape[axis], 3)
        ]
        np.testing.assert_array_equal(np.concatenate(parts, axis=axis), arr)

    def test_data_tag_slices_reassemble_leading_axis(self):
        arr = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
        axis = mesh_lib.shard_axis_for_tag("data", arr.ndim)
        assert axis == 0
        parts = [
            arr[lo:hi]
            for lo, hi in mesh_lib.host_slice_bounds(arr.shape[0], 4)
        ]
        np.testing.assert_array_equal(np.concatenate(parts, axis=0), arr)


# ---------------------------------------------------------------------------
# satellite: per-axis collective accounting on a 2-axis mesh
# ---------------------------------------------------------------------------

class TestTwoAxisAccounting:
    def test_sparse_bytes_attribute_to_data_axis_only(self, mesh_2d):
        """One program with a sparse pair-exchange over `data` and a dense
        psum over `model`: the wire accounting must keep the axes apart —
        sparse counters live under `collective.axis.data.*`, the model
        axis sees only its dense bytes, and `axis_wire_bytes` splits the
        delta per axis."""
        dim = 64

        def body(idx, val):
            g = collectives.sparse_all_reduce_sum(
                idx, val, dim, collectives.DATA_AXIS
            )
            s = collectives.all_reduce_sum(jnp.sum(g), collectives.MODEL_AXIS)
            return g + s

        mapped = collectives.shard_map_over(
            mesh_2d, (P(), P()), P(), fn=body
        )
        idx = jnp.arange(4, dtype=jnp.int32)
        val = jnp.ones(4, jnp.float32)
        before = metrics.snapshot()
        np.asarray(jax.jit(mapped)(idx, val))  # trace-time accounting
        delta = metrics.snapshot_delta(before, metrics.snapshot())

        counters = delta["counters"]
        assert counters["collective.axis.data.sparse.bytes"] > 0
        assert counters["collective.axis.data.bytes"] > 0
        assert counters["collective.axis.model.bytes"] > 0
        # nothing sparse ever ran on the model axis
        assert not any(
            name.startswith("collective.axis.model.sparse")
            for name in counters
        )
        wire = collectives.axis_wire_bytes(delta)
        assert set(wire) >= {"data", "model"}
        assert wire["data"] == counters["collective.axis.data.bytes"]
        assert wire["model"] == counters["collective.axis.model.bytes"]
        # pair exchange beats the dense-equivalent it replaced
        assert (
            counters["collective.axis.data.sparse.bytes"]
            < counters["collective.axis.data.sparse.dense_equiv_bytes"]
        )
        ratio = delta["gauges"].get("collective.sparse_ratio.data")
        assert ratio is not None and 0.0 < ratio < 1.0
        assert "collective.sparse_ratio.model" not in delta["gauges"]

    def test_2d_fit_routes_traffic_to_both_axes(self, mesh_2d):
        """End-to-end: a 2D fit's trace must account model-axis traffic
        (active-feature assembly) separately from data-axis traffic
        (gradient + loss reduces)."""
        from flink_ml_tpu.parallel import sgd2d

        sgd2d.clear_program_cache()  # force a fresh trace to count
        indices, values, y = _sparse_problem(n=64, d=16, nnz=4, seed=2)
        before = metrics.snapshot()
        _fit(mesh_2d, indices, values, y, 16, max_iter=2)
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        wire = collectives.axis_wire_bytes(delta)
        assert wire.get("data", 0) > 0
        assert wire.get("model", 0) > 0


# ---------------------------------------------------------------------------
# 1D-vs-2D parity
# ---------------------------------------------------------------------------

class TestParity:
    def test_single_feature_shard_is_bitwise_equal(self):
        """On an (8, 1) mesh the 2D program owns every feature, so the
        active-feature assembly is the identity and the data-axis sparse
        reduce is the SAME association as the GSPMD reference — the
        coefficients must agree BITWISE, not merely closely."""
        m = mesh_lib.create_mesh_2d(1)  # (data=8, model=1)
        indices, values, y = _sparse_problem(n=128, d=30, seed=7)
        with config.sparse_2d_mode("off"):
            ref = _fit(m, indices, values, y, 30)
        auto = _fit(m, indices, values, y, 30)
        np.testing.assert_array_equal(np.asarray(auto[0]), np.asarray(ref[0]))
        assert auto[2] == ref[2] == 6

    def test_multi_shard_allclose(self, mesh_2d):
        """Across real feature shards only the REDUCTION ORDER differs
        (per-shard scatter partials fold in a different association), so
        the contract is allclose, not bit equality — the same caveat as
        docs/performance.md "2D mesh"."""
        indices, values, y = _sparse_problem(n=128, d=30, seed=7)
        with config.sparse_2d_mode("off"):
            ref = _fit(mesh_2d, indices, values, y, 30)
        auto = _fit(mesh_2d, indices, values, y, 30)
        np.testing.assert_allclose(
            np.asarray(auto[0]), np.asarray(ref[0]), rtol=3e-5, atol=3e-6
        )
        assert auto[2] == ref[2] == 6

    def test_mode_off_disables_2d_routing(self, mesh_2d):
        sgd = SGD(max_iter=2, shard_features=True)
        with config.sparse_2d_mode("off"):
            assert not sgd._use_2d(mesh_2d, SPARSE_BINARY_LOGISTIC_LOSS)
        assert sgd._use_2d(mesh_2d, SPARSE_BINARY_LOGISTIC_LOSS)
        # dense losses never route 2D
        from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS

        assert not sgd._use_2d(mesh_2d, BINARY_LOGISTIC_LOSS)


# ---------------------------------------------------------------------------
# whole-fit residency: ONE dispatch, ONE readback
# ---------------------------------------------------------------------------

class TestWholeFit2D:
    def test_2d_fit_is_one_dispatch(self, mesh_2d):
        indices, values, y = _sparse_problem(n=128, d=24, nnz=4, seed=3)
        before = metrics.snapshot()
        coeff, _, epochs = _fit(mesh_2d, indices, values, y, 24, max_iter=5)
        delta = metrics.snapshot_delta(before, metrics.snapshot())
        assert delta["counters"]["fit.launch.n"] == 1
        assert epochs == 5
        assert coeff.shape == (24,)


# ---------------------------------------------------------------------------
# acceptance: beyond-HBM model trains only feature-sharded
# ---------------------------------------------------------------------------

class TestBeyondBudget:
    def test_wide_model_trains_2d_but_not_replicated(self):
        """d=200k f32: replicated coeff staging alone is 800 KB. Under a
        600 KB budget the (2, 4) mesh admits 2 × 200 KB per-shard carries
        and trains; the replicated layout is refused at admission before
        any dispatch — the HbmBudgetExceeded contract of ISSUE 17."""
        d = 200_000
        rng = np.random.default_rng(11)
        n, nnz = 256, 4
        indices = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
        values = rng.random((n, nnz))
        y = rng.integers(0, 2, size=n).astype(np.float64)

        memledger.reset()
        with config.hbm_budget_mode(3 * d):  # 600 KB, < one f32 replica
            m2 = mesh_lib.create_mesh_2d(4)  # (data=2, model=4)
            coeff, _, epochs = _fit(
                m2, indices, values, y, d, max_iter=2, global_batch_size=128
            )
            assert epochs == 2 and coeff.shape == (d,)
            assert np.all(np.isfinite(coeff))
            # per-shard residency is what the ledger sees: both sharded
            # carries fit where ONE replicated copy would not
            assert memledger.live_bytes("optimizer") <= 3 * d

            memledger.reset()
            m1 = mesh_lib.create_mesh(("data",))  # no model axis: replicated
            with pytest.raises(memledger.HbmBudgetExceeded):
                _fit(m1, indices, values, y, d, max_iter=2,
                     global_batch_size=128)
        memledger.reset()

    def test_ledger_reads_one_shard_of_a_feature_sharded_carry(self):
        """What the ledger holds for a staged carry is ONE device's share:
        on a (2, 4) mesh a feature-sharded carry costs a quarter of the
        replicated one, never the sum across shards."""
        from flink_ml_tpu.parallel import prefetch as h2d

        dim, model_shards = 40_000, 4

        def staged_bytes(mesh):
            memledger.reset()
            staged = h2d.stage_to_device(
                np.zeros(dim, np.float32), mesh_lib.model_sharding(mesh), category="optimizer"
            )
            live = memledger.live_bytes("optimizer")
            del staged
            memledger.reset()
            return live

        per_shard = staged_bytes(mesh_lib.create_mesh_2d(model_shards))
        replicated = staged_bytes(mesh_lib.create_mesh(("data",)))
        assert replicated == 4 * dim
        assert per_shard * model_shards == replicated


# ---------------------------------------------------------------------------
# 2D checkpoints through the multi-host coordinator + elastic resume
# ---------------------------------------------------------------------------

class TestCheckpoint2D:
    @pytest.mark.parametrize(
        "resume_shape,resume_hosts",
        [((2, 2), 2),   # fewer hosts, same model factorization
         ((2, 4), 4)],  # same device count, model axis refactored 2 -> 4
    )
    def test_elastic_sharded_resume_parity_with_single_file(
        self, tmp_path, resume_shape, resume_hosts
    ):
        """A 2D fit killed mid-run with SHARDED (4-host) snapshots resumes
        on a different mesh — fewer hosts or a re-factored model axis —
        and lands on the exact coefficients of the same kill/resume
        through the single-file snapshot path: the sharded transport of
        feature-sharded carries is lossless end to end."""
        indices, values, y = _sparse_problem(n=128, d=24, nnz=4, seed=5)

        def fit_on(shape, ckpt, max_iter):
            nd, nm = shape
            mesh = mesh_lib.create_mesh(
                ("data", "model"), shape=shape,
                devices=jax.devices()[: nd * nm],
            )
            return _fit(
                mesh, indices, values, y, 24, max_iter=max_iter,
                checkpoint_dir=ckpt, checkpoint_key="el2d",
            )

        single = str(tmp_path / "single")
        with faults.inject("chunk", after=6):
            with pytest.raises(InjectedFault):
                fit_on((4, 2), single, 12)
        single_coeff, _, single_epochs = fit_on(resume_shape, single, 12)

        sharded = str(tmp_path / "sharded")
        with config.snapshot_hosts_mode(4):
            with faults.inject("chunk", after=6):
                with pytest.raises(InjectedFault):
                    fit_on((4, 2), sharded, 12)
            assert coordinator.has_sharded(sharded, "el2d")
        with config.snapshot_hosts_mode(resume_hosts):
            sharded_coeff, _, sharded_epochs = fit_on(resume_shape, sharded, 12)

        assert single_epochs == sharded_epochs == 12
        np.testing.assert_array_equal(
            np.asarray(sharded_coeff), np.asarray(single_coeff)
        )

    def test_checkpointed_2d_matches_uncheckpointed(self, tmp_path, mesh_2d):
        """The chunked 2D checkpoint path must reproduce the whole-fit 2D
        coefficients exactly — chunking is a dispatch schedule, not a
        different optimization."""
        indices, values, y = _sparse_problem(n=96, d=16, nnz=4, seed=9)
        plain = _fit(mesh_2d, indices, values, y, 16, max_iter=4)
        ckpt = _fit(
            mesh_2d, indices, values, y, 16, max_iter=4,
            checkpoint_dir=str(tmp_path), checkpoint_key="c2d",
            checkpoint_interval=2,
        )
        np.testing.assert_array_equal(
            np.asarray(ckpt[0]), np.asarray(plain[0])
        )
        assert ckpt[2] == plain[2] == 4
