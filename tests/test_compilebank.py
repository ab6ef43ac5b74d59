"""AOT program bank (flink_ml_tpu/compilebank.py, ISSUE 20).

Pins the warm-load contract (a bank hit runs a deserialized executable —
zero traces, zero backend compiles, bit-identical outputs), the refusal
semantics (corrupt entries, stale digests, and fingerprint mismatches
are refused with a loud warning and a `bank.refused` tick, never a
crash), the bank x persistent-XLA-cache interplay, the keyed_jit LRU
bound (eviction must never be observable in results), and the serving
warmup -> bank-hit path.
"""

import json
import logging
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import compilebank, config
from flink_ml_tpu.utils import metrics
from flink_ml_tpu.utils.lazyjit import keyed_jit, lazy_jit


def _counter_delta(before, key):
    after = metrics.snapshot()
    return metrics.snapshot_delta(before, after)["counters"].get(key, 0.0)


def _affine(x, scale):
    return x * scale + 1.0


affine_kernel = lazy_jit(_affine, static_argnames=("scale",))


def _make_power(p):
    def power(x):
        return jnp.sum(x ** p)

    return power


power_kernel = keyed_jit(_make_power)


X = np.linspace(-2.0, 3.0, 32, dtype=np.float32)


# ---------------------------------------------------------------------------
# warm-load round trip
# ---------------------------------------------------------------------------

def test_miss_backfills_then_fresh_bank_hits_without_trace(tmp_path):
    bank_dir = str(tmp_path / "bank")
    fresh = np.asarray(affine_kernel(X, scale=2.0))

    with config.program_bank_mode(bank_dir):
        before = metrics.snapshot()
        first = np.asarray(affine_kernel(X, scale=2.0))
        assert _counter_delta(before, "bank.misses") == 1.0
    assert os.path.exists(os.path.join(bank_dir, compilebank.MANIFEST))

    # a NEW bank scope warm-loads the serialized executable from disk:
    # the hit must not trace and must be bit-identical to the fresh run
    with config.program_bank_mode(bank_dir):
        before = metrics.snapshot()
        again = np.asarray(affine_kernel(X, scale=2.0))
        assert _counter_delta(before, "jit.traces") == 0.0
        assert _counter_delta(before, "bank.hits") == 1.0
        assert _counter_delta(before, "jit.bankLoads") == 1.0
        bank = compilebank.active_bank()
        assert bank is not None and bank.stats()["entries"] == 1.0
    assert fresh.tobytes() == first.tobytes() == again.tobytes()


def test_distinct_shapes_and_statics_are_distinct_entries(tmp_path):
    bank_dir = str(tmp_path / "bank")
    with config.program_bank_mode(bank_dir):
        affine_kernel(X, scale=2.0)
        affine_kernel(X, scale=3.0)  # static differs -> new signature
        affine_kernel(X[:8], scale=2.0)  # shape differs -> new signature
        bank = compilebank.active_bank()
        assert bank.stats()["entries"] == 3.0
    with open(os.path.join(bank_dir, compilebank.MANIFEST)) as f:
        manifest = json.load(f)
    assert len(manifest["entries"]) == 3


def test_bank_with_persistent_xla_cache(tmp_path):
    """Both persistence tiers on at once (the production configuration):
    the bank must populate, warm-load, and hit exactly as it does alone,
    and outputs must stay bit-identical."""
    prev_cache = config.compilation_cache_dir
    config.enable_compilation_cache(str(tmp_path / "xla-cache"))
    try:
        bank_dir = str(tmp_path / "bank")
        with config.program_bank_mode(bank_dir):
            first = np.asarray(affine_kernel(X, scale=7.0))
        with config.program_bank_mode(bank_dir):
            before = metrics.snapshot()
            again = np.asarray(affine_kernel(X, scale=7.0))
            assert _counter_delta(before, "bank.hits") == 1.0
            assert _counter_delta(before, "jit.traces") == 0.0
        assert first.tobytes() == again.tobytes()
    finally:
        config.compilation_cache_dir = prev_cache
        # the jax-level cache switch is process-global: turn it back off
        # so later tests do not compile through this test's directory
        # (XLA:CPU cannot re-serialize an executable it loaded from the
        # persistent cache, which a later bank back-fill would trip on)
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# refusal semantics: corrupt / stale / mismatched banks never crash
# ---------------------------------------------------------------------------

def _populated_bank(tmp_path):
    bank_dir = str(tmp_path / "bank")
    with config.program_bank_mode(bank_dir):
        affine_kernel(X, scale=5.0)
    return bank_dir


def test_corrupt_entry_refused_with_warning_not_crash(tmp_path, caplog):
    bank_dir = _populated_bank(tmp_path)
    manifest = json.load(open(os.path.join(bank_dir, compilebank.MANIFEST)))
    (record,) = manifest["entries"].values()
    entry_path = os.path.join(bank_dir, record["file"])
    raw = open(entry_path, "rb").read()
    with open(entry_path, "wb") as f:  # flip payload bytes: digest mismatch
        f.write(raw[:-4] + b"\x00\x00\x00\x00")

    with caplog.at_level(logging.WARNING, logger="flink_ml_tpu.compilebank"):
        with config.program_bank_mode(bank_dir):
            before = metrics.snapshot()
            out = np.asarray(affine_kernel(X, scale=5.0))
            assert _counter_delta(before, "bank.refused") >= 1.0
            assert _counter_delta(before, "jit.bankLoads") == 0.0
    assert any("digest mismatch" in r.message for r in caplog.records)
    assert out.tobytes() == np.asarray(affine_kernel(X, scale=5.0)).tobytes()


def test_undeserializable_payload_refused(tmp_path, caplog):
    bank_dir = _populated_bank(tmp_path)
    manifest_path = os.path.join(bank_dir, compilebank.MANIFEST)
    manifest = json.load(open(manifest_path))
    (sig,) = manifest["entries"]
    record = manifest["entries"][sig]
    garbage = pickle.dumps({"not": "an executable"})
    with open(os.path.join(bank_dir, record["file"]), "wb") as f:
        f.write(garbage)
    import hashlib

    record["sha256"] = hashlib.sha256(garbage).hexdigest()  # digest is "valid"
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    with caplog.at_level(logging.WARNING, logger="flink_ml_tpu.compilebank"):
        with config.program_bank_mode(bank_dir):
            before = metrics.snapshot()
            out = np.asarray(affine_kernel(X, scale=5.0))
            assert _counter_delta(before, "bank.refused") >= 1.0
    assert any("deserialize" in r.message for r in caplog.records)
    assert np.isfinite(out).all()


def test_fingerprint_mismatch_refuses_whole_bank(tmp_path, caplog):
    bank_dir = _populated_bank(tmp_path)
    manifest_path = os.path.join(bank_dir, compilebank.MANIFEST)
    manifest = json.load(open(manifest_path))
    manifest["fingerprint"]["jax"] = "0.0.0"
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    with caplog.at_level(logging.WARNING, logger="flink_ml_tpu.compilebank"):
        with config.program_bank_mode(bank_dir):
            before = metrics.snapshot()
            out = np.asarray(affine_kernel(X, scale=5.0))
            assert _counter_delta(before, "jit.bankLoads") == 0.0
            assert _counter_delta(before, "bank.refused") >= 1.0
    assert any("fingerprint mismatch" in r.message for r in caplog.records)
    assert np.isfinite(out).all()


def test_torn_manifest_refused(tmp_path, caplog):
    bank_dir = _populated_bank(tmp_path)
    with open(os.path.join(bank_dir, compilebank.MANIFEST), "w") as f:
        f.write('{"fingerprint": {"jax"')  # mid-write truncation
    with caplog.at_level(logging.WARNING, logger="flink_ml_tpu.compilebank"):
        with config.program_bank_mode(bank_dir):
            out = np.asarray(affine_kernel(X, scale=5.0))
    assert any("unreadable manifest" in r.message for r in caplog.records)
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# signature edges
# ---------------------------------------------------------------------------

def test_unbankable_static_falls_through(tmp_path):
    class Opaque:  # no stable cross-process token
        def __hash__(self):
            return id(self)

    wobbly = lazy_jit(lambda x, tag: x + 1.0, static_argnames=("tag",))
    with config.program_bank_mode(str(tmp_path / "bank")):
        before = metrics.snapshot()
        out = np.asarray(wobbly(X, tag=Opaque()))
        assert _counter_delta(before, "bank.unbankable") == 1.0
        assert _counter_delta(before, "bank.misses") == 0.0
    np.testing.assert_allclose(out, X + 1.0)


def test_nested_trace_falls_through_to_inline(tmp_path):
    inner = lazy_jit(lambda x: x * 2.0)

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    with config.program_bank_mode(str(tmp_path / "bank")):
        before = metrics.snapshot()
        out = np.asarray(outer(jnp.asarray(X)))
        assert _counter_delta(before, "bank.nestedTrace") >= 1.0
    np.testing.assert_allclose(out, X * 2.0 + 1.0, rtol=1e-6)


def test_extras_roundtrip_across_warm_load(tmp_path):
    """Trace-time side state (FusedSegment guard messages ride this)
    persists with the entry and replays on a warm-load hit."""
    bank_dir = str(tmp_path / "bank")
    seen = []

    def run(x):
        return x + 1.0

    traced = lambda x: run(x)  # noqa: E731
    with config.program_bank_mode(bank_dir):
        bank = compilebank.active_bank()
        handled, _ = compilebank.banked_call(
            bank, "test.extras", traced, (jnp.asarray(X),), {}, {},
            extras_fn=lambda: {"guards": ["g1", "g2"]},
            on_extras=lambda e: seen.append(e),
        )
        assert handled
    with config.program_bank_mode(bank_dir):
        bank = compilebank.active_bank()
        handled, out = compilebank.banked_call(
            bank, "test.extras", traced, (jnp.asarray(X),), {}, {},
            on_extras=lambda e: seen.append(e),
        )
        assert handled
    assert seen == [{"guards": ["g1", "g2"]}, {"guards": ["g1", "g2"]}]
    np.testing.assert_allclose(np.asarray(out), X + 1.0)


# ---------------------------------------------------------------------------
# keyed_jit LRU bound (satellite: eviction must never be observable)
# ---------------------------------------------------------------------------

def test_keyed_jit_lru_evicts_and_reconstructs_identically():
    with config.kernel_cache_limit(2):
        before = metrics.snapshot()
        first = {p: np.asarray(power_kernel(p)(jnp.asarray(X))) for p in (1, 2, 3, 4)}
        evicted = _counter_delta(before, "jit.kernelCacheEvict")
        assert evicted >= 2.0
        assert metrics.snapshot()["gauges"]["jit.kernelCacheSize"] <= 2.0
        # touching an evicted key re-traces but the RESULT is identical:
        # eviction is a memory policy, never an observable behavior change
        again = {p: np.asarray(power_kernel(p)(jnp.asarray(X))) for p in (1, 2, 3, 4)}
    for p in (1, 2, 3, 4):
        assert first[p].tobytes() == again[p].tobytes()


def test_keyed_jit_lru_touch_refreshes_recency():
    with config.kernel_cache_limit(2):
        k5, k6 = power_kernel(5), power_kernel(6)
        power_kernel(5)  # touch 5: now 6 is least-recent
        before = metrics.snapshot()
        power_kernel(7)  # evicts 6, not 5
        assert _counter_delta(before, "jit.kernelCacheEvict") == 1.0
        before = metrics.snapshot()
        assert power_kernel(5) is k5  # still cached: no rebuild
        assert _counter_delta(before, "jit.kernels") == 0.0
        assert power_kernel(6) is not k6  # rebuilt after eviction


# ---------------------------------------------------------------------------
# serving warmup -> bank
# ---------------------------------------------------------------------------

def _serving_workload():
    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.table import Table

    rng = np.random.default_rng(11)
    scaler = StandardScalerModel()
    scaler.mean = rng.standard_normal(6)
    scaler.std = np.abs(rng.standard_normal(6)) + 0.1
    scaler.set_input_col("features").set_output_col("scaled")
    norm = Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm")
    model = PipelineModel([scaler, norm])
    example = Table({"features": rng.standard_normal((4, 6)).astype(np.float32)})
    return model, example


def test_server_warmup_populates_bank_then_serving_hits(tmp_path):
    from flink_ml_tpu.serving import MicroBatchServer

    bank_dir = str(tmp_path / "bank")
    model, example = _serving_workload()
    with config.program_bank_mode(bank_dir):
        info = MicroBatchServer(model, buckets=(4, 8)).warmup(example)
        assert info["programs"] == 2.0
        assert info["bankMisses"] == 2.0

    model2, _ = _serving_workload()
    with config.program_bank_mode(bank_dir):
        before = metrics.snapshot()
        out = list(
            MicroBatchServer(model2, buckets=(4, 8)).serve(iter([example]))
        )[0]
        delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        assert delta.get("jit.traces", 0) == 0, delta
        assert delta.get("bank.hits", 0) >= 1, delta
    assert np.isfinite(np.asarray(out.column("norm"))).all()


def test_warmup_reports_bank_counters_without_bank():
    from flink_ml_tpu.serving import MicroBatchServer

    model, example = _serving_workload()
    info = MicroBatchServer(model, buckets=(4,)).warmup(example)
    assert info["programs"] == 1.0
    assert info["bankHits"] == 0.0 and info["bankMisses"] == 0.0
    assert info["warmupMs"] >= 0.0


def test_modelstore_warmup_programs(tmp_path):
    from flink_ml_tpu.data.modelstore import ModelStore
    from flink_ml_tpu.serving import MicroBatchServer

    model, example = _serving_workload()
    store = ModelStore(budget_bytes=None)
    store.register("tenant-a", model)
    server = MicroBatchServer(model, buckets=(4,), store=store)
    with config.program_bank_mode(str(tmp_path / "bank")):
        info = store.warmup_programs(server, example)
        assert info["programs"] >= 1.0
