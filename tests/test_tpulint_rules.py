"""Per-rule unit tests for the tpulint framework: every rule proves a
true positive (known-bad source is flagged), a true negative (the
idiomatic good pattern is not), and — for the file-scanned rules — that a
``# tpulint: disable=<rule> -- reason`` suppression hides the finding
while an unmatched suppression is itself reported."""

import os
import textwrap

import pytest

from flink_ml_tpu.analysis import engine
from flink_ml_tpu.analysis.engine import Project
from flink_ml_tpu.analysis.source import SourceModule, code_only


def _make_tree(tmp_path, files):
    """Write a fixture package tree under tmp_path/flink_ml_tpu and load a
    Project over it. `files` maps package-relative paths to source."""
    for rel, src in files.items():
        path = tmp_path / "flink_ml_tpu" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return Project.load(root=str(tmp_path), scope=("flink_ml_tpu",))


def _run(tmp_path, files, rule_ids):
    project = _make_tree(tmp_path, files)
    rules = [engine.get_rule(r) for r in rule_ids]
    return engine.run(root=str(tmp_path), rules=rules, project=project)


LAZYJIT_STUB = {
    "utils/lazyjit.py": """
        def lazy_jit(fn, **kw):
            return fn
        def keyed_jit(make, **kw):
            return make
    """,
    "utils/__init__.py": "",
    "__init__.py": "",
}


# ---------------------------------------------------------------------------
# host-sync-leak
# ---------------------------------------------------------------------------

class TestHostSyncLeak:
    def test_true_positive_np_asarray_on_device_value(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                import numpy as np

                def fit(X):
                    dev = jnp.sum(X, axis=0)
                    return np.asarray(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.rule == "host-sync-leak"
        assert f.path == "flink_ml_tpu/models/bad.py"
        assert f.line == 7

    def test_true_positive_item_and_casts(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp

                def fit(X):
                    loss = jnp.mean(X)
                    a = loss.item()
                    b = float(loss)
                    return a, b
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        kinds = sorted(f.data[0] for f in report.findings)
        assert kinds == ["cast", "item"]

    def test_true_positive_block_until_ready(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax

                def wait(x):
                    jax.block_until_ready(x)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert [f.data[0] for f in report.findings] == ["block_until_ready"]

    def test_true_negative_host_values_and_funnel(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                import numpy as np

                def fit(X, hyper):
                    host = np.asarray(hyper)          # host in, host out
                    n = int(X.shape[0])               # shape metadata
                    dev = jnp.sum(X, axis=0)
                    from ..utils.packing import packed_device_get
                    out = packed_device_get(dev, sync_kind="fit")[0]
                    return np.asarray(out), host, n   # funnel output is host
            """,
            "utils/packing.py": "def packed_device_get(*a, **k):\n    return list(a)\n",
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert report.findings == []

    def test_suppression_hides_and_unused_is_reported(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                import numpy as np

                def fit(X):
                    dev = jnp.sum(X)
                    # tpulint: disable=host-sync-leak -- deliberate: tiny scalar, cold path
                    a = np.asarray(dev)
                    # tpulint: disable=host-sync-leak -- stale annotation
                    b = np.asarray(X.shape)
                    return a, b
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert len(report.suppressed) == 1
        assert [f.rule for f in report.findings] == ["unused-suppression"]


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

class TestRetraceHazard:
    def test_true_positive_raw_jit_and_closure(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax

                def fit(X, lr):
                    def step(c):
                        return c * lr
                    return jax.jit(step)(X)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["retrace-hazard"])
        tags = sorted(f.data[0] for f in report.findings)
        assert tags == ["closure", "raw-jit"]

    def test_true_positive_static_key_fstring(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from ..utils.lazyjit import lazy_jit

                def make(fn, name):
                    return lazy_jit(fn, static_argnames=f"{name}_arg")
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["retrace-hazard"])
        assert [f.data[0] for f in report.findings] == ["static-key"]

    def test_true_negative_lazyjit_module_level(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": """
                from ..utils.lazyjit import keyed_jit, lazy_jit

                def _impl(x):
                    return x + 1

                _kernel = lazy_jit(_impl, static_argnames=("n",))
                _family = keyed_jit(lambda k: _impl)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["retrace-hazard"])
        assert report.findings == []

    def test_suppression(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax

                def _impl(x):
                    return x

                # tpulint: disable=retrace-hazard -- cached by the caller keyed on mesh
                _kernel = jax.jit(_impl)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["retrace-hazard"])
        assert report.findings == []
        assert len(report.suppressed) == 1
        assert report.suppressed[0].data[0] == "raw-jit"


# ---------------------------------------------------------------------------
# donation-after-use
# ---------------------------------------------------------------------------

DONATING_PRELUDE = (
    "import jax\n"
    "\n"
    "def _impl(a, b):\n"
    "    return a + b\n"
    "\n"
    "_step = jax.jit(_impl)\n"
    "_step_donating = jax.jit(_impl, donate_argnums=(0,))\n"
)


class TestDonationAfterUse:
    def test_true_positive_read_after_donate(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": DONATING_PRELUDE + (
                "def fit(carry, other):\n"
                "    out = _step_donating(carry, other)\n"
                "    return out + carry  # carry's buffer was donated\n"
            ),
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("carry", "_step_donating")

    def test_true_positive_through_gating_alias(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": DONATING_PRELUDE + (
                "def fit(carry, other, ok):\n"
                "    step = _step_donating if ok else _step\n"
                "    out = step(carry, other)\n"
                "    return out + carry\n"
            ),
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert len(report.findings) == 1

    def test_true_negative_pingpong_rebind(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": DONATING_PRELUDE + (
                "def fit(carry, other):\n"
                "    carry = _step_donating(carry, other)  # rebound: fine\n"
                "    keep = _step(carry, other)            # borrowing: fine\n"
                "    return carry + keep + other\n"
            ),
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert report.findings == []

    def test_suppression(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": DONATING_PRELUDE + (
                "def fit(carry, other):\n"
                "    out = _step_donating(carry, other)\n"
                "    # tpulint: disable=donation-after-use -- CPU-only debug helper\n"
                "    return out + carry\n"
            ),
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# sharding-tags
# ---------------------------------------------------------------------------

SNAPSHOT_FIXTURE = {
    "ckpt/snapshot.py": """
        _SPEC_TAGS = ("replicated", "data", "model", "host")

        def _sharding_for(tag, mesh, ndim):
            if tag == "data":
                return "D"
            if tag == "model":
                return "M"
            return "R"

        def save_job_snapshot(path, key, sections, specs=None, **kw):
            pass

        def stage_section(snap, name, mesh=None, specs=None):
            pass
    """,
    "ckpt/__init__.py": "",
    "parallel/mesh.py": """
        def replicated_sharding(mesh):
            pass

        def data_sharding(mesh, ndim=1):
            pass

        def model_sharding(mesh, ndim=1):
            pass
    """,
    "parallel/__init__.py": "",
}


class TestShardingTags:
    def test_true_positive_unknown_tag_at_call_site(self, tmp_path):
        report = _run(tmp_path, {
            **SNAPSHOT_FIXTURE,
            "models/bad.py": """
                from ..ckpt.snapshot import save_job_snapshot

                def checkpoint(path, carry):
                    save_job_snapshot(
                        path, "job", {"model": carry},
                        specs={"model": "fully_sharded"},
                    )
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["sharding-tags"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("fully_sharded",)
        assert report.findings[0].path == "flink_ml_tpu/models/bad.py"

    def test_true_positive_table_without_constructor(self, tmp_path):
        fixture = dict(SNAPSHOT_FIXTURE)
        fixture["ckpt/snapshot.py"] = fixture["ckpt/snapshot.py"].replace(
            '"replicated", "data", "model", "host"',
            '"replicated", "data", "model", "host", "striped"',
        )
        report = _run(tmp_path, {**fixture, **LAZYJIT_STUB}, ["sharding-tags"])
        tags = {f.data[0] for f in report.findings if f.data}
        assert "striped" in tags

    def test_true_negative_known_tags_and_local_indirection(self, tmp_path):
        report = _run(tmp_path, {
            **SNAPSHOT_FIXTURE,
            "models/good.py": """
                from ..ckpt.snapshot import save_job_snapshot, stage_section

                def checkpoint(path, carry, shard):
                    carry_specs = (
                        ("model", "replicated") if shard else "replicated"
                    )
                    save_job_snapshot(
                        path, "job", {"model": carry},
                        specs={"model": carry_specs, "rng": "host"},
                    )
                    stage_section(None, "model", specs=carry_specs)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["sharding-tags"])
        assert report.findings == []

    def test_suppression(self, tmp_path):
        report = _run(tmp_path, {
            **SNAPSHOT_FIXTURE,
            "models/bad.py": """
                from ..ckpt.snapshot import save_job_snapshot

                def checkpoint(path, carry):
                    save_job_snapshot(
                        path, "job", {"model": carry},
                        # tpulint: disable=sharding-tags -- forward-compat tag, staged by a plugin
                        specs={"model": "fully_sharded"},
                    )
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["sharding-tags"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# ported accounting gates
# ---------------------------------------------------------------------------

class TestAccountingRules:
    def test_collective_true_positive_and_docstring_negative(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                '''lax.psum(x, axis) in a docstring is fine.'''
                from jax import lax

                # lax.psum(x) in a comment is fine
                def f(x):
                    return lax.psum(x, "data")
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["collective-accounting"])
        assert [(f.line, f.data[0]) for f in report.findings] == [(7, "psum")]

    def test_collective_out_of_scope_dir_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "parallel/infra.py": """
                from jax import lax

                def f(x):
                    return lax.psum(x, "data")
            """,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["collective-accounting"])
        assert report.findings == []

    def test_upload_true_positive_and_suppression(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax

                def stage(x):
                    a = jax.device_put(x)
                    # tpulint: disable=upload-accounting -- test-only helper
                    b = jax.device_put(x)
                    return a, b
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["upload-accounting"])
        assert [f.line for f in report.findings] == [5]
        assert [f.line for f in report.suppressed] == [7]


# ---------------------------------------------------------------------------
# ported coverage gates (import-based; synthetic class graph)
# ---------------------------------------------------------------------------

class TestCoverageRules:
    def test_fusion_true_positive_synthetic(self, monkeypatch):
        from flink_ml_tpu.analysis.rules import coverage

        class Silent:  # neither kernel nor declaration
            pass

        monkeypatch.setattr(
            coverage, "_iter_operator_classes", lambda base: iter(())
        )
        monkeypatch.setattr(
            coverage.FusionCoverageRule,
            "finder",
            staticmethod(
                lambda: [("fake.Silent", "no transform_kernel and no explicit "
                          "fusable declaration")]
            ),
        )
        rule = coverage.FusionCoverageRule()
        findings = list(rule.check_project(Project(root=os.getcwd())))
        assert len(findings) == 1
        assert findings[0].rule == "fusion-coverage"
        assert "Silent" in findings[0].message

    def test_fusion_and_checkpoint_true_negative_on_real_tree(self):
        from flink_ml_tpu.analysis.rules.coverage import (
            find_checkpoint_violations,
            find_fusion_violations,
        )

        assert find_fusion_violations() == []
        assert find_checkpoint_violations() == []

    def test_checkpoint_violation_logic_synthetic(self):
        from flink_ml_tpu.analysis.rules import coverage

        # the funnel check reads comment/string-stripped source
        assert not any(
            funnel in code_only('"""mentions run_sgd only in docs."""\n')
            for funnel in coverage.CHECKPOINT_FUNNELS
        )
        assert any(
            funnel in code_only("coeff = run_sgd(params)\n")
            for funnel in coverage.CHECKPOINT_FUNNELS
        )


# ---------------------------------------------------------------------------
# unbounded-queue
# ---------------------------------------------------------------------------

class TestUnboundedQueue:
    def test_true_positive_bare_deque_and_queue(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import queue
                from collections import deque

                pending = deque()
                inbox = queue.Queue()
                lifo = queue.LifoQueue(maxsize=0)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        kinds = sorted(f.data[0] for f in report.findings)
        assert kinds == ["deque", "queue", "queue"]
        assert all(f.rule == "unbounded-queue" for f in report.findings)

    def test_true_positive_raw_thread_spawn(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import threading

                def go(fn):
                    t = threading.Thread(target=fn, daemon=True)
                    t.start()
                    return t
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        assert [f.data[0] for f in report.findings] == ["thread"]
        assert "flow.pump" in report.findings[0].message

    def test_true_positive_simplequeue_and_from_imports(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from queue import Queue, SimpleQueue
                from threading import Thread

                a = Queue()
                b = SimpleQueue()  # cannot be bounded at all
                c = Thread(target=print)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        kinds = sorted(f.data[0] for f in report.findings)
        assert kinds == ["queue", "queue", "thread"]

    def test_true_negative_bounded_structures(self, tmp_path):
        report = _run(tmp_path, {
            "models/ok.py": """
                import queue
                import collections
                from collections import deque

                ring = deque(maxlen=128)
                ring2 = collections.deque([], 16)
                inbox = queue.Queue(maxsize=8)
                inbox2 = queue.Queue(cap)  # dynamic bound: trusted
                counts = collections.Counter()
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        assert report.findings == []

    def test_true_negative_flow_and_prefetch_exempt(self, tmp_path):
        src = """
            import threading
            from collections import deque

            items = deque()
            worker = threading.Thread(target=print)
        """
        report = _run(tmp_path, {
            "flow.py": src,
            "parallel/prefetch.py": src,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["unbounded-queue"])
        assert report.findings == []

    def test_suppression_with_reason_hides_finding(self, tmp_path):
        report = _run(tmp_path, {
            "models/ok.py": """
                from collections import deque

                # tpulint: disable=unbounded-queue -- drained past depth in the same call
                q = deque()
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        assert report.findings == []
        assert len(report.suppressed) == 1

    def test_unused_suppression_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "models/ok.py": """
                from collections import deque

                # tpulint: disable=unbounded-queue -- stale
                q = deque(maxlen=4)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unbounded-queue"])
        assert [f.rule for f in report.findings] == ["unused-suppression"]


# ---------------------------------------------------------------------------
# engine / suppression machinery
# ---------------------------------------------------------------------------

class TestEngine:
    def test_unknown_rule_suppression_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "models/odd.py": """
                # tpulint: disable=no-such-rule -- whatever
                x = 1
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert [f.rule for f in report.findings] == ["unused-suppression"]
        assert "unknown rule" in report.findings[0].message

    def test_inline_and_preceding_line_suppressions(self):
        mod = SourceModule(
            path="m.py",
            abspath="m.py",
            source="",
        )
        src = (
            "import numpy as np\n"
            "# tpulint: disable=rule-a -- above\n"
            "x = 1\n"
            "y = 2  # tpulint: disable=rule-b -- inline\n"
        )
        from flink_ml_tpu.analysis.source import _parse_suppressions

        sups = _parse_suppressions(src)
        assert [(s.rule, s.line, s.reason) for s in sups] == [
            ("rule-a", 3, "above"),
            ("rule-b", 4, "inline"),
        ]
        del mod

    def test_code_only_blanks_strings_and_comments(self):
        stripped = code_only('x = "lax.psum"  # lax.psum\ny = 2\n')
        assert "psum" not in stripped
        assert "y = 2" in stripped
        # line structure is preserved for true line numbers
        assert stripped.count("\n") == 2

    def test_rule_catalogue_metadata_complete(self):
        for rule in engine.all_rules():
            assert rule.id and rule.title and rule.rationale, rule
            assert rule.scope, rule.id

    def test_findings_filtered_by_only_paths(self, tmp_path):
        project = _make_tree(tmp_path, {
            "models/a.py": "import jax\nf = jax.jit(int)\n",
            "models/b.py": "import jax\ng = jax.jit(int)\n",
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        })
        rules = [engine.get_rule("retrace-hazard")]
        full = engine.run(root=str(tmp_path), rules=rules, project=project)
        assert len(full.findings) == 2
        # reload (Suppression.used state is per-Project)
        project = Project.load(root=str(tmp_path), scope=("flink_ml_tpu",))
        partial = engine.run(
            root=str(tmp_path),
            rules=rules,
            project=project,
            only_paths=["flink_ml_tpu/models/a.py"],
        )
        assert [f.path for f in partial.findings] == ["flink_ml_tpu/models/a.py"]


# ---------------------------------------------------------------------------
# interprocedural host-sync-leak (the v2 call-graph rewiring)
# ---------------------------------------------------------------------------

class TestInterproceduralHostSync:
    def test_laundered_pull_flagged_at_call_site_with_chain(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                import numpy as np

                def _to_host(x):
                    return np.asarray(x)

                def fit(X):
                    dev = jnp.sum(X, axis=0)
                    return _to_host(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.line == 10  # the call site in fit, not the helper
        assert f.data[0] == "np-pull-chain"
        assert "_to_host" in f.message
        assert "models/bad.py:6" in f.message  # the sink's file:line

    def test_cross_module_laundering(self, tmp_path):
        report = _run(tmp_path, {
            "ops/helpers.py": """
                import numpy as np

                def to_host(x):
                    return np.asarray(x)
            """,
            "ops/__init__.py": "",
            "models/bad.py": """
                import jax.numpy as jnp

                from ..ops.helpers import to_host

                def fit(X):
                    dev = jnp.mean(X)
                    return to_host(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert [f.path for f in report.findings] == ["flink_ml_tpu/models/bad.py"]
        assert report.findings[0].line == 8
        assert "ops/helpers.py:5" in report.findings[0].message

    def test_helper_returning_device_taints_caller(self, tmp_path):
        """A resolved helper that RETURNS a device value un-launders the
        old per-function blind spot: np.asarray on its result is flagged."""
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                import numpy as np

                def _make(X):
                    return jnp.sum(X, axis=0)

                def fit(X):
                    dev = _make(X)
                    return np.asarray(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert len(report.findings) == 1
        assert report.findings[0].line == 10
        assert report.findings[0].data[0] == "np-pull"

    def test_method_helper_resolved_through_self(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                import numpy as np

                class Model:
                    def _pull(self, v):
                        return np.asarray(v)

                    def fit(self, X):
                        dev = jnp.sum(X)
                        return self._pull(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert len(report.findings) == 1
        assert report.findings[0].line == 11
        assert "Model._pull" in report.findings[0].message

    def test_host_input_through_helper_is_clean(self, tmp_path):
        """The under-approximation survives: callers passing HOST values
        to a syncing helper are not flagged."""
        report = _run(tmp_path, {
            "models/good.py": """
                import numpy as np

                def _to_host(x):
                    return np.asarray(x)

                def fit(rows):
                    return _to_host(rows)  # rows is host data
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert report.findings == []

    def test_suppressed_sink_in_helper_covers_callers(self, tmp_path):
        """A suppression-with-reason ON the helper's sink line keeps the
        site out of the summary (callers inherit no finding) while the
        annotated helper still shows in the census."""
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                import numpy as np

                def _probe(x):
                    # tpulint: disable=host-sync-leak -- deliberate: tiny scalar probe
                    return np.asarray(x)

                def fit(X):
                    dev = jnp.sum(X)
                    return _probe(dev)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["host-sync-leak"])
        assert report.findings == []  # no caller finding, no unused-suppression
        assert len(report.suppressed) == 1
        assert report.suppressed[0].path == "flink_ml_tpu/models/good.py"


# ---------------------------------------------------------------------------
# interprocedural donation-after-use
# ---------------------------------------------------------------------------

class TestInterproceduralDonation:
    def test_wrapper_around_donating_kernel_poisons_caller(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax

                def _impl(a, b):
                    return a + b

                _step_donating = jax.jit(_impl, donate_argnums=(0,))

                def wrapper(carry, other):
                    return _step_donating(carry, other)

                def fit(carry, other):
                    out = wrapper(carry, other)
                    return out + carry
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.line == 14  # `return out + carry` in fit
        assert "wrapper" in f.message and "_step_donating" in f.message

    def test_wrapper_result_use_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": """
                import jax

                def _impl(a, b):
                    return a + b

                _step_donating = jax.jit(_impl, donate_argnums=(0,))

                def wrapper(carry, other):
                    return _step_donating(carry, other)

                def fit(carry, other):
                    carry = wrapper(carry, other)  # ping-pong rebind
                    return carry
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["donation-after-use"])
        assert report.findings == []


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

class TestLockOrder:
    def test_true_positive_abba_inversion(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                import threading

                class S:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()

                    def one(self):
                        with self._a:
                            with self._b:
                                pass

                    def two(self):
                        with self._b:
                            with self._a:
                                pass
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data[0] == "cycle"
        assert "S._a" in f.message and "S._b" in f.message

    def test_true_negative_consistent_order(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                import threading

                class S:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()

                    def one(self):
                        with self._a:
                            with self._b:
                                pass

                    def two(self):
                        with self._a:
                            with self._b:
                                pass
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert report.findings == []

    def test_self_deadlock_through_transitive_call(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                import threading

                class S:
                    def __init__(self):
                        self._m = threading.Lock()

                    def outer(self):
                        with self._m:
                            self.inner()

                    def inner(self):
                        with self._m:
                            pass
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert len(report.findings) == 1
        assert report.findings[0].data[0] == "self-deadlock"
        assert "S.inner" in report.findings[0].message

    def test_reentrant_condition_self_nesting_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                import threading

                class S:
                    def __init__(self):
                        self._cv = threading.Condition()

                    def outer(self):
                        with self._cv:
                            self.inner()

                    def inner(self):
                        with self._cv:
                            pass
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert report.findings == []

    def test_cross_module_cycle_via_imported_call(self, tmp_path):
        report = _run(tmp_path, {
            "data/devicecache.py": """
                import threading

                from ..serving import poke

                _cache_lock = threading.Lock()

                def refresh():
                    with _cache_lock:
                        poke()

                def touch():
                    with _cache_lock:
                        pass
            """,
            "data/__init__.py": "",
            "serving.py": """
                import threading

                _serve_lock = threading.Lock()

                def poke():
                    with _serve_lock:
                        pass

                def other():
                    from .data.devicecache import touch
                    with _serve_lock:
                        touch()
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert len(report.findings) == 1
        assert report.findings[0].data[0] == "cycle"
        assert "_cache_lock" in report.findings[0].message
        assert "_serve_lock" in report.findings[0].message

    def test_suppression_hides_cycle_finding(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                import threading

                class S:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()

                    def one(self):
                        with self._a:
                            # tpulint: disable=lock-order -- fixture: order proven safe by external protocol
                            with self._b:
                                pass

                    def two(self):
                        with self._b:
                            with self._a:
                                pass
            """,
            **LAZYJIT_STUB,
        }, ["lock-order"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# channel-protocol
# ---------------------------------------------------------------------------

FLOW_STUB = {
    "flow.py": """
        import threading

        class BoundedChannel:
            def __init__(self, capacity, policy="block", name="channel"):
                self._cv = threading.Condition()
                self.name = name
            def put(self, item, timeout=None):
                return True
            def get(self, timeout=None):
                return None
            def close(self, error=None):
                pass
            def cancel(self):
                return []
            def __iter__(self):
                return iter(())

        def pump(items, channel, transform=None, watchdog=None):
            pass

        def spawn(fn, name="worker"):
            pass
    """,
}


class TestChannelProtocol:
    def test_worker_never_closing_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                class Server:
                    def start(self):
                        self._out = flow.BoundedChannel(4, name="out")
                        self._worker = flow.spawn(self._run, name="d")

                    def _run(self):
                        while True:
                            self._out.put(1)
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data == ("worker", "Server._run")
        assert "never closes" in f.message

    def test_worker_without_error_path_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                class Server:
                    def start(self):
                        self._out = flow.BoundedChannel(4, name="out")
                        self._worker = flow.spawn(self._run, name="d")

                    def _run(self):
                        for item in (1, 2, 3):
                            self._out.put(item)
                        self._out.close()
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert len(report.findings) == 1
        assert "happy path" in report.findings[0].message

    def test_close_with_error_worker_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                class Server:
                    def start(self):
                        self._out = flow.BoundedChannel(4, name="out")
                        self._worker = flow.spawn(self._run, name="d")

                    def _run(self):
                        try:
                            for item in (1, 2, 3):
                                self._out.put(item)
                            self._out.close()
                        except BaseException as e:
                            self._out.close(error=e)
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert report.findings == []

    def test_worker_closing_via_helper_in_finally_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                class Server:
                    def start(self):
                        self._out = flow.BoundedChannel(4, name="out")
                        self._worker = flow.spawn(self._run, name="d")

                    def _release(self):
                        self._out.cancel()

                    def _run(self):
                        try:
                            self._out.put(1)
                        finally:
                            self._release()
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert report.findings == []

    def test_undrained_channel_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                def leak():
                    ch = flow.BoundedChannel(2, name="x")
                    ch.put(1)
                    ch.put(2)
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("undrained-channel", "ch")

    def test_pumped_iterated_cancelled_channel_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                def prefetch(items, stage):
                    ch = flow.BoundedChannel(4, name="p")
                    flow.pump(items, ch, transform=stage)
                    try:
                        yield from ch
                    finally:
                        ch.cancel()
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert report.findings == []

    def test_channel_closed_by_resolved_helper_is_clean(self, tmp_path):
        """param_closes: handing the channel to a helper that cancels it
        satisfies the contract through the call graph."""
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                def _teardown(window):
                    window.cancel()

                def serve(stream):
                    window = flow.BoundedChannel(4, name="w")
                    for item in stream:
                        window.put(item)
                    _teardown(window)
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert report.findings == []

    def test_submit_without_results_is_flagged(self, tmp_path):
        report = _run(tmp_path, {
            "models/client.py": """
                def run(server, batches):
                    for b in batches:
                        server.submit(b)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["channel-protocol"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("submit-without-results",)

    def test_submit_with_results_loop_is_clean(self, tmp_path):
        report = _run(tmp_path, {
            "models/client.py": """
                def run(server, batches):
                    for b in batches:
                        server.submit(b)
                    server.close()
                    return list(server.results())
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["channel-protocol"])
        assert report.findings == []

    def test_suppression_hides_undrained_channel(self, tmp_path):
        report = _run(tmp_path, {
            "serving.py": """
                from . import flow

                def leak():
                    # tpulint: disable=channel-protocol -- fixture: drained by the caller via attribute
                    ch = flow.BoundedChannel(2, name="x")
                    ch.put(1)
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
        }, ["channel-protocol"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# hot-swap publication idioms (lifecycle.py / the online models)
# ---------------------------------------------------------------------------

class TestHotSwapPublishIdioms:
    """The fixture pair behind docs/model_lifecycle.md's publication
    contract: a torn publish guarded by TWO locks taken in inconsistent
    order is exactly the ABBA inversion `lock-order` exists for, while the
    shipped single-atomic-reference swap (one immutable record, no lock
    nesting) lints clean under both concurrency rules."""

    def test_torn_publish_antipattern_is_flagged(self, tmp_path):
        # anti-pattern: version and arrays live behind separate locks; the
        # trainer writes arrays-then-version, the server reads
        # version-then-arrays — a deadlock-or-torn-read protocol
        report = _run(tmp_path, {
            "models/torn.py": """
                import threading

                class TornModel:
                    def __init__(self):
                        self._version_lock = threading.Lock()
                        self._arrays_lock = threading.Lock()
                        self.version = 0
                        self.arrays = None

                    def publish(self, arrays, version):
                        with self._arrays_lock:
                            self.arrays = arrays
                            with self._version_lock:
                                self.version = version

                    def serve_snapshot(self):
                        with self._version_lock:
                            version = self.version
                            with self._arrays_lock:
                                return version, self.arrays
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["lock-order"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data[0] == "cycle"
        assert "_arrays_lock" in f.message and "_version_lock" in f.message

    def test_atomic_swap_idiom_is_clean(self, tmp_path):
        # the shipped idiom: ONE immutable (version, arrays) record behind
        # ONE reference; the promote worker pumps through a flow channel
        # it closes on every path
        report = _run(tmp_path, {
            "models/swap.py": """
                from collections import namedtuple

                from .. import flow

                Published = namedtuple("Published", ["version", "arrays"])

                class SwapModel:
                    def __init__(self):
                        self._published = Published(0, None)

                    def publish(self, arrays, version):
                        self._published = Published(version, arrays)

                    def serve_snapshot(self):
                        pub = self._published
                        return pub.version, pub.arrays

                class Promoter:
                    def __init__(self, model, candidates):
                        self.model = model
                        self._in = flow.BoundedChannel(4, name="promote.in")
                        flow.pump(candidates, self._in)
                        self._worker = flow.spawn(self._run, name="promote")

                    def _run(self):
                        try:
                            for version, arrays in self._in:
                                self.model.publish(arrays, version)
                        finally:
                            self._in.cancel()
            """,
            **FLOW_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["lock-order", "channel-protocol"])
        assert report.findings == []


# ---------------------------------------------------------------------------
# tpulint v3: the SPMD mesh/axis verifier (analysis/spmd.py)
# ---------------------------------------------------------------------------

#: minimal mesh + collectives pair the SPMD layer anchors on (same relative
#: paths as the real package: parallel/mesh.py declares the *_AXIS
#: constants, parallel/collectives.py the accounted wrappers)
SPMD_STUB = {
    "parallel/__init__.py": "",
    "parallel/mesh.py": """
        DATA_AXIS = "data"
        MODEL_AXIS = "model"

        def create_mesh(axis_names=(DATA_AXIS,), shape=None, devices=None):
            pass
    """,
    "parallel/collectives.py": """
        from jax import lax

        from .mesh import DATA_AXIS, MODEL_AXIS

        def all_reduce_sum(x, axis_name=DATA_AXIS):
            return lax.psum(x, axis_name)

        def all_reduce_min(x, axis_name=DATA_AXIS):
            return lax.pmin(x, axis_name)

        def all_gather(x, axis_name=DATA_AXIS, axis=0, tiled=True):
            return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)

        def ppermute_ring(x, axis_name=DATA_AXIS, shift=1):
            return lax.ppermute(x, axis_name, [(0, 0)])

        def axis_index(axis_name=DATA_AXIS):
            return lax.axis_index(axis_name)

        def axis_size(axis_name=DATA_AXIS):
            return 1

        def shard_map_over(mesh, in_specs, out_specs, fn=None, check_vma=False):
            return fn
    """,
}


class TestMeshAxis:
    def test_true_positive_unknown_axis_literal(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from ..parallel.collectives import all_reduce_sum

                def reduce(x):
                    return all_reduce_sum(x, "dta")
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["mesh-axis"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data == ("unknown-axis", "dta")
        assert f.path == "flink_ml_tpu/models/bad.py" and f.line == 5

    def test_true_positive_constant_bypass_literal(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives

                def reduce(x):
                    return collectives.all_reduce_sum(x, "data")

                def spec():
                    return P("model", None)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["mesh-axis"])
        kinds = sorted((f.data[0], f.data[1]) for f in report.findings)
        assert kinds == [("axis-bypass", "data"), ("axis-bypass", "model")]
        assert "DATA_AXIS" in report.findings[0].message

    def test_true_positive_gather_over_unsharded_axis(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

                def build(mesh):
                    def body(x):
                        return collectives.all_gather(x, MODEL_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(DATA_AXIS), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["mesh-axis"])
        assert [f.data[0] for f in report.findings] == ["unsharded-collective"]
        assert report.findings[0].data[2] == "model"

    def test_true_negative_constants_and_known_axes(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.collectives import DATA_AXIS, all_reduce_sum
                from ..parallel.mesh import MODEL_AXIS

                def reduce(x):
                    return all_reduce_sum(x, DATA_AXIS)

                def reduce_feature(x):
                    return collectives.all_reduce_sum(x, axis_name=MODEL_AXIS)

                def spec():
                    return P(DATA_AXIS, MODEL_AXIS)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["mesh-axis"])
        assert report.findings == []

    def test_suppression_hides_and_unused_is_reported(self, tmp_path):
        report = _run(tmp_path, {
            "models/mixed.py": """
                from ..parallel.collectives import all_reduce_sum

                def reduce(x):
                    # tpulint: disable=mesh-axis -- exercising a foreign mesh in a compat shim
                    return all_reduce_sum(x, "data")

                def clean(x):
                    # tpulint: disable=mesh-axis -- nothing here
                    return x
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["mesh-axis"])
        assert [f.rule for f in report.findings] == ["unused-suppression"]
        assert len(report.suppressed) == 1
        assert report.suppressed[0].data[0] == "axis-bypass"


class TestCollectiveDivergence:
    def test_true_positive_axis_index_branch(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        i = collectives.axis_index(DATA_AXIS)
                        if i == 0:
                            x = collectives.all_reduce_sum(x, DATA_AXIS)
                        return x
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(DATA_AXIS), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["collective-divergence"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data[0] == "divergent" and f.data[1] == "all_reduce_sum"
        assert f.line == 10  # the collective, not the branch
        assert "line 9" in f.message  # ... which is named in the message

    def test_true_positive_data_dependent_branch(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        if jnp.sum(x) > 0:
                            x = collectives.all_gather(x, DATA_AXIS)
                        return x
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(DATA_AXIS), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["collective-divergence"])
        assert [f.data[0] for f in report.findings] == ["divergent"]

    def test_true_negative_uniform_branch_and_masked_contribution(self, tmp_path):
        # branch on a REDUCED (uniform) value, collective outside any
        # branch, contribution masked — the sanctioned shape
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh, epochs):
                    def body(x):
                        total = collectives.all_reduce_sum(jnp.sum(x), DATA_AXIS)
                        if total > 0:
                            scale = 2.0
                        else:
                            scale = 1.0
                        if epochs > 1:
                            scale = scale + 1.0
                        mask = x > 0
                        return collectives.all_reduce_sum(
                            jnp.where(mask, x, 0.0), DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["collective-divergence"])
        assert report.findings == []

    def test_suppression_hides_finding(self, tmp_path):
        report = _run(tmp_path, {
            "models/mixed.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        i = collectives.axis_index(DATA_AXIS)
                        if i == 0:
                            # tpulint: disable=collective-divergence -- single-host probe, documented
                            x = collectives.all_reduce_sum(x, DATA_AXIS)
                        return x
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(DATA_AXIS), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["collective-divergence"])
        assert report.findings == []
        assert len(report.suppressed) == 1


class TestSpecConsistency:
    def test_true_positive_replicated_output_never_reduced(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        return x * 2.0
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data[0] == "unreduced-output"
        assert "data" in f.message

    def test_true_positive_double_reduce(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        y = collectives.all_reduce_sum(x, DATA_AXIS)
                        return collectives.all_reduce_sum(y, DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert [f.data[0] for f in report.findings] == ["double-reduce"]
        assert report.findings[0].data[2] == "data"

    def test_true_positive_spec_arity_mismatch(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x, y):
                        return collectives.all_reduce_sum(x + y, DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert [f.data[0] for f in report.findings] == ["spec-arity"]

    def test_true_negative_reduced_output_and_carry_loop(self, tmp_path):
        # sharded batch, carry-delayed reduce through a lax.while_loop,
        # replicated result
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                from jax import lax
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    axis = DATA_AXIS

                    def body(X, coeff):
                        def cond(state):
                            c, g, epoch = state
                            return epoch < 3

                        def step(state):
                            c, g, epoch = state
                            c = c - collectives.all_reduce_sum(g, axis)
                            g = X.T @ (X @ c)
                            return (c, g, epoch + 1)

                        init = (coeff, jnp.zeros_like(coeff), 0)
                        c, g, _ = lax.while_loop(cond, step, init)
                        return c - collectives.all_reduce_sum(g, axis)

                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS, None), P()), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert report.findings == []

    def test_true_positive_2d_model_axis_unreduced(self, tmp_path):
        # the 2D (data x model) trap: a feature-sharded product reduced
        # over DATA only but declared fully replicated — the model-axis
        # variation silently survives into the "replicated" output
        report = _run(tmp_path, {
            "models/bad2d.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

                def build(mesh):
                    def body(X, coeff):
                        grad = collectives.all_reduce_sum(X @ coeff, DATA_AXIS)
                        return grad
                    return collectives.shard_map_over(
                        mesh,
                        (P(DATA_AXIS, MODEL_AXIS), P(MODEL_AXIS)),
                        P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data[0] == "unreduced-output"
        assert "model" in f.message

    def test_true_negative_2d_sharded_carry_out(self, tmp_path):
        # the sgd2d program in miniature: activations psum over MODEL,
        # gradient psum over DATA, the updated carry declared P(model) —
        # per-axis bookkeeping must see every axis resolved
        report = _run(tmp_path, {
            "models/good2d.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

                def build(mesh):
                    def body(X, coeff):
                        act = collectives.all_reduce_sum(
                            X @ coeff, MODEL_AXIS)
                        grad = collectives.all_reduce_sum(
                            X.T @ act, DATA_AXIS)
                        loss = collectives.all_reduce_sum(act, DATA_AXIS)
                        return coeff - grad, loss
                    return collectives.shard_map_over(
                        mesh,
                        (P(DATA_AXIS, MODEL_AXIS), P(MODEL_AXIS)),
                        (P(MODEL_AXIS), P()), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert report.findings == []

    def test_unknown_specs_suppress_findings(self, tmp_path):
        # unresolvable in_specs: the engine must stay quiet, not guess
        report = _run(tmp_path, {
            "models/opaque.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives

                def build(mesh, in_specs):
                    def body(x):
                        return x
                    return collectives.shard_map_over(mesh, in_specs, P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert report.findings == []

    def test_suppression_hides_finding(self, tmp_path):
        report = _run(tmp_path, {
            "models/mixed.py": """
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        # tpulint: disable=spec-consistency -- shard 0's value IS the result here, documented
                        return x * 2.0
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert report.findings == []
        assert len(report.suppressed) == 1


class TestPrecisionDeterminism:
    def test_true_positive_downcast_before_reduce(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        return collectives.all_reduce_sum(
                            x.astype(jnp.bfloat16), DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.data == ("downcast", "all_reduce_sum", "bfloat16")

    def test_true_positive_downcast_through_assignment(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        small = x.astype(jnp.float16)
                        return collectives.all_reduce_sum(small, DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert [f.data[0] for f in report.findings] == ["downcast"]
        assert report.findings[0].data[2] == "float16"

    def test_true_negative_f32_accumulator_cast(self, tmp_path):
        # astype(jnp.float32) on a loss scalar before its reduce is a
        # WIDENING (or no-op) cast and must stay legal
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        return collectives.all_reduce_sum(
                            jnp.sum(x).astype(jnp.float32), DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert report.findings == []

    def test_true_positive_manual_ring_fold_outside_sanctioned(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def fold(x, n):
                    acc = x
                    for _ in range(n - 1):
                        x = collectives.ppermute_ring(x, DATA_AXIS)
                        acc = acc + x
                    return acc
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert [f.data[0] for f in report.findings] == ["order-fold"]

    def test_true_negative_fold_inside_collectives_is_sanctioned(self, tmp_path):
        # the same fold INSIDE parallel/collectives.py is the sanctioned
        # replica-order implementation
        stub = dict(SPMD_STUB)
        stub["parallel/collectives.py"] = stub["parallel/collectives.py"] + (
            "\n"
            "def ring_fold(x, n, axis_name=DATA_AXIS):\n"
            "    acc = x\n"
            "    for _ in range(n - 1):\n"
            "        x = ppermute_ring(x, axis_name)\n"
            "        acc = acc + x\n"
            "    return acc\n"
        )
        report = _run(tmp_path, {
            **stub,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert report.findings == []

    def test_suppression_hides_finding(self, tmp_path):
        report = _run(tmp_path, {
            "models/mixed.py": """
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def body(x):
                        # tpulint: disable=precision-determinism -- wire-format bf16, error budget documented
                        return collectives.all_reduce_sum(
                            x.astype(jnp.bfloat16), DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=body)
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["precision-determinism"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# resident-program
# ---------------------------------------------------------------------------

class TestResidentProgram:
    def test_true_positive_debug_print_in_jitted_impl(self, tmp_path):
        report = _run(tmp_path, {
            "ops/bad.py": """
                import jax
                import jax.numpy as jnp
                from jax import lax
                from ..utils.lazyjit import lazy_jit

                def _train_impl(X, carry):
                    def step(state):
                        c, e = state
                        jax.debug.print("epoch {e}", e=e)
                        return c + jnp.sum(X), e + 1
                    return lax.while_loop(lambda s: s[1] < 10, step, carry)

                _train = lazy_jit(_train_impl)
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert len(report.findings) == 1
        assert report.findings[0].rule == "resident-program"
        assert "jax.debug.print" in report.findings[0].message

    def test_true_positive_io_callback_in_loop_body(self, tmp_path):
        report = _run(tmp_path, {
            "ops/bad2.py": """
                import jax.numpy as jnp
                from jax import lax
                from jax.experimental import io_callback

                def fit(X):
                    def body(state):
                        io_callback(print, None, state)
                        return state + 1
                    return lax.while_loop(lambda s: s < 5, body, jnp.asarray(0))
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert len(report.findings) == 1
        assert "io_callback" in report.findings[0].message

    def test_true_positive_print_in_decorated_kernel(self, tmp_path):
        report = _run(tmp_path, {
            "ops/bad3.py": """
                import jax
                import jax.numpy as jnp

                @jax.jit
                def kernel(x):
                    print("tracing side effect")
                    return jnp.sum(x)
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert len(report.findings) == 1
        assert "print" in report.findings[0].message

    def test_true_negative_host_functions(self, tmp_path):
        report = _run(tmp_path, {
            "ops/good.py": """
                import jax
                import jax.numpy as jnp
                from ..utils.lazyjit import lazy_jit

                def _kernel_impl(x):
                    return jnp.sum(x) * 2.0

                _kernel = lazy_jit(_kernel_impl)

                def host_driver(x):
                    out = _kernel(x)
                    print("fit done")  # host side: fine
                    jax.debug.print("host-side debug {o}", o=out)
                    return out
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert report.findings == []

    def test_suppression_hides_and_unused_is_reported(self, tmp_path):
        report = _run(tmp_path, {
            "ops/supp.py": """
                import jax
                import jax.numpy as jnp
                from jax import lax
                from ..utils.lazyjit import lazy_jit

                def _probe_impl(X, carry):
                    def step(state):
                        # tpulint: disable=resident-program -- diagnostic build, stripped before release
                        jax.debug.print("state {s}", s=state)
                        return state + jnp.sum(X)
                    return lax.while_loop(lambda s: s < 3, step, carry)

                _probe = lazy_jit(_probe_impl)
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert report.findings == []
        assert len(report.suppressed) == 1

        stale = _run(tmp_path, {
            "ops/stale.py": """
                def host_only():
                    # tpulint: disable=resident-program -- nothing resident here
                    print("plain host print")
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert any(f.rule == "unused-suppression" for f in stale.findings)


# ---------------------------------------------------------------------------
# snapshot-commit
# ---------------------------------------------------------------------------

class TestSnapshotCommit:
    def test_true_positive_raw_savez_and_replace_in_ckpt(self, tmp_path):
        report = _run(tmp_path, {
            "ckpt/rogue.py": """
                import json
                import os

                import numpy as np

                def hand_rolled_commit(target, arrays):
                    tmp = target + ".tmp"
                    np.savez(tmp, **arrays)
                    os.replace(tmp, target)
            """,
            "ckpt/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert len(report.findings) == 2
        kinds = {f.data[1] for f in report.findings}
        assert kinds == {"np.savez", "os.replace"}
        assert all(f.rule == "snapshot-commit" for f in report.findings)

    def test_true_positive_raw_json_dump_open_w(self, tmp_path):
        report = _run(tmp_path, {
            "ckpt/manifesto.py": """
                import json

                def write_manifest(path, manifest):
                    with open(path, "w") as f:
                        json.dump(manifest, f)
            """,
            "ckpt/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert len(report.findings) == 2  # the open(w) AND the dump
        assert {f.data[1] for f in report.findings} == {"open(..., 'w')", "json.dump"}

    def test_true_positive_os_rename_in_ckpt(self, tmp_path):
        report = _run(tmp_path, {
            "ckpt/mover.py": """
                import os

                def publish(tmp, target):
                    os.rename(tmp, target)
            """,
            "ckpt/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("write", "os.rename")

    def test_true_negative_atomic_commit_machinery(self, tmp_path):
        """The helper itself, inline-lambda payloads, AND named payload
        writers referenced from an atomic_commit call are all sanctioned;
        reads and deletes are not commits."""
        report = _run(tmp_path, {
            "ckpt/coordinator.py": """
                import json
                import os

                import numpy as np

                def atomic_commit(target, write_payload, *, site):
                    tmp = target + ".tmp"
                    write_payload(tmp)
                    os.replace(tmp, target)

                def _dump_json(tmp, manifest):
                    with open(tmp, "w") as f:
                        json.dump(manifest, f)

                def save(target, arrays, manifest):
                    atomic_commit(
                        target, lambda tmp: np.savez(tmp, **arrays), site="s"
                    )
                    atomic_commit(
                        target + ".json",
                        lambda tmp: _dump_json(tmp, manifest),
                        site="s",
                    )

                def gc(path):
                    os.remove(path)  # a delete is not a commit

                def read(path):
                    with open(path, "rb") as f:
                        return f.read()
            """,
            "ckpt/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert report.findings == []

    def test_true_negative_writes_outside_ckpt(self, tmp_path):
        report = _run(tmp_path, {
            "parallel/iteration.py": """
                import os

                import numpy as np

                def legacy_writer(target, leaves):
                    tmp = target + ".tmp"
                    np.savez(tmp, **leaves)
                    os.replace(tmp, target)
            """,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert report.findings == []

    def test_suppression_with_reason(self, tmp_path):
        report = _run(tmp_path, {
            "ckpt/debugdump.py": """
                import numpy as np

                def dump_for_postmortem(path, arrays):
                    # tpulint: disable=snapshot-commit -- postmortem scratch dump, never read back as a checkpoint
                    np.savez(path, **arrays)
            """,
            "ckpt/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["snapshot-commit"])
        assert report.findings == []
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------------
# untimed-wait
# ---------------------------------------------------------------------------

class TestUntimedWait:
    def test_true_positive_wait_join_and_channel_get(self, tmp_path):
        report = _run(tmp_path, {
            "parallel/runner.py": """
                import threading

                from ..flow import BoundedChannel

                def drive(items):
                    done = threading.Event()
                    ch = BoundedChannel(4)
                    worker = threading.Thread(target=lambda: None)
                    done.wait()
                    worker.join()
                    return ch.get()
            """,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert len(report.findings) == 3
        assert {f.data[0] for f in report.findings} == {"wait", "join", "get"}
        assert all(f.rule == "untimed-wait" for f in report.findings)

    def test_true_positive_queueish_name_without_constructor(self, tmp_path):
        report = _run(tmp_path, {
            "serving2.py": """
                def pull(window, results_queue):
                    a = window.get()
                    b = results_queue.get()
                    return a, b
            """,
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert len(report.findings) == 2

    def test_true_negative_timeouts_strings_dicts_contextvars(self, tmp_path):
        report = _run(tmp_path, {
            "parallel/clean.py": """
                import contextvars
                import threading

                _current = contextvars.ContextVar("cur", default=None)

                def drive(parts, table, remaining):
                    done = threading.Event()
                    worker = threading.Thread(target=lambda: None)
                    while not done.wait(0.1):
                        pass
                    worker.join(timeout=2.0)
                    sep = ", ".join(parts)
                    ctx = _current.get()
                    return table.get("key"), sep, ctx
            """,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert report.findings == []

    def test_timeout_none_is_still_untimed(self, tmp_path):
        report = _run(tmp_path, {
            "parallel/nonewait.py": """
                import threading

                def drive():
                    done = threading.Event()
                    done.wait(timeout=None)
            """,
            "parallel/__init__.py": "",
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("wait",)

    def test_flow_module_is_the_sanctioned_home(self, tmp_path):
        report = _run(tmp_path, {
            "flow.py": """
                import threading

                class Channel:
                    def block(self):
                        cv = threading.Condition()
                        with cv:
                            cv.wait()
            """,
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert report.findings == []

    def test_suppression_with_reason_and_stale_suppression(self, tmp_path):
        report = _run(tmp_path, {
            "serving3.py": """
                from .flow import BoundedChannel

                def pull(entry):
                    window = BoundedChannel(2)
                    if not window.offer(entry):
                        # tpulint: disable=untimed-wait -- offer() returned False, so the window is non-empty and get() cannot block
                        return window.get()
                    return None
            """,
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert report.findings == []
        assert len(report.suppressed) == 1
        stale = _run(tmp_path, {
            "serving4.py": """
                def pull(window):
                    # tpulint: disable=untimed-wait -- nothing here blocks
                    return window.credits()
            """,
            **LAZYJIT_STUB,
        }, ["untimed-wait"])
        assert any(f.rule == "unused-suppression" for f in stale.findings)

# ---------------------------------------------------------------------------
# unledgered-residency
# ---------------------------------------------------------------------------

class TestUnledgeredResidency:
    def test_true_positive_module_level_and_self_attr(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad.py": """
                import jax
                import jax.numpy as jnp

                LUT = jnp.arange(1024)

                class Model:
                    def publish(self, weights):
                        self._weights = jax.device_put(weights)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unledgered-residency"])
        assert len(report.findings) == 2
        by_binding = {f.data[1]: f.data[0] for f in report.findings}
        assert by_binding == {
            "module-level name": "jax.numpy.arange",
            "self._weights": "jax.device_put",
        }
        assert all(f.rule == "unledgered-residency" for f in report.findings)

    def test_true_positive_bare_import_and_from_jax_numpy(self, tmp_path):
        report = _run(tmp_path, {
            "models/bad2.py": """
                from jax import device_put
                from jax import numpy as jnp

                class Model:
                    def __init__(self, k, d):
                        self._centroids = jnp.zeros((k, d))

                    def publish(self, w):
                        self._w = device_put(w)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unledgered-residency"])
        creators = sorted(f.data[0] for f in report.findings)
        assert creators == ["jax.device_put", "jax.numpy.zeros"]

    def test_true_negative_transients_funnels_and_host_arrays(self, tmp_path):
        report = _run(tmp_path, {
            "models/good.py": """
                import jax.numpy as jnp
                import numpy as np

                from ..parallel import prefetch
                from ..obs import memledger

                HOST_TABLE = np.zeros(16)  # host memory, not HBM

                def step(X):
                    mask = jnp.ones(X.shape[0])  # function-local transient
                    return X * mask

                class Model:
                    def publish(self, weights):
                        # the accounted funnel ledgers this residency
                        self._weights = prefetch.stage_to_device(
                            weights, category="model"
                        )

                    def adopt(self, arrs):
                        self._arrs = memledger.track(arrs, "model")
            """,
            **LAZYJIT_STUB,
            "parallel/__init__.py": "",
            "obs/__init__.py": "",
            "models/__init__.py": "",
        }, ["unledgered-residency"])
        assert report.findings == []

    def test_suppression_with_reason_hides_finding(self, tmp_path):
        report = _run(tmp_path, {
            "models/tiny.py": """
                import jax.numpy as jnp

                class Probe:
                    def __init__(self):
                        # tpulint: disable=unledgered-residency -- 8-byte sentinel, below any budget's noise floor
                        self._sentinel = jnp.zeros(1)
            """,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["unledgered-residency"])
        assert report.findings == []
        assert len(report.suppressed) == 1

    def test_true_positive_raw_device_put_in_paging_helper(self, tmp_path):
        """A model-store-style paging helper that uploads with a raw
        `jax.device_put` bypasses the ledger: the resident model bytes
        never land in `hbm.live.model` (ISSUE 19 satellite)."""
        report = _run(tmp_path, {
            "data/badstore.py": """
                import jax

                class PagingStore:
                    def page_in_raw(self, key, host_arrays):
                        self._resident = jax.device_put(host_arrays)
                        return self._resident
            """,
            **LAZYJIT_STUB,
            "data/__init__.py": "",
        }, ["unledgered-residency"])
        assert len(report.findings) == 1
        assert report.findings[0].data == ("jax.device_put", "self._resident")

    def test_true_negative_model_store_page_in_funnel(self, tmp_path):
        """`ModelStore.page_in` is a sanctioned funnel: every byte it
        makes resident stages through `device_constants()` ->
        `stage_to_device(category="model")`, so bindings fed by it are
        ledgered by construction."""
        from flink_ml_tpu.analysis.rules.memledger import FUNNEL_CALLS

        assert "page_in" in FUNNEL_CALLS  # the ISSUE 19 sanction itself
        report = _run(tmp_path, {
            "data/goodstore.py": """
                import jax

                class Server:
                    def __init__(self, store):
                        self._store = store

                    def pin_tenant(self, key, fallback):
                        # resident + accounted: page_in rides the funnel
                        self._hot_entry = self._store.page_in(key)
                        return self._hot_entry

                    def pin_or_stage(self, key, fallback):
                        # funnel presence exempts the whole binding even
                        # with a raw constructor in the expression
                        self._entry = (
                            self._store.page_in(key)
                            if key in self._store
                            else jax.device_put(fallback)
                        )
                        return self._entry
            """,
            **LAZYJIT_STUB,
            "data/__init__.py": "",
        }, ["unledgered-residency"])
        assert report.findings == []


# ---------------------------------------------------------------------------
# vmap transparency: the fleet kernels wrap resident bodies in jax.vmap
# (fleet.py / ops/optimizer.py `_sgd_fleet_*`) — resident-program and
# spec-consistency must see THROUGH the batching wrapper: vmap changes
# the batch axis, not residency or reduction structure
# ---------------------------------------------------------------------------

class TestResidentProgramVmap:
    def test_true_positive_callback_in_vmapped_kernel(self, tmp_path):
        """`NAME = lazy_jit(jax.vmap(impl))` — the fleet-kernel binding
        idiom — is still ONE resident program; an in-body callback
        re-enters the host every epoch for every member."""
        report = _run(tmp_path, {
            "ops/fleetbad.py": """
                import jax
                import jax.numpy as jnp
                from jax import lax
                from ..utils.lazyjit import lazy_jit

                def _member_fit_impl(X, carry):
                    def step(state):
                        c, e = state
                        jax.debug.print("member epoch {e}", e=e)
                        return c + jnp.sum(X), e + 1
                    return lax.while_loop(lambda s: s[1] < 10, step, carry)

                _fleet_fit = lazy_jit(jax.vmap(_member_fit_impl))
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert len(report.findings) == 1
        assert "jax.debug.print" in report.findings[0].message

    def test_true_positive_callback_in_vmapped_loop_body(self, tmp_path):
        """A loop body handed to lax.while_loop THROUGH a vmap wrapper is
        resident for every fleet member."""
        report = _run(tmp_path, {
            "ops/fleetbad2.py": """
                import jax
                import jax.numpy as jnp
                from jax import lax
                from jax.experimental import io_callback

                def fleet_fit(X):
                    def cond(s):
                        return s < 5
                    def body(s):
                        io_callback(print, None, s)
                        return s + 1
                    return lax.while_loop(
                        jax.vmap(cond), jax.vmap(body), jnp.zeros(4))
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert len(report.findings) == 1
        assert "io_callback" in report.findings[0].message

    def test_true_negative_clean_vmapped_kernel(self, tmp_path):
        """A callback-free vmapped kernel with host-side logging OUTSIDE
        the program is the idiomatic fleet pattern — no finding."""
        report = _run(tmp_path, {
            "ops/fleetgood.py": """
                import jax
                import jax.numpy as jnp
                from jax import lax
                from ..utils.lazyjit import lazy_jit

                def _member_fit_impl(X, carry):
                    def step(state):
                        c, e = state
                        return c + jnp.sum(X), e + 1
                    return lax.while_loop(lambda s: s[1] < 10, step, carry)

                _fleet_fit = lazy_jit(jax.vmap(_member_fit_impl))

                def drive(X, carry):
                    out = _fleet_fit(X, carry)
                    print("fleet fit done")  # host side: fine
                    return out
            """,
            **LAZYJIT_STUB,
            "ops/__init__.py": "",
        }, ["resident-program"])
        assert report.findings == []


class TestSpecConsistencyVmap:
    def test_true_positive_unreduced_output_behind_vmap(self, tmp_path):
        """A vmapped shard_map body that never reduces still publishes a
        per-shard partial as the claimed-replicated result."""
        report = _run(tmp_path, {
            "models/fleetbad.py": """
                import jax
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def member(x):
                        return x * 2.0
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=jax.vmap(member))
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert len(report.findings) == 1
        assert report.findings[0].data[0] == "unreduced-output"

    def test_true_negative_reduced_vmapped_body(self, tmp_path):
        report = _run(tmp_path, {
            "models/fleetgood.py": """
                import jax
                from jax.sharding import PartitionSpec as P
                from ..parallel import collectives
                from ..parallel.mesh import DATA_AXIS

                def build(mesh):
                    def member(x):
                        return collectives.all_reduce_sum(x, DATA_AXIS)
                    return collectives.shard_map_over(
                        mesh, (P(DATA_AXIS),), P(), fn=jax.vmap(member))
            """,
            **SPMD_STUB,
            **LAZYJIT_STUB,
            "models/__init__.py": "",
        }, ["spec-consistency"])
        assert report.findings == []


# ---------------------------------------------------------------------------
# serve-path-trace
# ---------------------------------------------------------------------------

SERVING_ROOT = {
    "serving.py": """
        from .pipeline import PipelineModel

        class MicroBatchServer:
            def __init__(self, model):
                self.model = model

            def _dispatch(self, batch):
                return self.model.transform(batch)
    """,
    "__init__.py": "",
}


class TestServePathTrace:
    def test_true_positive_raw_jit_reachable_via_cha(self, tmp_path):
        report = _run(tmp_path, {
            **SERVING_ROOT,
            "pipeline.py": """
                import jax

                class PipelineModel:
                    def transform(self, batch):
                        fn = jax.jit(lambda x: x * 2.0)
                        return fn(batch)
            """,
            **LAZYJIT_STUB,
        }, ["serve-path-trace"])
        assert len(report.findings) == 1
        f = report.findings[0]
        assert f.path == "flink_ml_tpu/pipeline.py"
        assert f.data[0] == "raw-jit"
        assert "MicroBatchServer._dispatch" in f.message

    def test_true_positive_on_path_wrapper_construction(self, tmp_path):
        report = _run(tmp_path, {
            **SERVING_ROOT,
            "pipeline.py": """
                from .utils.lazyjit import lazy_jit

                class PipelineModel:
                    def transform(self, batch):
                        fn = lazy_jit(lambda x: x * 2.0)
                        return fn(batch)
            """,
            **LAZYJIT_STUB,
        }, ["serve-path-trace"])
        assert len(report.findings) == 1
        assert report.findings[0].data[0] == "on-path-wrapper"

    def test_true_negative_module_level_wrapper(self, tmp_path):
        report = _run(tmp_path, {
            **SERVING_ROOT,
            "pipeline.py": """
                from .utils.lazyjit import lazy_jit

                def _scale(x):
                    return x * 2.0

                _kernel = lazy_jit(_scale)

                class PipelineModel:
                    def transform(self, batch):
                        return _kernel(batch)
            """,
            **LAZYJIT_STUB,
        }, ["serve-path-trace"])
        assert report.findings == []

    def test_true_negative_training_path_raw_jit_unreachable(self, tmp_path):
        report = _run(tmp_path, {
            **SERVING_ROOT,
            "pipeline.py": """
                class PipelineModel:
                    def transform(self, batch):
                        return batch
            """,
            "trainer.py": """
                import jax

                def fit(X):
                    return jax.jit(lambda x: x.sum())(X)
            """,
            **LAZYJIT_STUB,
        }, ["serve-path-trace"])
        assert report.findings == []

    def test_suppression_with_reason_is_the_census_entry(self, tmp_path):
        report = _run(tmp_path, {
            **SERVING_ROOT,
            "pipeline.py": """
                import jax

                class PipelineModel:
                    def transform(self, batch):
                        # tpulint: disable=serve-path-trace -- bank-off fallback, one compile per plan
                        fn = jax.jit(lambda x: x * 2.0)
                        return fn(batch)
            """,
            **LAZYJIT_STUB,
        }, ["serve-path-trace"])
        assert report.findings == []
        assert len(report.suppressed) == 1
        assert report.suppressed[0].rule == "serve-path-trace"
