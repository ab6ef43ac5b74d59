"""serve-path-trace: the statically-checked no-compile serving SLA.

The AOT program bank (compilebank.py, docs/performance.md §12) promises
that a warmed serving process never traces or compiles on the request
path: every kernel the dispatch path can reach must route through a
bank-consulting funnel (``utils/lazyjit.py`` or ``compilebank.py``), so
that a bank hit is a warm-loaded executable call and the zero-trace CI
pin (scripts/coldstart_smoke.py) holds by construction, not by luck.

This rule walks the v2 call graph from the serving dispatch roots
(``MicroBatchServer`` and ``serve_stream``) and flags, in any reachable
function outside the sanctioned funnel modules:

- **raw ``jax.jit``** — a trace site the bank cannot see. The
  ``FusedSegment`` bank-off fallback is the one legitimate case and
  carries a suppression-with-reason (the census entry the acceptance
  criteria allow).
- **``lazy_jit``/``keyed_jit`` wrapper construction inside a reachable
  function body** — module-level wrappers are built at import time and
  consult the bank per call, but a wrapper constructed *on* the dispatch
  path traces on its first call mid-request, busting the SLA.

Reachability is an over-approximation on the serving surface: direct
resolution (module-level calls, one-hop imports, ``self.`` methods) via
``callgraph.CallGraph.resolve``, plus class-hierarchy lifting for
attribute calls — ``x.m(...)`` reaches every method named ``m`` declared
in the serving-path module set below. Over-approximate reachability +
exact trace-site matching keeps the rule sound for the SLA: a real trace
site on the path cannot hide behind an unresolvable receiver.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from .. import callgraph
from ..engine import Finding, Rule, register
from . import _jitindex

#: the dispatch-path entry points the SLA protects
ROOTS = (
    ("flink_ml_tpu/serving.py", "MicroBatchServer."),
    ("flink_ml_tpu/serving.py", "serve_stream"),
)

#: modules whose classes participate in attribute-call (CHA) lifting —
#: the serving dispatch surface
CHA_MODULES = (
    "flink_ml_tpu/serving.py",
    "flink_ml_tpu/pipeline.py",
    "flink_ml_tpu/table.py",
    "flink_ml_tpu/api.py",
    "flink_ml_tpu/lifecycle.py",
    "flink_ml_tpu/data/modelstore.py",
    "flink_ml_tpu/parallel/prefetch.py",
    "flink_ml_tpu/utils/packing.py",
)

#: the bank-consulting funnels: trace sites INSIDE these are the
#: SLA's implementation, not violations of it
SANCTIONED = (
    "flink_ml_tpu/utils/lazyjit.py",
    "flink_ml_tpu/compilebank.py",
)


@register
class ServePathTraceRule(Rule):
    id = "serve-path-trace"
    title = "trace site reachable from the serving dispatch path"
    rationale = (
        "The no-compile serving SLA (docs/performance.md §12) requires "
        "every kernel reachable from MicroBatchServer's dispatch path to "
        "route through the bank-consulting funnels (utils/lazyjit.py, "
        "compilebank.py). A raw jax.jit or an on-path wrapper "
        "construction is a trace site the AOT program bank cannot "
        "satisfy — the first request that touches it traces and "
        "compiles mid-flight, which is exactly the dishonest-p999 "
        "cold start the bank exists to kill."
    )
    example = "self._jit = jax.jit(self._run)  # reachable from _dispatch"
    scope = ("flink_ml_tpu",)
    exclude = SANCTIONED

    def check_project(self, project) -> Iterable[Finding]:
        graph = callgraph.get(project)
        jitindex = _jitindex.jit_index(project)
        cha = _cha_index(graph)
        reachable = _reachable(project, graph, cha)
        findings: List[Finding] = []
        for (path, qualname), chain in sorted(reachable.items()):
            if any(path == s for s in SANCTIONED):
                continue
            decl = graph.by_module.get(path, {}).get(qualname)
            module = project.module_at(path)
            if decl is None or module is None:
                continue
            info = jitindex.get(path)
            findings.extend(
                self._trace_sites(module, info, decl, chain)
            )
        return findings

    def _trace_sites(self, module, info, decl, chain: str) -> List[Finding]:
        findings: List[Finding] = []
        via = f" (reached via {chain})" if chain else ""
        for node in ast.walk(decl.node):
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "jit"
                and isinstance(node.value, ast.Name)
                and node.value.id in info.jax_aliases
            ):
                findings.append(
                    Finding(
                        path=module.path,
                        line=node.lineno,
                        rule=self.id,
                        message=(
                            f"raw jax.jit in {decl.qualname} is reachable "
                            "from the serving dispatch path but not proven "
                            "bank-resolvable — route through the "
                            "lazyjit/compilebank funnels or suppress with "
                            f"a reason{via}"
                        ),
                        data=("raw-jit", decl.qualname),
                    )
                )
            elif isinstance(node, ast.Call):
                name = callgraph.dotted_name(node.func)
                if name is not None and (
                    name in info.lazy_jit_names or name in info.keyed_jit_names
                ):
                    findings.append(
                        Finding(
                            path=module.path,
                            line=node.lineno,
                            rule=self.id,
                            message=(
                                f"{name} wrapper constructed inside "
                                f"{decl.qualname} on the serving dispatch "
                                "path — its first call traces mid-request; "
                                "hoist the wrapper to module scope so the "
                                f"bank can warm it{via}"
                            ),
                            data=("on-path-wrapper", decl.qualname),
                        )
                    )
        return findings


def _cha_index(graph) -> Dict[str, List]:
    """method name -> decls with that name across the serving-surface
    modules (class-hierarchy lifting for attribute calls)."""
    index: Dict[str, List] = {}
    for path in CHA_MODULES:
        for qualname, decl in graph.by_module.get(path, {}).items():
            method = qualname.rsplit(".", 1)[-1]
            index.setdefault(method, []).append(decl)
    return index


def _reachable(project, graph, cha) -> Dict[Tuple[str, str], str]:
    """BFS over the call graph from the serving roots: decl key ->
    discovery chain (root-first qualname path, for finding messages)."""
    seen: Dict[Tuple[str, str], str] = {}
    queue: List[Tuple] = []
    for root_path, prefix in ROOTS:
        for qualname, decl in graph.by_module.get(root_path, {}).items():
            if qualname == prefix or qualname.startswith(prefix):
                seen[decl.key] = ""
                queue.append(decl)
    while queue:
        decl = queue.pop()
        module = project.module_at(decl.path)
        if module is None:
            continue
        chain = seen[decl.key]
        child_chain = f"{chain} -> {decl.qualname}" if chain else decl.qualname
        current_class = (
            decl.qualname.split(".")[0] if decl.is_method else None
        )
        callees: List = []
        attr_names: Set[str] = set()
        for node in ast.walk(decl.node):
            if not isinstance(node, ast.Call):
                continue
            resolved = graph.resolve(module, node.func, current_class)
            if resolved is not None:
                callees.append(resolved[0])
            elif isinstance(node.func, ast.Attribute):
                attr_names.add(node.func.attr)
        for name in attr_names:
            callees.extend(cha.get(name, ()))
        for callee in callees:
            if callee.key not in seen:
                seen[callee.key] = child_chain
                queue.append(callee)
    return seen
