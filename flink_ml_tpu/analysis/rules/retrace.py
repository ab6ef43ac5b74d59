"""retrace-hazard: compile-cache-busting jit usage.

A fit is one dispatch and one readback, so every stray recompile is a
wall-clock cliff: a single retrace plus XLA compile costs seconds, and a
jit wrapper constructed per call retraces on *every* call. The rule pins three hazard shapes:

- **raw ``jax.jit``** anywhere outside ``utils/lazyjit.py``: even when a
  module-level wrapper reuses its cache, it bypasses the ``jit.kernels``
  counter (and the hook install) that keeps compile accounting
  exhaustive — route through ``lazy_jit`` / ``keyed_jit``.
- **jitted closures over local state**: ``lazy_jit``/``jax.jit`` applied
  (inside a function) to a lambda or nested def that captures enclosing
  locals — a NEW wrapper per outer call, so nothing is ever reused, and
  hyperparameters captured as closure constants force a retrace per
  value (the packed-hparam vector exists precisely to make them runtime
  operands).
- **non-hashable static args**: f-strings or dict displays feeding
  ``static_argnums``/``static_argnames`` values — every call builds a
  fresh static key (or fails to hash), so the compile cache never hits.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Set

from ..engine import Finding, Rule, register
from ..source import SourceModule, dotted_name
from . import _jitindex


def _assigned_names(node: ast.AST) -> Set[str]:
    """Names bound anywhere inside ``node`` (params, assignments, defs)."""
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            out.add(sub.id)
        elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            out.add(sub.name)
        elif isinstance(sub, ast.arg):
            out.add(sub.arg)
    return out


def _loaded_names(node: ast.AST) -> Set[str]:
    return {
        sub.id
        for sub in ast.walk(node)
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load)
    }


@register
class RetraceHazardRule(Rule):
    id = "retrace-hazard"
    title = "jit usage that busts the compile cache or its accounting"
    rationale = (
        "A jit wrapper constructed per call retraces and recompiles per "
        "call (seconds each), and raw jax.jit — even "
        "module-level — bypasses the jit.kernels counter that keeps "
        "compile accounting exhaustive. Route kernels through "
        "utils/lazyjit.py; pack hyperparameters into runtime operands "
        "instead of closure constants; keep static_argnums keys hashable "
        "and stable."
    )
    example = "fn = jax.jit(step)  # use lazy_jit(step) — counted + reused"
    scope = ("flink_ml_tpu",)
    # the two accounted jit funnels: lazyjit installs the compile hooks
    # and counts kernels/traces; compilebank AOT-compiles through the
    # same traced wrappers (its jit.jit().lower().compile() is the bank
    # backfill path, accounted under bank.* + jit.traces)
    exclude = (
        "flink_ml_tpu/utils/lazyjit.py",
        "flink_ml_tpu/compilebank.py",
    )

    def check_module(
        self, project, module: SourceModule
    ) -> Iterable[Finding]:
        if module.tree is None:
            return ()
        info = _jitindex.jit_index(project)[module.path]
        findings: List[Finding] = []

        # --- raw jax.jit references ---------------------------------------
        for node in ast.walk(module.tree):
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
                            "raw jax.jit bypasses utils/lazyjit.py — the "
                            "jit.kernels counter (and hook install) misses "
                            "this wrapper; use lazy_jit/keyed_jit"
                        ),
                        data=("raw-jit",),
                    )
                )

        # --- non-hashable static_argnums feeds ----------------------------
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg not in ("static_argnums", "static_argnames"):
                    continue
                for sub in ast.walk(kw.value):
                    if isinstance(sub, (ast.JoinedStr, ast.Dict, ast.DictComp)):
                        findings.append(
                            Finding(
                                path=module.path,
                                line=sub.lineno,
                                rule=self.id,
                                message=(
                                    f"{kw.arg} fed a "
                                    f"{'f-string' if isinstance(sub, ast.JoinedStr) else 'dict'}"
                                    " — per-call static keys never hit the "
                                    "compile cache"
                                ),
                                data=("static-key",),
                            )
                        )

        # --- jitted closures over enclosing locals ------------------------
        # each call is judged against its INNERMOST enclosing function so
        # nested defs don't double-report
        for node, func in _calls_with_enclosing_function(module.tree):
            if not node.args:
                continue
            is_jit = info.is_jit_callable(node.func) or (
                dotted_name(node.func) in ("partial", "functools.partial")
                and info.is_jit_callable(node.args[0])
            )
            if not is_jit:
                continue
            wrapped = node.args[0]
            local_defs = {
                n.name: n
                for n in ast.walk(func)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n is not func
            }
            if isinstance(wrapped, ast.Lambda):
                target = wrapped
            elif isinstance(wrapped, ast.Name) and wrapped.id in local_defs:
                target = local_defs[wrapped.id]
            else:
                continue
            captured = (
                _loaded_names(target) - _assigned_names(target)
            ) & _assigned_names(func)
            if captured:
                findings.append(
                    Finding(
                        path=module.path,
                        line=node.lineno,
                        rule=self.id,
                        message=(
                            "jitted closure captures enclosing locals "
                            f"({', '.join(sorted(captured)[:4])}) — a new "
                            "wrapper traces per outer call; hoist the "
                            "kernel to module scope and pass captured "
                            "state as (packed) runtime operands"
                        ),
                        data=("closure",),
                    )
                )
        return findings


def _calls_with_enclosing_function(tree: ast.AST):
    """(Call, innermost enclosing FunctionDef) pairs, each call once."""
    out = []

    def visit(node: ast.AST, func) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func = node
        if isinstance(node, ast.Call) and func is not None:
            out.append((node, func))
        for child in ast.iter_child_nodes(node):
            visit(child, func)

    visit(tree, None)
    return out
