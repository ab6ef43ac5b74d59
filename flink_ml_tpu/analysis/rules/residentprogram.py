"""resident-program: host callbacks inside whole-fit program bodies.

The whole-fit work (parallel/dispatch.py, docs/performance.md "Whole-fit
resident programs") exists to make a fit exactly ONE host↔device round
trip — which a single ``io_callback`` / ``pure_callback`` /
``jax.debug.print`` / ``jax.debug.callback`` (or a stray builtin
``print``) inside the compiled loop silently destroys: each epoch of the
resident while-loop then re-enters the host, turning the one-dispatch
program back into a per-epoch host conversation that no counter
accounts (callbacks bypass the ``packed_device_get`` funnels AND the
``iteration.host_sync`` budget). The rule flags host-callback calls that
are lexically inside a resident program body:

- any **jitted kernel** function (a ``lazy_jit``/``keyed_jit``/``jax.jit``
  bound or decorated def — resolved through the shared ``_jitindex``,
  including the ``NAME = lazy_jit(_impl, ...)`` binding idiom, where the
  body is ``_impl``), nested defs included;
- any local function **passed to a lax loop/branch combinator**
  (``lax.while_loop`` / ``fori_loop`` / ``scan`` / ``cond`` / ``switch``
  / ``map``) anywhere in a scoped module — loop bodies are resident by
  construction even when the enclosing jit wrapper lives elsewhere.

``jax.debug.print`` during interactive debugging is legitimate — which is
exactly why a committed one takes a ``# tpulint: disable=resident-program
-- <why this callback must ship>`` suppression or gets deleted.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set

from ..engine import Finding, Rule, register
from ..source import SourceModule, dotted_name
from . import _jitindex

#: dotted-call suffixes that re-enter the host from inside a program
_CALLBACK_SUFFIXES = (
    "io_callback",
    "pure_callback",
    "debug.print",
    "debug.callback",
    "debug.breakpoint",
    "experimental.io_callback",
)

#: lax combinators whose function arguments become resident loop bodies
_LOOP_COMBINATORS = ("while_loop", "fori_loop", "scan", "cond", "switch", "map")


def _is_callback_call(node: ast.Call, info, imports: Dict[str, tuple]) -> str:
    """The callback's display name if `node` calls a host callback, else ''."""
    name = dotted_name(node.func)
    if name is None:
        return ""
    if name == "print":
        return "print"
    root, _, rest = name.partition(".")
    if root in info.jax_aliases and rest:
        for suffix in _CALLBACK_SUFFIXES:
            if rest == suffix or rest.endswith("." + suffix):
                return name
    # from jax.experimental import io_callback / from jax import pure_callback
    target = imports.get(root)
    if target is not None and rest == "":
        module, original = target
        if module.startswith("jax") and original in (
            "io_callback",
            "pure_callback",
        ):
            return f"{module}.{original}"
    return ""


def _is_vmap_call(node: ast.AST, info) -> bool:
    """True for `jax.vmap(...)` / `vmap(...)` (any jax alias / direct
    import) — a batching wrapper whose operand stays a resident body."""
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func)
    if name is None:
        return False
    root, _, rest = name.partition(".")
    if root in info.jax_aliases and rest == "vmap":
        return True
    target = info.imports.get(root)
    return (
        target is not None
        and rest == ""
        and target[0].startswith("jax")
        and target[1] == "vmap"
    )


def _unwrap_vmap_name(node: ast.AST, info) -> str:
    """The function NAME under any stack of vmap wrappers (`jax.vmap(f)`,
    `vmap(vmap(f))`, ...); '' when the operand is not a plain name.
    vmap changes batching, not residency — a vmapped while_loop body is
    still compiled into the one-dispatch program (fleet kernels)."""
    while _is_vmap_call(node, info):
        if not node.args:
            return ""
        node = node.args[0]
    return node.id if isinstance(node, ast.Name) else ""


def _loop_body_names(module: SourceModule, info) -> Set[str]:
    """Names of local functions passed positionally to a lax loop/branch
    combinator (their bodies run inside the compiled program) — seen
    through vmap wrappers (`lax.while_loop(vmap(cond), vmap(body), ...)`)."""
    names: Set[str] = set()
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = dotted_name(node.func)
        if fn is None:
            continue
        root, _, rest = fn.partition(".")
        is_lax = (root in info.lax_aliases and rest in _LOOP_COMBINATORS) or (
            root in info.jax_aliases
            and rest.startswith("lax.")
            and rest.split(".")[-1] in _LOOP_COMBINATORS
        )
        if not is_lax:
            continue
        for arg in node.args:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
            else:
                unwrapped = _unwrap_vmap_name(arg, info)
                if unwrapped:
                    names.add(unwrapped)
    return names


def _kernel_impl_names(module: SourceModule, info) -> Set[str]:
    """Function names whose defs ARE jitted-kernel bodies: decorated defs
    plus the first positional argument of a `NAME = lazy_jit(impl, ...)` /
    `jax.jit(impl, ...)` module-level binding — including a vmap-wrapped
    impl (`NAME = lazy_jit(jax.vmap(impl), ...)`, the fleet-kernel
    idiom)."""
    names: Set[str] = set(info.kernels)
    for node in module.tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id in info.kernels
            and isinstance(node.value, ast.Call)
            and node.value.args
        ):
            arg0 = node.value.args[0]
            if isinstance(arg0, ast.Name):
                names.add(arg0.id)
            else:
                unwrapped = _unwrap_vmap_name(arg0, info)
                if unwrapped:
                    names.add(unwrapped)
    return names


@register
class ResidentProgramRule(Rule):
    id = "resident-program"
    title = "host callback inside a resident (whole-fit) program body"
    rationale = (
        "A whole-fit resident program is ONE dispatch and ONE packed "
        "readback; an io_callback/pure_callback/jax.debug.print inside "
        "its loop body re-enters the host EVERY epoch — an unaccounted "
        "per-epoch sync that resurrects the dispatch wall the resident "
        "path exists to kill, invisible to hostSyncCount. Keep program "
        "bodies callback-free, or suppress WITH the reason the callback "
        "must ship."
    )
    example = "jax.debug.print('epoch {e}', e=epoch)  # inside a while_loop body"
    scope = ("flink_ml_tpu",)

    def check_module(
        self, project, module: SourceModule
    ) -> Iterable[Finding]:
        if module.tree is None:
            return ()
        info = _jitindex.jit_index(project)[module.path]
        resident_names = _kernel_impl_names(module, info) | _loop_body_names(
            module, info
        )
        findings: List[Finding] = []
        seen = set()

        def scan(fn_node: ast.AST, owner: str) -> None:
            for node in ast.walk(fn_node):
                if not isinstance(node, ast.Call):
                    continue
                callback = _is_callback_call(node, info, info.imports)
                if not callback:
                    continue
                key = (node.lineno, callback)
                if key in seen:
                    continue
                seen.add(key)
                findings.append(
                    Finding(
                        path=module.path,
                        line=node.lineno,
                        rule=self.id,
                        message=(
                            f"{callback} inside resident program body "
                            f"{owner}() re-enters the host every epoch — "
                            "an unaccounted per-epoch sync inside a "
                            "one-dispatch program; move it outside the "
                            "compiled loop or suppress with the reason "
                            "it must ship"
                        ),
                        data=("callback", callback, owner),
                    )
                )

        for node in ast.walk(module.tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in resident_names
            ):
                scan(node, node.name)
        return findings
