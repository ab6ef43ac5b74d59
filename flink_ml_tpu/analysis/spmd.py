"""Abstract sharding interpreter — the SPMD layer under tpulint v3.

ROADMAP item 1 rebuilds ``parallel/mesh.py`` into a ``(data, feature)``
2D mesh with feature-sharded weights and axis-restricted collectives.
That is the class of change where a wrong axis name, a collective under
a rank-dependent branch, or an ``out_specs`` that lies about a reduction
produces *silent numeric corruption* or a *multi-host deadlock* — not a
test failure. This module gives the rule layer the semantic facts those
hazards are made of:

- **Axis registry** (:func:`axis_registry`): every mesh-axis constant
  declared in ``parallel/mesh.py`` (``DATA_AXIS = "data"``-style
  module-level string assigns) plus every axis literal a ``create_mesh``
  / ``Mesh`` construction introduces. The constants are the single
  source of truth for axis names; a literal that matches one is a
  *constant bypass*, a literal that matches none is an *unknown axis*.
- **Collective index** (:func:`collective_index`): the accounted wrapper
  functions in ``parallel/collectives.py`` (any module-level def with an
  ``axis_name`` parameter), classified as ``reduce`` / ``gather`` /
  ``permute`` / ``index`` by the raw ``lax`` primitive in their body
  (name-based fallback), with the axis parameter's position and default.
  Raw ``lax.psum``-family calls are indexed too, so the interpreter sees
  collectives with or without the wrapper layer.
- **Spec parsing** (:func:`parse_spec_expr`): ``PartitionSpec`` /
  ``P(...)`` expressions to abstract per-dim axis tuples, following
  local names one assignment deep (the ``batched = P(None, axis, None)``
  idiom) and resolving axis constants through module aliases
  (``mesh_lib.DATA_AXIS``).
- **The interpreter** (:class:`BodyInterpreter`): walks each
  ``shard_map``-ped body with an abstract value per name — the set of
  mesh axes the value *varies over* (sharded data, per-shard partial
  sums, ``axis_index`` results), or ``unknown`` when a spec could not be
  resolved (unknown suppresses findings; the engine under-approximates,
  same discipline as the taint walker). Collectives transform the
  variance set (a reduce/gather over axis *a* makes the result uniform
  along *a*); ``lax.while_loop``/``cond``/``scan`` bodies are run to a
  small join fixpoint before one recording pass; local and one-hop
  cross-module calls are interpreted inline (bounded depth), unknown
  calls join their arguments' variance.

Everything is exposed as one memoized :class:`SpmdInterpretation` per
project (``project.index("spmd", interpret)``) holding typed
:class:`SpmdEvent` records; the four v3 rules (``mesh-axis``,
``collective-divergence``, ``spec-consistency``,
``precision-determinism`` in ``rules/``) are thin filters over the
event stream, so all four agree on what a collective, an axis, and a
spec are. docs/static_analysis.md carries the rule catalogue and the
2D-mesh readiness checklist this gates.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

from .source import SourceModule, dotted_name

MESH_PATH = "flink_ml_tpu/parallel/mesh.py"
COLLECTIVES_PATH = "flink_ml_tpu/parallel/collectives.py"

#: modules whose hand-rolled reduction folds are the sanctioned,
#: replica-order implementations (the sparse scatter-partial fold lives
#: here; anywhere else a manual fold over collective results reassociates
#: the sum)
SANCTIONED_FOLD_PATHS = (COLLECTIVES_PATH,)

#: raw lax primitive -> (kind, axis positional index)
LAX_COLLECTIVES = {
    "psum": ("reduce", 1),
    "pmean": ("reduce", 1),
    "pmax": ("reduce", 1),
    "pmin": ("reduce", 1),
    "psum_scatter": ("reduce", 1),
    "all_gather": ("gather", 1),
    "all_to_all": ("gather", 1),
    "ppermute": ("permute", 1),
    "axis_index": ("index", 0),
    "axis_size": ("size", 0),
}

#: body-scan classification priority (a wrapper whose body mixes
#: primitives is named for the strongest semantic it applies)
_KIND_PRIORITY = ("reduce", "gather", "permute", "index", "size")

#: wrapper-name fallbacks when the body gives no primitive away
WRAPPER_NAME_KINDS = (
    ("all_reduce", "reduce"),
    ("reduce_scatter", "reduce"),
    ("sparse_all_reduce", "reduce"),
    ("all_gather", "gather"),
    ("ppermute", "permute"),
    ("axis_index", "index"),
    ("axis_size", "size"),
)

#: dtypes whose use as an accumulator/reduction operand narrows any
#: float32 operand — the implicit-downcast-before-psum hazard
NARROW_DTYPES = {"bfloat16", "float16", "int8", "uint8", "float8_e4m3fn", "float8_e5m2"}

#: sentinel for "could not resolve" — suppresses findings downstream
UNKNOWN = object()

#: bounded interpretation depth for inlined calls
MAX_DEPTH = 4
#: join-fixpoint iterations for loop carries before the recording pass
FIXPOINT_ROUNDS = 3


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SpmdEvent:
    """One semantic fact the rules turn into findings.

    Kinds:
      ``unknown-axis``        — axis literal not declared by any mesh constant
      ``axis-bypass``         — axis literal that duplicates a named constant
      ``unsharded-collective``— gather/permute over an axis the operand does
                                not vary on
      ``divergent-collective``— collective reachable under a shard-varying
                                branch inside a shard_map body
      ``double-reduce``       — reduction over an axis the operand is already
                                uniform on (double-counting)
      ``unreduced-output``    — out_spec declares replicated but the returned
                                value still varies over mesh axes
      ``spec-arity``          — in_specs arity does not match the body params
      ``downcast-before-reduce`` — narrowed dtype feeds a reduction
      ``order-fold``          — manual accumulation of permuted shards outside
                                parallel/collectives.py
    """

    path: str
    line: int
    kind: str
    detail: str = ""  # axis name / op name / dtype, rule-specific
    extra: Tuple = ()  # structured payload (site line, branch line, ...)


# ---------------------------------------------------------------------------
# axis registry
# ---------------------------------------------------------------------------

@dataclass
class AxisRegistry:
    #: (module_name, NAME) -> axis string, e.g. (…parallel.mesh, DATA_AXIS)
    constants: Dict[Tuple[str, str], str] = field(default_factory=dict)
    #: axis string -> constant NAME (for bypass messages)
    by_value: Dict[str, str] = field(default_factory=dict)
    #: every axis name any mesh declaration can produce
    known_axes: Set[str] = field(default_factory=set)

    def constant_value(self, module_name: str, name: str) -> Optional[str]:
        return self.constants.get((module_name, name))


def _build_axis_registry(project) -> AxisRegistry:
    reg = AxisRegistry()
    mesh = project.module_at(MESH_PATH)
    for source_mod in (mesh, project.module_at(COLLECTIVES_PATH)):
        if source_mod is None or source_mod.tree is None:
            continue
        for node in source_mod.tree.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.endswith("_AXIS")
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                name, value = node.targets[0].id, node.value.value
                reg.constants[(source_mod.module_name, name)] = value
                reg.by_value.setdefault(value, name)
                reg.known_axes.add(value)
    # re-exports: `from .mesh import DATA_AXIS` binds the constant in the
    # importing module under the same (or aliased) name
    for module in project.modules:
        if module.tree is None:
            continue
        from .source import resolve_relative_import

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            target = resolve_relative_import(
                module.module_name, node, module.is_package
            )
            if target is None:
                continue
            for alias in node.names:
                value = reg.constants.get((target, alias.name))
                if value is not None:
                    bound = alias.asname or alias.name
                    reg.constants[(module.module_name, bound)] = value
    return reg


def axis_registry(project) -> AxisRegistry:
    return project.index("spmd-axes", _build_axis_registry)


# ---------------------------------------------------------------------------
# collective index
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CollectiveWrapper:
    name: str
    kind: str  # reduce | gather | permute | index
    axis_param: int  # positional index of axis_name in the signature
    default_axis: Optional[str]  # resolved default, None when required/unknown
    operand_params: Tuple[int, ...] = (0,)  # positions of reduced operands


def _wrapper_kind(name: str, node: ast.FunctionDef) -> Optional[str]:
    # the wrapper NAME is the API contract — classify by it first
    # (all_reduce_sum_chunked's body opens with axis_size, not psum)
    for prefix, kind in WRAPPER_NAME_KINDS:
        if name.lstrip("_").startswith(prefix):
            return kind
    found: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            called = dotted_name(sub.func)
            if called is None:
                continue
            base = called.split(".")[-1]
            if base in LAX_COLLECTIVES:
                found.add(LAX_COLLECTIVES[base][0])
    for kind in _KIND_PRIORITY:
        if kind in found:
            return kind
    return None


def _build_collective_index(project) -> Dict[str, CollectiveWrapper]:
    out: Dict[str, CollectiveWrapper] = {}
    module = project.module_at(COLLECTIVES_PATH)
    if module is None or module.tree is None:
        return out
    reg = axis_registry(project)
    for node in module.tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        params = [a.arg for a in node.args.args]
        if "axis_name" not in params:
            continue
        kind = _wrapper_kind(node.name, node)
        if kind is None:
            continue
        axis_param = params.index("axis_name")
        default_axis = None
        defaults = node.args.defaults
        if defaults:
            offset = len(params) - len(defaults)
            if axis_param >= offset:
                default = defaults[axis_param - offset]
                if isinstance(default, ast.Constant) and isinstance(
                    default.value, str
                ):
                    default_axis = default.value
                elif isinstance(default, ast.Name):
                    default_axis = reg.constant_value(
                        module.module_name, default.id
                    )
        operands: Tuple[int, ...] = (0,)
        if node.name == "sparse_all_reduce_sum":
            operands = (0, 1)
        out[node.name] = CollectiveWrapper(
            name=node.name,
            kind=kind,
            axis_param=axis_param,
            default_axis=default_axis,
            operand_params=operands,
        )
    return out


def collective_index(project) -> Dict[str, CollectiveWrapper]:
    return project.index("spmd-collectives", _build_collective_index)


# ---------------------------------------------------------------------------
# per-module resolution context
# ---------------------------------------------------------------------------

class ModuleContext:
    """Resolution facts for one module: jit/alias info, the axis
    registry, and which local names denote the collective wrappers."""

    def __init__(self, project, module: SourceModule):
        from .rules import _jitindex

        self.project = project
        self.module = module
        self.info = _jitindex.jit_index(project)[module.path]
        self.axes = axis_registry(project)
        self.wrappers = collective_index(project)
        self.is_collectives_module = module.path == COLLECTIVES_PATH

    # -- collective call recognition ----------------------------------------
    def collective_for(self, call: ast.Call) -> Optional[Tuple[str, str, int, Optional[str], Tuple[int, ...]]]:
        """``(op_name, kind, axis_param, default_axis, operand_params)``
        when ``call`` is a collective — a wrapper from collectives.py
        (called locally, via a from-import, or via a module alias) or a
        raw ``lax`` primitive."""
        name = dotted_name(call.func)
        if name is None:
            return None
        root, _, rest = name.partition(".")
        base = name.split(".")[-1]
        # raw lax primitive
        if rest and root in self.info.lax_aliases and base in LAX_COLLECTIVES:
            kind, axis_param = LAX_COLLECTIVES[base]
            return (base, kind, axis_param, None, (0,))
        if (
            not rest
            and base in LAX_COLLECTIVES
            and self._imported_from(base, "jax.lax")
        ):
            kind, axis_param = LAX_COLLECTIVES[base]
            return (base, kind, axis_param, None, (0,))
        # wrapper, by any route that reaches collectives.py
        if base in self.wrappers and self._names_wrapper(name, base):
            w = self.wrappers[base]
            return (w.name, w.kind, w.axis_param, w.default_axis, w.operand_params)
        return None

    def _imported_from(self, bound: str, target_module: str) -> bool:
        entry = self.info.imports.get(bound)
        return entry is not None and entry[0] == target_module

    def _names_wrapper(self, name: str, base: str) -> bool:
        if self.is_collectives_module and name == base:
            return True
        root, _, rest = name.partition(".")
        if not rest:
            entry = self.info.imports.get(name)
            return entry is not None and (
                entry[0] == "flink_ml_tpu.parallel.collectives"
                or entry[0].endswith("parallel.collectives")
            )
        entry = self.info.imports.get(root)
        if entry is None:
            return False
        dotted = f"{entry[0]}.{entry[1]}"
        return dotted.endswith("parallel.collectives")

    # -- axis expression resolution -----------------------------------------
    def resolve_axis(
        self, node: ast.AST, local_env: Optional[Dict[str, ast.AST]] = None
    ):
        """``("literal", value, line)`` for a string literal,
        ``("const", value)`` when the expression denotes a declared axis
        constant, else None (parameter / unresolvable)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return ("literal", node.value, node.lineno)
        if isinstance(node, ast.Name):
            value = self.axes.constant_value(self.module.module_name, node.id)
            if value is not None:
                return ("const", value)
            if local_env and node.id in local_env:
                target = local_env[node.id]
                if target is not node:
                    return self.resolve_axis(target, None)
            return None
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
            entry = self.info.imports.get(node.value.id)
            if entry is not None:
                target_module = f"{entry[0]}.{entry[1]}"
                value = self.axes.constants.get((target_module, node.attr))
                if value is None:
                    value = self.axes.constants.get((entry[0], node.attr))
                if value is not None:
                    return ("const", value)
        return None


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------

def _is_partition_spec_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func)
    if name is None:
        return False
    base = name.split(".")[-1]
    return base in ("P", "PartitionSpec")


def parse_spec_expr(
    ctx: ModuleContext, node: ast.AST, local_env: Dict[str, ast.AST]
):
    """Parse a specs expression into the abstract form the interpreter
    consumes: a ``P(...)`` call becomes a tuple of per-dim entries (axis
    string, None, or UNKNOWN); a tuple/list of specs becomes a tuple of
    parsed specs; anything unresolvable is UNKNOWN."""
    if isinstance(node, ast.Name) and node.id in local_env:
        target = local_env[node.id]
        if target is not node:
            return parse_spec_expr(ctx, target, local_env)
        return UNKNOWN
    if _is_partition_spec_call(node):
        entries: List = []
        for arg in node.args:
            if isinstance(arg, ast.Constant) and arg.value is None:
                entries.append(None)
                continue
            if isinstance(arg, (ast.Tuple, ast.List)):
                sub = []
                for elt in arg.elts:
                    resolved = ctx.resolve_axis(elt, local_env)
                    sub.append(resolved[1] if resolved else UNKNOWN)
                entries.append(tuple(sub))
                continue
            resolved = ctx.resolve_axis(arg, local_env)
            entries.append(resolved[1] if resolved else UNKNOWN)
        return ("spec", tuple(entries))
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(parse_spec_expr(ctx, elt, local_env) for elt in node.elts)
    return UNKNOWN


def spec_axes(spec) -> Optional[FrozenSet[str]]:
    """Axes a parsed spec shards over; None when the spec is UNKNOWN
    anywhere (suppresses downstream findings)."""
    if spec is UNKNOWN:
        return None
    if isinstance(spec, tuple) and spec and spec[0] == "spec":
        axes: Set[str] = set()
        for entry in spec[1]:
            if entry is None:
                continue
            if entry is UNKNOWN:
                return None
            if isinstance(entry, tuple):
                for sub in entry:
                    if sub is UNKNOWN:
                        return None
                    axes.add(sub)
            else:
                axes.add(entry)
        return frozenset(axes)
    return None


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AbsVal:
    """What the interpreter knows about a value: the mesh axes it varies
    over (empty = uniform across every shard), whether anything along
    the way was unresolvable (unknown poisons — no findings), and
    provenance flags for the precision rule."""

    axes: FrozenSet[str] = frozenset()
    unknown: bool = False
    narrowed: Optional[str] = None  # dtype name set by a narrowing astype
    permuted: bool = False  # derives from a ppermute result

    def join(self, other: "AbsVal") -> "AbsVal":
        return AbsVal(
            axes=self.axes | other.axes,
            unknown=self.unknown or other.unknown,
            narrowed=self.narrowed or other.narrowed,
            permuted=self.permuted or other.permuted,
        )


UNIFORM = AbsVal()
UNKNOWN_VAL = AbsVal(unknown=True)


class TupleVal:
    """Tuple-structured abstract value (loop carries, multi-returns)."""

    __slots__ = ("elts",)

    def __init__(self, elts: Sequence):
        self.elts = list(elts)

    def collapse(self) -> AbsVal:
        out = UNIFORM
        for e in self.elts:
            out = out.join(e.collapse() if isinstance(e, TupleVal) else e)
        return out

    def join(self, other):
        if isinstance(other, TupleVal) and len(other.elts) == len(self.elts):
            return TupleVal(
                [_join(a, b) for a, b in zip(self.elts, other.elts)]
            )
        return self.collapse().join(
            other.collapse() if isinstance(other, TupleVal) else other
        )

    def __eq__(self, other):
        return isinstance(other, TupleVal) and self.elts == other.elts

    def __hash__(self):  # pragma: no cover - not used as dict key
        return hash(tuple(self.elts))


def _join(a, b):
    if isinstance(a, TupleVal):
        return a.join(b)
    if isinstance(b, TupleVal):
        return b.join(a)
    return a.join(b)


def _scalar(v) -> AbsVal:
    return v.collapse() if isinstance(v, TupleVal) else v


def spec_to_absval(spec) -> object:
    """Abstract value of a parameter bound with ``spec``."""
    if spec is UNKNOWN:
        return UNKNOWN_VAL
    if isinstance(spec, tuple) and spec and spec[0] == "spec":
        axes = spec_axes(spec)
        if axes is None:
            return UNKNOWN_VAL
        return AbsVal(axes=axes)
    if isinstance(spec, tuple):  # tuple of specs -> tuple-structured param
        return TupleVal([spec_to_absval(s) for s in spec])
    return UNKNOWN_VAL


# attribute reads returning host metadata (uniform across shards)
_META_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "itemsize", "sharding"}


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

class BodyInterpreter:
    """Abstract walk of one function body under per-shard semantics.

    ``record=False`` runs a join pass (loop-carry fixpointing) without
    emitting events; the driver runs a few join rounds, then one
    recording pass against the stabilized environment.
    """

    def __init__(
        self,
        ctx: ModuleContext,
        fn: ast.FunctionDef,
        arg_vals: Sequence,
        events: List[SpmdEvent],
        local_env: Dict[str, ast.AST],
        depth: int = 0,
        record: bool = True,
        divergent: Optional[Tuple[int, str]] = None,
        seen: Optional[Set[Tuple[str, str]]] = None,
        closure_env: Optional[Dict[str, object]] = None,
        closure_defs: Optional[Dict[str, ast.FunctionDef]] = None,
    ):
        self.ctx = ctx
        self.fn = fn
        self.events = events
        self.local_env = local_env
        self.depth = depth
        self.record = record
        #: (branch line, reason) when inside a shard-varying branch
        self.divergent = divergent
        self.seen = seen if seen is not None else set()
        # lexical scoping: a nested def (branch fn, local helper) reads its
        # enclosing scope's names — seed from the parent env, params shadow
        self.env: Dict[str, object] = dict(closure_env or {})
        params = [a.arg for a in fn.args.args]
        if params and params[0] in ("self", "cls"):
            params = params[1:]
        for i, name in enumerate(params):
            self.env[name] = arg_vals[i] if i < len(arg_vals) else UNKNOWN_VAL
        self.returns: List[Tuple[object, int]] = []
        self._local_defs = dict(closure_defs or {})
        self._local_defs.update(
            {n.name: n for n in ast.walk(fn) if isinstance(n, ast.FunctionDef)}
        )

    # -- events -------------------------------------------------------------
    def emit(self, line: int, kind: str, detail: str = "", extra: Tuple = ()):
        if self.record:
            self.events.append(
                SpmdEvent(
                    path=self.ctx.module.path,
                    line=line,
                    kind=kind,
                    detail=detail,
                    extra=extra,
                )
            )

    # -- evaluation ---------------------------------------------------------
    def eval(self, node: ast.AST):
        if node is None:
            return UNIFORM
        if isinstance(node, ast.Name):
            if node.id in self.env:
                return self.env[node.id]
            return UNIFORM  # closure constants / hyperparams: uniform
        if isinstance(node, ast.Constant):
            return UNIFORM
        if isinstance(node, ast.Call):
            return self.eval_call(node)
        if isinstance(node, ast.Attribute):
            if node.attr in _META_ATTRS:
                return UNIFORM
            return _scalar(self.eval(node.value))
        if isinstance(node, ast.Subscript):
            base = self.eval(node.value)
            if isinstance(base, TupleVal):
                idx = node.slice
                if isinstance(idx, ast.Constant) and isinstance(idx.value, int):
                    if -len(base.elts) <= idx.value < len(base.elts):
                        return base.elts[idx.value]
                return base.collapse()
            return _scalar(base)
        if isinstance(node, ast.BinOp):
            return _scalar(self.eval(node.left)).join(_scalar(self.eval(node.right)))
        if isinstance(node, ast.BoolOp):
            out = UNIFORM
            for v in node.values:
                out = out.join(_scalar(self.eval(v)))
            return out
        if isinstance(node, ast.UnaryOp):
            return _scalar(self.eval(node.operand))
        if isinstance(node, ast.Compare):
            out = _scalar(self.eval(node.left))
            for comp in node.comparators:
                out = out.join(_scalar(self.eval(comp)))
            return out
        if isinstance(node, ast.IfExp):
            return _join(self.eval(node.body), self.eval(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List)):
            return TupleVal([self.eval(e) for e in node.elts])
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        if isinstance(node, ast.NamedExpr):
            value = self.eval(node.value)
            self.assign(node.target, value)
            return value
        if isinstance(node, (ast.ListComp, ast.GeneratorExp, ast.SetComp)):
            out = UNIFORM
            for gen in node.generators:
                out = out.join(_scalar(self.eval(gen.iter)))
            return out
        return UNIFORM

    # -- calls --------------------------------------------------------------
    def eval_call(self, call: ast.Call):
        collective = self.ctx.collective_for(call)
        if collective is not None:
            return self.apply_collective(call, collective)

        name = dotted_name(call.func)
        arg_vals = [_scalar(self.eval(a)) for a in call.args] + [
            _scalar(self.eval(kw.value)) for kw in call.keywords
        ]
        joined = UNIFORM
        for v in arg_vals:
            joined = joined.join(v)

        # control-flow primitives with function operands
        if name is not None:
            base = name.split(".")[-1]
            root, _, rest = name.partition(".")
            is_lax = (rest and root in self.ctx.info.lax_aliases) or (
                not rest and self.ctx._imported_from(base, "jax.lax")
            )
            if is_lax and base == "while_loop" and len(call.args) >= 3:
                return self.apply_while_loop(call)
            if is_lax and base == "fori_loop" and len(call.args) >= 4:
                return self.apply_fori_loop(call)
            if is_lax and base == "cond" and len(call.args) >= 3:
                return self.apply_cond(call)
            if is_lax and base == "switch" and len(call.args) >= 2:
                return self.apply_switch(call)
            if is_lax and base == "scan" and len(call.args) >= 2:
                return self.apply_scan(call)

        # .astype(narrow) marks provenance for the precision rule
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "astype"
            and call.args
        ):
            target = self._dtype_name(call.args[0])
            base_val = _scalar(self.eval(call.func.value))
            if target in NARROW_DTYPES:
                return AbsVal(
                    axes=base_val.axes,
                    unknown=base_val.unknown,
                    narrowed=target,
                    permuted=base_val.permuted,
                )
            return base_val

        # local nested function: interpret inline with this scope as its
        # closure (bounded)
        if isinstance(call.func, ast.Name) and call.func.id in self._local_defs:
            return self._interpret_local(
                self._local_defs[call.func.id],
                [self.eval(a) for a in call.args],
            )

        # cross-module / module-level function via the call graph
        resolved = self._resolve_cross(call)
        if resolved is not None:
            decl, target_ctx, skip_self = resolved
            args = [self.eval(a) for a in call.args]
            return self.interpret_callee(decl.node, args, target_ctx)

        # unknown call: variance joins through (conservative propagation)
        return joined

    def _dtype_name(self, node: ast.AST) -> Optional[str]:
        name = dotted_name(node)
        if name is not None:
            return name.split(".")[-1]
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        return None

    def _resolve_cross(self, call: ast.Call):
        if self.depth >= MAX_DEPTH:
            return None
        from . import callgraph

        graph = callgraph.get(self.ctx.project)
        resolved = graph.resolve(self.ctx.module, call.func, None)
        if resolved is None:
            return None
        decl, skip_self = resolved
        key = (decl.path, decl.qualname)
        if key in self.seen:
            return None
        target_module = self.ctx.project.module_at(decl.path)
        if target_module is None:
            return None
        target_ctx = (
            self.ctx
            if target_module is self.ctx.module
            else ModuleContext(self.ctx.project, target_module)
        )
        return decl, target_ctx, skip_self

    def _interpret_local(self, fn: ast.FunctionDef, args: Sequence):
        key = (self.ctx.module.path, fn.name)
        if self.depth >= MAX_DEPTH or key in self.seen:
            joined = UNIFORM
            for a in args:
                joined = joined.join(_scalar(a))
            return joined
        sub = BodyInterpreter(
            ctx=self.ctx,
            fn=fn,
            arg_vals=args,
            events=self.events,
            local_env=self.local_env,
            depth=self.depth + 1,
            record=self.record,
            divergent=self.divergent,
            seen=self.seen | {key},
            closure_env=self.env,
            closure_defs=self._local_defs,
        )
        sub.run(fn.body)
        return sub.return_value(args)

    def interpret_callee(self, fn: ast.FunctionDef, args: Sequence, ctx):
        key = (ctx.module.path, fn.name)
        if self.depth >= MAX_DEPTH or key in self.seen:
            joined = UNIFORM
            for a in args:
                joined = joined.join(_scalar(a))
            return joined
        sub = BodyInterpreter(
            ctx=ctx,
            fn=fn,
            arg_vals=args,
            events=self.events,
            local_env=self.local_env if ctx is self.ctx else {},
            depth=self.depth + 1,
            record=self.record and ctx.module is self.ctx.module,
            divergent=self.divergent,
            seen=self.seen | {key},
        )
        sub.run(fn.body)
        return sub.return_value(args)

    def return_value(self, args: Sequence):
        if not self.returns:
            joined = UNIFORM
            for a in args:
                joined = joined.join(_scalar(a))
            return joined
        out = self.returns[0][0]
        for v, _ in self.returns[1:]:
            out = _join(out, v)
        return out

    # -- collectives --------------------------------------------------------
    def apply_collective(self, call: ast.Call, collective):
        op, kind, axis_param, default_axis, operand_params = collective
        axis = self._collective_axis(call, axis_param, default_axis)
        operand = UNIFORM
        for pos in operand_params:
            if pos < len(call.args):
                operand = operand.join(_scalar(self.eval(call.args[pos])))
        # evaluate remaining args for their side effects on env
        for i, a in enumerate(call.args):
            if i not in operand_params and i != axis_param:
                self.eval(a)

        if self.divergent is not None and kind in ("reduce", "gather", "permute"):
            branch_line, reason = self.divergent
            self.emit(
                call.lineno,
                "divergent-collective",
                op,
                extra=(branch_line, reason, axis or "?"),
            )

        if kind == "size":
            return UNIFORM  # static participant count, same on every shard
        if kind == "index":
            return AbsVal(axes=frozenset({axis}) if axis else frozenset())

        if axis is None or operand.unknown:
            # unresolvable axis or unknown operand: keep the variance flow
            # honest but emit nothing
            if kind in ("reduce", "gather"):
                return AbsVal(unknown=operand.unknown)
            return operand

        if kind == "reduce":
            if axis not in operand.axes:
                self.emit(call.lineno, "double-reduce", op, extra=(axis,))
            if operand.narrowed:
                self.emit(
                    call.lineno,
                    "downcast-before-reduce",
                    op,
                    extra=(operand.narrowed,),
                )
            return AbsVal(axes=operand.axes - {axis})
        if kind == "gather":
            if axis not in operand.axes:
                self.emit(call.lineno, "unsharded-collective", op, extra=(axis,))
            return AbsVal(axes=operand.axes - {axis}, narrowed=operand.narrowed)
        if kind == "permute":
            if axis not in operand.axes:
                self.emit(call.lineno, "unsharded-collective", op, extra=(axis,))
            return AbsVal(
                axes=operand.axes | ({axis} if axis else frozenset()),
                narrowed=operand.narrowed,
                permuted=True,
            )
        return operand

    def _collective_axis(
        self, call: ast.Call, axis_param: int, default_axis: Optional[str]
    ) -> Optional[str]:
        node = None
        if axis_param < len(call.args):
            node = call.args[axis_param]
        else:
            for kw in call.keywords:
                if kw.arg in ("axis_name", "axis"):
                    node = kw.value
                    break
        if node is None:
            return default_axis
        resolved = self.ctx.resolve_axis(node, self.local_env)
        if resolved is None:
            # a Name bound inside this body (e.g. unpacked) — try env-free
            # local assignment table built by the site scanner
            return None
        return resolved[1]

    # -- structured control flow --------------------------------------------
    def _branch_fn(self, node: ast.AST) -> Optional[ast.FunctionDef]:
        if isinstance(node, ast.Name):
            return self._local_defs.get(node.id)
        if isinstance(node, ast.Lambda):
            return None
        return None

    def _run_branch_fn(self, fn, args, divergent):
        sub = BodyInterpreter(
            ctx=self.ctx,
            fn=fn,
            arg_vals=args,
            events=self.events,
            local_env=self.local_env,
            depth=self.depth + 1,
            record=self.record,
            divergent=divergent,
            seen=self.seen | {(self.ctx.module.path, fn.name)},
            closure_env=self.env,
            closure_defs=self._local_defs,
        )
        sub.run(fn.body)
        return sub.return_value(args)

    def apply_while_loop(self, call: ast.Call):
        cond_fn = self._branch_fn(call.args[0])
        body_fn = self._branch_fn(call.args[1])
        carry = self.eval(call.args[2])
        if body_fn is None:
            return _scalar(carry)
        # join-fixpoint the carry silently, then one recording pass
        for _ in range(FIXPOINT_ROUNDS):
            nxt = self._run_quiet(body_fn, [carry])
            joined = _join(carry, nxt)
            if joined == carry:
                break
            carry = joined
        divergent = self.divergent
        if cond_fn is not None:
            pred = _scalar(self._run_quiet(cond_fn, [carry]))
            if pred.axes and not pred.unknown:
                divergent = (call.lineno, "while_loop predicate varies per shard")
        out = self._run_branch_fn(body_fn, [carry], divergent)
        return _join(carry, out)

    def apply_fori_loop(self, call: ast.Call):
        body_fn = self._branch_fn(call.args[2])
        carry = self.eval(call.args[3])
        bounds = _scalar(self.eval(call.args[0])).join(
            _scalar(self.eval(call.args[1]))
        )
        if body_fn is None:
            return _scalar(carry)
        for _ in range(FIXPOINT_ROUNDS):
            nxt = self._run_quiet(body_fn, [UNIFORM, carry])
            joined = _join(carry, nxt)
            if joined == carry:
                break
            carry = joined
        divergent = self.divergent
        if bounds.axes and not bounds.unknown:
            divergent = (call.lineno, "fori_loop bounds vary per shard")
        out = self._run_branch_fn(body_fn, [UNIFORM, carry], divergent)
        return _join(carry, out)

    def apply_cond(self, call: ast.Call):
        pred = _scalar(self.eval(call.args[0]))
        operands = [self.eval(a) for a in call.args[3:]]
        divergent = self.divergent
        if pred.axes and not pred.unknown:
            divergent = (call.lineno, "cond predicate varies per shard")
        out = None
        for branch_arg in call.args[1:3]:
            fn = self._branch_fn(branch_arg)
            if fn is None:
                continue
            res = self._run_branch_fn(fn, operands, divergent)
            out = res if out is None else _join(out, res)
        if out is None:
            joined = pred
            for v in operands:
                joined = joined.join(_scalar(v))
            return joined
        return _join(out, pred if pred.axes else UNIFORM)

    def apply_switch(self, call: ast.Call):
        pred = _scalar(self.eval(call.args[0]))
        divergent = self.divergent
        if pred.axes and not pred.unknown:
            divergent = (call.lineno, "switch index varies per shard")
        out = UNIFORM
        branches = call.args[1]
        fns = []
        if isinstance(branches, (ast.Tuple, ast.List)):
            fns = [self._branch_fn(e) for e in branches.elts]
        operands = [self.eval(a) for a in call.args[2:]]
        for fn in fns:
            if fn is not None:
                out = _join(out, self._run_branch_fn(fn, operands, divergent))
        return out

    def apply_scan(self, call: ast.Call):
        body_fn = self._branch_fn(call.args[0])
        carry = self.eval(call.args[1])
        xs = self.eval(call.args[2]) if len(call.args) > 2 else UNIFORM
        if body_fn is None:
            return _join(carry, xs)
        for _ in range(FIXPOINT_ROUNDS):
            nxt = self._run_quiet(body_fn, [carry, xs])
            if isinstance(nxt, TupleVal) and len(nxt.elts) == 2:
                nxt = nxt.elts[0]
            joined = _join(carry, nxt)
            if joined == carry:
                break
            carry = joined
        out = self._run_branch_fn(body_fn, [carry, xs], self.divergent)
        return _join(carry, out)

    def _run_quiet(self, fn, args):
        sub = BodyInterpreter(
            ctx=self.ctx,
            fn=fn,
            arg_vals=args,
            events=self.events,
            local_env=self.local_env,
            depth=self.depth + 1,
            record=False,
            divergent=None,
            seen=self.seen | {(self.ctx.module.path, fn.name)},
            closure_env=self.env,
            closure_defs=self._local_defs,
        )
        sub.run(fn.body)
        return sub.return_value(args)

    # -- statements ---------------------------------------------------------
    def assign(self, target: ast.AST, value):
        if isinstance(target, ast.Name):
            self.env[target.id] = value
        elif isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(value, TupleVal) and len(value.elts) == len(target.elts):
                for elt, v in zip(target.elts, value.elts):
                    self.assign(
                        elt.value if isinstance(elt, ast.Starred) else elt, v
                    )
            else:
                collapsed = _scalar(value)
                for elt in target.elts:
                    self.assign(
                        elt.value if isinstance(elt, ast.Starred) else elt,
                        collapsed,
                    )

    def run(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self.run_statement(stmt)

    def run_statement(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.FunctionDef):
            return  # interpreted on demand at call sites
        if isinstance(stmt, (ast.AsyncFunctionDef, ast.ClassDef)):
            return
        if isinstance(stmt, ast.Return):
            self.returns.append((self.eval(stmt.value), stmt.lineno))
            return
        if isinstance(stmt, ast.Assign):
            value = self.eval(stmt.value)
            for target in stmt.targets:
                self.assign(target, value)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self.assign(stmt.target, self.eval(stmt.value))
            return
        if isinstance(stmt, ast.AugAssign):
            value = _scalar(self.eval(stmt.value))
            if isinstance(stmt.target, ast.Name):
                prev = _scalar(self.env.get(stmt.target.id, UNIFORM))
                self.env[stmt.target.id] = prev.join(value)
            return
        if isinstance(stmt, ast.Expr):
            self.eval(stmt.value)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            test = _scalar(self.eval(stmt.test))
            prev = self.divergent
            if test.axes and not test.unknown:
                self.divergent = (
                    stmt.lineno,
                    "branch condition varies per shard",
                )
            self.run(stmt.body)
            self.run(stmt.orelse)
            self.divergent = prev
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            iter_val = _scalar(self.eval(stmt.iter))
            self.assign(stmt.target, iter_val)
            prev = self.divergent
            if iter_val.axes and not iter_val.unknown:
                self.divergent = (stmt.lineno, "loop iterates per-shard data")
            self.run(stmt.body)
            self.run(stmt.orelse)
            self.divergent = prev
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                val = self.eval(item.context_expr)
                if item.optional_vars is not None:
                    self.assign(item.optional_vars, val)
            self.run(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            self.run(stmt.body)
            for handler in stmt.handlers:
                self.run(handler.body)
            self.run(stmt.orelse)
            self.run(stmt.finalbody)
            return


# ---------------------------------------------------------------------------
# shard_map site discovery + module-level scans
# ---------------------------------------------------------------------------

@dataclass
class ShardMapSite:
    path: str
    line: int
    fn: Optional[ast.FunctionDef]
    in_specs: object
    out_specs: object
    local_env: Dict[str, ast.AST]


def _is_shard_map_call(name: Optional[str]) -> Optional[str]:
    if name is None:
        return None
    base = name.split(".")[-1]
    if base in ("shard_map_over", "shard_map"):
        return base
    return None


def _assignment_env(scopes: List[ast.AST]) -> Dict[str, ast.AST]:
    """name -> value-expression for simple assignments in the enclosing
    scopes (outermost first, so inner scopes shadow)."""
    env: Dict[str, ast.AST] = {}
    for scope in scopes:
        body = scope.body if hasattr(scope, "body") else []
        for stmt in body:
            if (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
            ):
                env[stmt.targets[0].id] = stmt.value
    return env


def _find_def(scopes: List[ast.AST], name: str) -> Optional[ast.FunctionDef]:
    for scope in reversed(scopes):  # innermost first
        for stmt in getattr(scope, "body", []):
            if isinstance(stmt, ast.FunctionDef) and stmt.name == name:
                return stmt
    return None


def _unwrap_vmap_expr(expr: Optional[ast.AST]) -> Optional[ast.AST]:
    """Peel `jax.vmap(f)` / `vmap(vmap(f))` wrappers off a function
    expression: vmap adds a batch axis but the wrapped body is still the
    shard_map body whose reductions the specs must match (the fleet
    kernels shard_map vmapped member programs)."""
    while (
        isinstance(expr, ast.Call)
        and (dotted_name(expr.func) or "").split(".")[-1] == "vmap"
        and expr.args
    ):
        expr = expr.args[0]
    return expr


def find_shard_map_sites(ctx: ModuleContext) -> List[ShardMapSite]:
    module = ctx.module
    sites: List[ShardMapSite] = []
    if module.tree is None:
        return sites

    def visit(node: ast.AST, scopes: List[ast.AST]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes = scopes + [node]
        for child in ast.iter_child_nodes(node):
            visit(child, scopes)
        if not isinstance(node, ast.Call):
            return
        base = _is_shard_map_call(dotted_name(node.func))
        if base is None:
            return
        env = _assignment_env(scopes)
        fn_expr = None
        in_expr = None
        out_expr = None
        if base == "shard_map_over":
            if len(node.args) >= 3:
                in_expr, out_expr = node.args[1], node.args[2]
            for kw in node.keywords:
                if kw.arg == "fn":
                    fn_expr = kw.value
                elif kw.arg == "in_specs":
                    in_expr = kw.value
                elif kw.arg == "out_specs":
                    out_expr = kw.value
        else:  # jax.shard_map(f, mesh=..., in_specs=..., out_specs=...)
            if node.args:
                fn_expr = node.args[0]
            for kw in node.keywords:
                if kw.arg == "in_specs":
                    in_expr = kw.value
                elif kw.arg == "out_specs":
                    out_expr = kw.value
        fn_def = None
        fn_expr = _unwrap_vmap_expr(fn_expr)
        if isinstance(fn_expr, ast.Name):
            fn_def = _find_def(scopes, fn_expr.id)
        if fn_def is None:
            return  # decorator form / pass-through param: nothing to walk
        in_specs = (
            parse_spec_expr(ctx, in_expr, env) if in_expr is not None else UNKNOWN
        )
        out_specs = (
            parse_spec_expr(ctx, out_expr, env) if out_expr is not None else UNKNOWN
        )
        sites.append(
            ShardMapSite(
                path=module.path,
                line=node.lineno,
                fn=fn_def,
                in_specs=in_specs,
                out_specs=out_specs,
                local_env=env,
            )
        )

    visit(module.tree, [module.tree])
    return sites


def _scan_axis_literals(ctx: ModuleContext, events: List[SpmdEvent]) -> None:
    """Module-wide axis hygiene, independent of shard_map bodies: every
    collective call's axis argument, every ``P(...)`` entry, and every
    ``create_mesh``/``Mesh`` axis tuple."""
    module = ctx.module
    if module.tree is None or module.path == MESH_PATH:
        return  # mesh.py DECLARES the constants; its literals are the truth

    def check(resolved, line_fallback: int):
        if resolved is None:
            return
        kind, value = resolved[0], resolved[1]
        line = resolved[2] if kind == "literal" else line_fallback
        if value not in ctx.axes.known_axes:
            events.append(
                SpmdEvent(
                    path=module.path, line=line, kind="unknown-axis", detail=value
                )
            )
        elif kind == "literal":
            events.append(
                SpmdEvent(
                    path=module.path,
                    line=line,
                    kind="axis-bypass",
                    detail=value,
                    extra=(ctx.axes.by_value.get(value, ""),),
                )
            )

    # enclosing-scope assignment envs, rebuilt per top-level walk for
    # one-deep Name resolution
    def visit(node: ast.AST, scopes: List[ast.AST]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes = scopes + [node]
        for child in ast.iter_child_nodes(node):
            visit(child, scopes)
        if not isinstance(node, ast.Call):
            return
        env = _assignment_env(scopes)
        name = dotted_name(node.func)
        base = name.split(".")[-1] if name else ""
        collective = ctx.collective_for(node)
        if collective is not None:
            _, _, axis_param, _, _ = collective
            axis_node = None
            if axis_param < len(node.args):
                axis_node = node.args[axis_param]
            else:
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis"):
                        axis_node = kw.value
                        break
            if axis_node is not None:
                check(ctx.resolve_axis(axis_node, env), node.lineno)
            return
        if _is_partition_spec_call(node):
            for arg in node.args:
                if isinstance(arg, ast.Constant) and arg.value is None:
                    continue
                if isinstance(arg, (ast.Tuple, ast.List)):
                    for elt in arg.elts:
                        check(ctx.resolve_axis(elt, env), node.lineno)
                else:
                    check(ctx.resolve_axis(arg, env), node.lineno)
            return
        if base in ("create_mesh", "Mesh"):
            candidates = list(node.args[:1]) + [
                kw.value for kw in node.keywords if kw.arg == "axis_names"
            ]
            for cand in candidates:
                if isinstance(cand, (ast.Tuple, ast.List)):
                    for elt in cand.elts:
                        check(ctx.resolve_axis(elt, env), node.lineno)

    visit(module.tree, [module.tree])


def _scan_order_folds(ctx: ModuleContext, events: List[SpmdEvent]) -> None:
    """Manual accumulation of permuted shards outside collectives.py: a
    python loop whose body both calls a permute collective and accumulates
    into a loop-carried name reassociates the reduction — replica-order
    folds live only in collectives.py."""
    module = ctx.module
    if module.tree is None or module.path in SANCTIONED_FOLD_PATHS:
        return

    def loop_has_permute(loop: ast.AST) -> Optional[int]:
        for sub in ast.walk(loop):
            if isinstance(sub, ast.Call):
                collective = ctx.collective_for(sub)
                if collective is not None and collective[1] == "permute":
                    return sub.lineno
        return None

    def loop_accumulates(loop: ast.AST) -> bool:
        for sub in ast.walk(loop):
            if isinstance(sub, ast.AugAssign) and isinstance(
                sub.op, (ast.Add, ast.Sub)
            ):
                return True
            if (
                isinstance(sub, ast.Assign)
                and len(sub.targets) == 1
                and isinstance(sub.targets[0], ast.Name)
                and isinstance(sub.value, ast.BinOp)
                and isinstance(sub.value.op, (ast.Add, ast.Sub))
            ):
                target = sub.targets[0].id
                for operand in ast.walk(sub.value):
                    if isinstance(operand, ast.Name) and operand.id == target:
                        return True
        return False

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.For, ast.While)):
            permute_line = loop_has_permute(node)
            if permute_line is not None and loop_accumulates(node):
                events.append(
                    SpmdEvent(
                        path=module.path,
                        line=permute_line,
                        kind="order-fold",
                        detail="ppermute",
                        extra=(node.lineno,),
                    )
                )


# ---------------------------------------------------------------------------
# the interpretation (project-level, memoized)
# ---------------------------------------------------------------------------

@dataclass
class SpmdInterpretation:
    events: List[SpmdEvent] = field(default_factory=list)
    sites: List[ShardMapSite] = field(default_factory=list)

    def of_kind(self, *kinds: str) -> List[SpmdEvent]:
        wanted = set(kinds)
        return [e for e in self.events if e.kind in wanted]


def _interpret_site(ctx: ModuleContext, site: ShardMapSite, events: List[SpmdEvent]):
    fn = site.fn
    params = [a.arg for a in fn.args.args]
    if params and params[0] in ("self", "cls"):
        params = params[1:]
    in_specs = site.in_specs
    if isinstance(in_specs, tuple) and in_specs and in_specs[0] == "spec":
        in_specs = (in_specs,)  # single spec for a single param
    if isinstance(in_specs, tuple) and in_specs and in_specs[0] != "spec":
        if len(in_specs) != len(params):
            events.append(
                SpmdEvent(
                    path=site.path,
                    line=site.line,
                    kind="spec-arity",
                    detail=fn.name,
                    extra=(len(in_specs), len(params)),
                )
            )
        arg_vals = [
            spec_to_absval(in_specs[i]) if i < len(in_specs) else UNKNOWN_VAL
            for i in range(len(params))
        ]
    elif in_specs is UNKNOWN:
        arg_vals = [UNKNOWN_VAL] * len(params)
    else:
        arg_vals = [spec_to_absval(in_specs)] + [UNKNOWN_VAL] * (len(params) - 1)

    interp = BodyInterpreter(
        ctx=ctx,
        fn=fn,
        arg_vals=arg_vals,
        events=events,
        local_env=site.local_env,
    )
    interp.run(fn.body)

    # out_specs vs what actually came back
    out_specs = site.out_specs
    if out_specs is UNKNOWN or not interp.returns:
        return
    for ret_val, ret_line in interp.returns:
        _check_output(site, fn, out_specs, ret_val, ret_line, events)


def _check_output(site, fn, out_specs, ret_val, ret_line, events):
    def check_one(spec, value):
        axes = spec_axes(spec)
        if axes is None:
            return
        v = _scalar(value) if not isinstance(value, TupleVal) else value.collapse()
        if v.unknown:
            return
        leftover = v.axes - axes
        if leftover:
            events.append(
                SpmdEvent(
                    path=site.path,
                    line=ret_line,
                    kind="unreduced-output",
                    detail=fn.name,
                    extra=(tuple(sorted(leftover)), site.line),
                )
            )

    if isinstance(out_specs, tuple) and out_specs and out_specs[0] == "spec":
        check_one(out_specs, ret_val)
    elif isinstance(out_specs, tuple):
        if isinstance(ret_val, TupleVal) and len(ret_val.elts) == len(out_specs):
            for spec, value in zip(out_specs, ret_val.elts):
                check_one(spec, value)
        else:
            for spec in out_specs:
                check_one(spec, ret_val)


def _build_interpretation(project) -> SpmdInterpretation:
    out = SpmdInterpretation()
    for module in project.modules:
        if module.tree is None:
            continue
        ctx = ModuleContext(project, module)
        _scan_axis_literals(ctx, out.events)
        _scan_order_folds(ctx, out.events)
        for site in find_shard_map_sites(ctx):
            out.sites.append(site)
            _interpret_site(ctx, site, out.events)
    # one event per (path, line, kind, detail): branch fns re-interpreted
    # under several contexts would otherwise repeat themselves
    seen: Set[Tuple] = set()
    unique: List[SpmdEvent] = []
    for e in out.events:
        key = (e.path, e.line, e.kind, e.detail)
        if key not in seen:
            seen.add(key)
            unique.append(e)
    out.events = unique
    return out


def interpretation(project) -> SpmdInterpretation:
    return project.index("spmd", _build_interpretation)
