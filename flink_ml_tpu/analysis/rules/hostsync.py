"""host-sync-leak: implicit or unaccounted device→host synchronization.

The dispatch-bound bench verdict (wallMs 299 vs hostDispatchMs 297) means
a single stray device→host pull in a hot path stalls the whole pipeline
for a blocking round trip — and nothing in the profile says which
line did it. The sanctioned funnel is ``utils/packing.packed_device_get``
(one packed transfer, ``host_sync.*``/``readback.*`` accounted); this
rule flags the ways a sync leaks around it:

- ``np.asarray(x)`` / ``np.array(x)`` where ``x`` traces back to a
  device array (a jnp/lax call, a jitted kernel's result, memoized
  ``device_constants()``) — numpy silently issues a blocking
  device→host copy;
- ``float(x)`` / ``int(x)`` / ``bool(x)`` on such values — same sync,
  hidden in a cast;
- ``.item()`` — the idiomatic scalar pull, always a blocking sync;
- ``block_until_ready`` — a deliberate barrier, which is exactly why it
  must be either inside an accounted funnel or annotated with a
  suppression carrying its reason;
- **a device value passed to a helper that syncs it** — since v2 the
  rule consults the project call graph (``analysis/callgraph.py``): a
  *known* call resolves to the callee's bounded-depth summary, so an
  ``np.asarray`` buried two helpers deep is flagged at the top-level
  call site, with the full call chain and the sink's file:line in the
  finding.

Taint is tracked per function as source sets (device and/or parameter
origins); the interprocedural summaries fold parameter-sourced sinks
into the callers. *Unknown* calls still launder taint — the rule
under-approximates by design, so every finding is worth reading. The
resulting suppression set IS the library's audited census of host sync
points.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .. import callgraph
from ..engine import Finding, Rule, register
from ..source import SourceModule
from . import _jitindex

# backwards-compatible aliases (v1 exported these from here)
_META_ATTRS = callgraph.META_ATTRS
_HOST_SINKS = callgraph.HOST_SINKS

_DIRECT_MESSAGES = {
    "barrier": (
        "block_until_ready is a blocking device sync outside the "
        "accounted funnels — route the readback through "
        "packed_device_get, or suppress with the reason this "
        "barrier is deliberate"
    ),
    "item": (
        ".item() issues a blocking device->host scalar pull — "
        "batch it through packed_device_get (or keep the value "
        "on device)"
    ),
}


@register
class HostSyncLeakRule(Rule):
    id = "host-sync-leak"
    title = "implicit or unaccounted device->host synchronization"
    rationale = (
        "The train loop is host-dispatch-bound; one stray device->host "
        "pull stalls it for a blocking readback and vanishes from "
        "hostSyncCount. Every sync must ride packed_device_get (packed, "
        "accounted) or carry a suppression stating why it is deliberate — "
        "the suppression set doubles as the library's host-sync census. "
        "Since v2 the taint is interprocedural: a pull laundered through "
        "helper functions is flagged at the call site with the chain."
    )
    example = "centers = np.asarray(dev_centroids)  # implicit D2H pull"
    scope = ("flink_ml_tpu",)
    # the funnel itself performs the one sanctioned transfer
    exclude = ("flink_ml_tpu/utils/packing.py",)
    #: consult callee summaries (False = tpulint v1 per-function recall,
    #: kept as the baseline the tier-1 superset test compares against)
    interprocedural = True

    def check_module(
        self, project, module: SourceModule
    ) -> Iterable[Finding]:
        if module.tree is None:
            return ()
        info = _jitindex.jit_index(project)[module.path]
        graph = callgraph.get(project) if self.interprocedural else None
        events: List[callgraph.SyncEvent] = []

        covered = set()
        if graph is not None:
            for decl in graph.decls_in(module.path).values():
                covered.add(id(decl.node))
                events.extend(graph.analyze(decl).events)

        def walk(body, params):
            walker = callgraph.TaintWalker(
                graph=graph, module=module, info=info, params=params
            )
            walker.run_block(body)
            events.extend(walker.events)

        # nested functions (and, without the call graph, every function)
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if id(node) in covered:
                    continue
                params = {
                    a.arg: i
                    for i, a in enumerate(
                        list(node.args.posonlyargs) + list(node.args.args)
                    )
                }
                walk(node.body, params)
        # module level (rare, but kernels can be exercised at import)
        walk(module.tree.body, {})

        findings: List[Finding] = []
        suppressed_here = module.suppressions_for(self.id)
        for event in events:
            if callgraph.DEVICE in event.sources:
                findings.append(self._finding(module, event))
            elif (
                graph is not None
                and not event.funcs
                and event.kind in ("np-pull", "cast")
                and event.line in suppressed_here
            ):
                # parameter-sourced sink under a suppression: the callee
                # summary dropped it (documented deliberate sync) — emit
                # the census finding so --show-suppressed lists it and the
                # annotation cannot rot unused
                findings.append(
                    Finding(
                        path=module.path,
                        line=event.line,
                        rule=self.id,
                        message=(
                            f"{'np.' if event.kind == 'np-pull' else ''}"
                            f"{event.detail}"
                            f"{'' if event.kind == 'np-pull' else '()'} on a "
                            "function parameter is a blocking pull when "
                            "callers pass device values — deliberate here "
                            "(suppressed); callers inherit no finding"
                        ),
                        data=(f"{event.kind}-param", event.detail),
                    )
                )
        # nested scopes can be revisited — dedup on (line, message)
        seen = set()
        unique = []
        for f in findings:
            key = (f.line, f.message)
            if key not in seen:
                seen.add(key)
                unique.append(f)
        return unique

    def _finding(self, module: SourceModule, event) -> Finding:
        if event.funcs:
            chain = " -> ".join(event.funcs)
            sink = (
                f"np.{event.detail}" if event.kind == "np-pull" else f"{event.detail}()"
            )
            message = (
                f"device value passed to {event.funcs[0]}() is pulled to the "
                f"host by {sink} at {event.sink_path}:{event.sink_line} "
                f"(call chain: {chain}) — an implicit device->host sync "
                "laundered through helpers; route the readback through "
                "packed_device_get or keep the helper on device"
            )
            data = (f"{event.kind}-chain", event.detail) + tuple(event.funcs)
        elif event.kind in _DIRECT_MESSAGES:
            message = _DIRECT_MESSAGES[event.kind]
            data = (event.detail,)
        elif event.kind == "np-pull":
            message = (
                f"np.{event.detail} on a device value is an implicit device->host "
                "pull — route it through packed_device_get (accounted, "
                "packed) or keep the computation on the host branch"
            )
            data = ("np-pull", event.detail)
        else:  # cast
            message = (
                f"{event.detail}() on a device value is a hidden blocking sync — "
                "read it back through packed_device_get with the fit's "
                "packed result instead"
            )
            data = ("cast", event.detail)
        return Finding(
            path=module.path,
            line=event.line,
            rule=self.id,
            message=message,
            data=data,
        )
