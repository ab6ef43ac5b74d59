"""precision-determinism: narrowing casts and unsanctioned fold orders.

Two ways a distributed reduction quietly stops being the number the
math says:

1. **Implicit downcast before a reduce.** An ``astype`` to bf16/f16
   (or an 8-bit type) immediately upstream of a ``psum``-family
   collective makes every addend lose mantissa *before* the sum — at
   dim=1e6 the accumulated error is not noise, it is a different
   model. An f32 accumulator over f32 operands is fine (an
   ``astype(jnp.float32)`` on a loss scalar before its psum is exactly
   that); it is the *narrowing* direction that corrupts.
   The fix is to reduce in the operand's dtype (or wider) and narrow
   the *result* if the wire format demands it.

2. **Reduction-order-sensitive folds outside the comm layer.**
   The parity contract of the comm layer (`docs/performance.md` §7)
   holds because its reductions are the backend's own collectives
   (``psum``, reduce_scatter + all_gather) or fold in **replica
   order** in ``parallel/collectives.py``. A manual loop elsewhere that
   accumulates permuted shards reassociates the sum: bit-identity
   silently becomes "close enough", which breaks every parity test the
   repo pins (chunked==monolithic, kill→resume bit-identical). A new
   reduction belongs in ``parallel/collectives.py``, where its parity
   suite lives.

Both checks ride the shared SPMD layer (``analysis/spmd.py``): the
interpreter tracks narrowing provenance through assignments into
collective operands inside shard_map bodies, and a module-level scan
catches permute-accumulate loops anywhere outside the sanctioned
modules.
"""

from __future__ import annotations

from typing import Iterable

from .. import spmd
from ..engine import Finding, Rule, register


@register
class PrecisionDeterminismRule(Rule):
    id = "precision-determinism"
    title = "narrowing cast before a reduction / unsanctioned fold order"
    rationale = (
        "An astype to bf16/f16 upstream of a psum makes every addend "
        "lose mantissa BEFORE the sum — at wide dims that is a different "
        "model, not noise; reduce in the operand dtype and narrow the "
        "result instead. And a manual loop accumulating permuted shards "
        "outside parallel/collectives.py reassociates the reduction, "
        "breaking the replica-order bit-exactness contract every parity "
        "suite in the repo pins (chunked==monolithic, resume "
        "bit-identical)."
    )
    example = "total = all_reduce_sum(grad.astype(jnp.bfloat16), DATA_AXIS)"
    scope = ("flink_ml_tpu",)

    def check_project(self, project) -> Iterable[Finding]:
        interp = spmd.interpretation(project)
        for event in interp.of_kind("downcast-before-reduce"):
            if not self.applies_to(event.path):
                continue
            dtype = event.extra[0] if event.extra else "?"
            yield Finding(
                path=event.path,
                line=event.line,
                rule=self.id,
                message=(
                    f"operand of {event.detail} was narrowed to {dtype} "
                    "before the reduction — every addend loses mantissa "
                    "before the sum; reduce in the operand's dtype (or "
                    "wider) and cast the reduced result instead"
                ),
                data=("downcast", event.detail, dtype),
            )
        for event in interp.of_kind("order-fold"):
            if not self.applies_to(event.path):
                continue
            loop_line = event.extra[0] if event.extra else "?"
            yield Finding(
                path=event.path,
                line=event.line,
                rule=self.id,
                message=(
                    f"loop at line {loop_line} accumulates permuted shards "
                    "— a hand-rolled ring fold outside parallel/"
                    "collectives.py; its association differs from psum, so "
                    "results are no longer bit-identical to the monolithic "
                    "collective (the contract docs/performance.md §7 "
                    "pins). Reduce with psum (collectives.all_reduce_sum) "
                    "instead"
                ),
                data=("order-fold", event.detail),
            )
