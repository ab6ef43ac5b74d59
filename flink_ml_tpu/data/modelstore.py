"""Multi-tenant device-resident model store — HBM paging at model-count scale.

`DeviceEpochCache` (devicecache.py) answers "which *epochs* stay resident";
this module answers the serving-tier question ROADMAP item 3 poses: which
*models* stay resident when one mesh serves far more tenants than fit in
HBM. A `ModelStore` holds registered `(key -> model)` entries and pages
each model's kernel constants host<->HBM under an LRU byte budget
(`config.model_store_bytes`):

- **Page-in rides the sanctioned funnel.** The store never stages bytes
  itself: `page_in` calls each served stage's `device_constants()`, which
  uploads through `prefetch.stage_to_device(..., category="model")` — so
  every resident model byte is h2d-accounted and ledgered under the
  memledger `model` category, and `hbm.live.model` IS the store's
  residency. (tpulint's `unledgered-residency` rule sanctions `page_in`
  alongside the other funnels for exactly this reason.)
- **Page-out is deterministic.** `invalidate_device_constants()` drops the
  only persistent reference to the staged tree; the tracked entries'
  `weakref.finalize` release on the spot (CPython refcounting), so the
  ledger falls the moment the store decides, not at some later GC.
- **Zero recompiles by construction.** Model constants are *runtime
  operands* on the fused path: `FusedSegment.execute` re-reads
  `device_constants()` per dispatch and the plan-cache token excludes
  swap-capable array identities, so a page-out/page-in cycle re-uploads
  the same avals into the same compiled program.
  `tests/test_modelstore.py` pins `jit.compiles` flat across steady-state
  paging.
- **Admission is conservative.** Eviction is driven by the *host-side*
  byte estimate of each model's kernel constants, which (under jax's
  default x64-disabled canonicalization) is >= the device-resident bytes
  — so `hbm.live.model` can never exceed `budget_bytes` through this
  store, even before the post-staging measurement lands.

Integration points: an optional per-key `lifecycle.ModelLifecycle`
(version ring, promotion gate, auto-rollback — promote through
`ModelStore.promote` so residency accounting follows the republish), and
an optional per-key admission `quota` consumed by
`serving.MicroBatchServer`'s per-tenant reject-policy gates.
"""

from __future__ import annotations

import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

from .. import config, flow
from ..api import AlgoOperator
from ..obs import memledger
from ..pipeline import PipelineModel
from ..utils import metrics

__all__ = ["ModelStore", "ModelStoreBudgetExceeded"]

_UNSET = object()


class ModelStoreBudgetExceeded(RuntimeError):
    """A single model's estimated constants exceed the whole store budget
    — no eviction schedule can make it fit. Carries the numbers."""

    def __init__(self, key: str, nbytes: int, budget: int):
        super().__init__(
            f"model {key!r} needs ~{nbytes} constant bytes but "
            f"config.model_store_bytes={budget}"
        )
        self.key, self.nbytes, self.budget = key, nbytes, budget


def _served_stages(model) -> List[Any]:
    """The stages whose `device_constants()` are this model's resident
    footprint: the AlgoOperator members of a PipelineModel, or the model
    itself."""
    if isinstance(model, PipelineModel):
        return [s for s in model.stages if isinstance(s, AlgoOperator)]
    if isinstance(model, AlgoOperator):
        return [model]
    raise TypeError(
        f"ModelStore pages PipelineModel/AlgoOperator stages, got {type(model).__name__}"
    )


def _host_nbytes(tree) -> int:
    """Host-side bytes of a kernel-constants tree — the conservative
    admission estimate (>= device bytes under default canonicalization:
    f64/i64 hosts stage as f32/i32)."""
    import jax

    from ..table import register_device_pytrees

    register_device_pytrees()
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 8))
    return total


@dataclass
class _StoredModel:
    model: Any
    stages: List[Any]
    lifecycle: Any = None
    quota: Optional[int] = None
    est_nbytes: int = 0  # host-side estimate (admission)
    dev_nbytes: int = 0  # ledgered device bytes while resident
    resident: bool = False
    page_ins: int = 0


class ModelStore:
    """LRU-paged registry of served models, ledgered under `model`.

    `budget_bytes` defaults to `config.model_store_bytes` (None =
    unbounded). `acquire(key)` returns the model ready to dispatch,
    paging it in (and evicting least-recently-used residents first) as
    needed; `prefetch(keys)` warms upcoming tenants off the dispatch
    path. All mutation is lock-serialized — the dispatch worker and a
    prefetch worker may share one store.

    The store owns paging from `register` on: registration invalidates
    any externally staged constants so residency starts clean, and
    callers must route republishes through `promote` (or call
    `refresh(key)`) so accounting follows the new arrays.
    """

    def __init__(self, budget_bytes=_UNSET, name: str = "modelstore"):
        self.name = name
        self._budget = config.model_store_bytes if budget_bytes is _UNSET else budget_bytes
        if self._budget is not None:
            self._budget = max(0, int(self._budget))
        self._entries: "OrderedDict[str, _StoredModel]" = OrderedDict()
        self._lock = threading.RLock()
        self._used = 0  # ledgered device bytes of resident entries
        # learned device/host-estimate inflation (>= 1.0): real devices
        # pad constants past the host estimate (lane-aligned layouts), so
        # reserving by raw estimates would let residency overshoot the
        # budget. Every staging updates the max observed ratio and later
        # reservations are inflated by it; on backends where device bytes
        # <= estimate (CPU canonicalization) this stays exactly 1.0
        self._infl = 1.0
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    # -- registry ------------------------------------------------------------
    def register(
        self,
        key: str,
        model,
        lifecycle=None,
        quota: Optional[int] = None,
    ) -> None:
        """Add (or replace) a served model. `lifecycle` attaches a
        per-model version ring; `quota` is the tenant's admission-queue
        share (consumed by MicroBatchServer's per-tenant reject gates)."""
        stages = _served_stages(model)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None and old.resident:
                self._page_out_locked(key, old)
            entry = _StoredModel(
                model=model,
                stages=stages,
                lifecycle=lifecycle,
                quota=None if quota is None else max(1, int(quota)),
            )
            for stage in stages:  # start clean: the store owns residency now
                stage.invalidate_device_constants()
            entry.est_nbytes = sum(_host_nbytes(s._kernel_constants()) for s in stages)
            if self._budget is not None and entry.est_nbytes > self._budget:
                raise ModelStoreBudgetExceeded(key, entry.est_nbytes, self._budget)
            self._entries[key] = entry
            metrics.set_gauge("modelstore.models", len(self._entries))

    def unregister(self, key: str) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None and entry.resident:
                self._page_out_locked(key, entry)
            metrics.set_gauge("modelstore.models", len(self._entries))

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> List[str]:
        with self._lock:
            return list(self._entries)

    def lifecycle(self, key: str):
        return self._entry(key).lifecycle

    def quota(self, key: str) -> Optional[int]:
        return self._entry(key).quota

    def estimated_nbytes(self, key: str) -> int:
        """The host-side admission estimate for one model — what sizing a
        budget against N models costs (bench/example use this to pick a
        `model_store_bytes` that forces paging)."""
        return self._entry(key).est_nbytes

    def _entry(self, key: str) -> _StoredModel:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                raise KeyError(f"model {key!r} is not registered in {self.name}")
            return entry

    # -- paging --------------------------------------------------------------
    def acquire(self, key: str):
        """The dispatch-path read: page `key` in if needed, mark it
        most-recently-used, return its model."""
        return self.page_in(key).model

    def page_in(self, key: str) -> _StoredModel:
        """Make `key` device-resident (the sanctioned paging funnel: all
        bytes stage through each stage's `device_constants()` ->
        `prefetch.stage_to_device(category="model")`). Evicts LRU
        residents first so estimated residency never exceeds the budget."""
        with self._lock:
            entry = self._entry(key)
            self._entries.move_to_end(key)
            if entry.resident and all(
                "_device_consts" in s.__dict__ for s in entry.stages
            ):
                self._hits += 1
                metrics.inc_counter("modelstore.hit")
                return entry
            self._misses += 1
            metrics.inc_counter("modelstore.miss")
            if entry.resident:
                # externally invalidated (e.g. a republish outside
                # `promote`) — drop stale accounting and restage
                self._page_out_locked(key, entry, count_eviction=False)
            self._ensure_room(key, math.ceil(entry.est_nbytes * self._infl))
            dev = 0
            for stage in entry.stages:
                dev += memledger.tracked_nbytes(stage.device_constants())
            if entry.est_nbytes > 0:
                self._infl = max(self._infl, dev / entry.est_nbytes)
            entry.resident = True
            entry.dev_nbytes = dev
            entry.page_ins += 1
            self._used += dev
            metrics.inc_counter("modelstore.pageIn")
            metrics.inc_counter("modelstore.pageInBytes", dev)
            metrics.set_gauge("modelstore.bytes", self._used)
            return entry

    def page_out(self, key: str) -> None:
        """Release `key`'s device constants (the ledger entries close via
        the dropped references — deterministic on CPython)."""
        with self._lock:
            entry = self._entry(key)
            if entry.resident:
                self._page_out_locked(key, entry)

    def _page_out_locked(self, key: str, entry: _StoredModel, count_eviction: bool = True) -> None:
        for stage in entry.stages:
            stage.invalidate_device_constants()
        self._used -= entry.dev_nbytes
        if count_eviction:
            self._evictions += 1
            metrics.inc_counter("modelstore.evict")
            metrics.inc_counter("modelstore.evictBytes", entry.dev_nbytes)
        entry.resident = False
        entry.dev_nbytes = 0
        metrics.set_gauge("modelstore.bytes", self._used)

    def _ensure_room(self, incoming_key: str, est_nbytes: int) -> None:
        """Evict least-recently-used residents until the conservative
        estimate fits. `_used` tracks *ledgered* bytes (<= estimates), so
        `hbm.live.model` stays <= budget through the staging itself."""
        if self._budget is None:
            return
        if est_nbytes > self._budget:
            raise ModelStoreBudgetExceeded(incoming_key, est_nbytes, self._budget)
        while self._used + est_nbytes > self._budget:
            victim = next(
                (k for k, e in self._entries.items() if e.resident and k != incoming_key),
                None,
            )
            if victim is None:  # accounting can't shrink further
                break
            self._page_out_locked(victim, self._entries[victim])

    def prefetch(self, keys: Iterable[str], wait: bool = True):
        """Warm `keys` ahead of their dispatches — the miss-staging path
        the dispatch loop never pays. `wait=False` pages on a background
        `flow.spawn` worker (store-lock serialized against the dispatch
        path) and returns the worker handle."""
        keys = [k for k in keys]

        def _warm():
            for k in keys:
                metrics.inc_counter("modelstore.prefetch")
                self.page_in(k)

        if wait:
            _warm()
            return None
        return flow.spawn(_warm, name=f"{self.name}.prefetch")

    def warmup_programs(
        self, server, example, buckets=None
    ) -> "Dict[str, float]":
        """Drive every (registered tenant x bucket) serving program once
        through `server` (a MicroBatchServer) ahead of traffic: models
        page in through the normal `page_in` funnel and each program
        compiles — or, with an AOT program bank active
        (`config.program_bank_dir`), warm-loads without a trace or
        compile. The store side of the no-compile serving SLA
        (docs/performance.md §12)."""
        return server.warmup(example, tenants=self.keys(), buckets=buckets)

    # -- lifecycle integration ----------------------------------------------
    def promote(self, key: str, arrays: tuple, version: Optional[int] = None):
        """Promote a candidate through `key`'s lifecycle ring (gate +
        canary + version ring), then refresh residency accounting: the
        republish dropped the old constants' tree, so a resident entry
        restages and re-measures under the same compiled plan."""
        entry = self._entry(key)
        if entry.lifecycle is None:
            raise ValueError(f"model {key!r} has no lifecycle attached")
        result = entry.lifecycle.promote(arrays, version=version)
        self.refresh(key)
        return result

    def refresh(self, key: str) -> None:
        """Re-sync accounting after `key`'s arrays changed (republish or
        rollback): recompute the host estimate and, if resident, restage
        the new constants immediately."""
        with self._lock:
            entry = self._entry(key)
            was_resident = entry.resident
            if was_resident:
                self._page_out_locked(key, entry, count_eviction=False)
            entry.est_nbytes = sum(
                _host_nbytes(s._kernel_constants()) for s in entry.stages
            )
            if self._budget is not None and entry.est_nbytes > self._budget:
                raise ModelStoreBudgetExceeded(key, entry.est_nbytes, self._budget)
            if was_resident:
                self.page_in(key)

    # -- introspection -------------------------------------------------------
    def resident_keys(self) -> List[str]:
        with self._lock:
            return [k for k, e in self._entries.items() if e.resident]

    @property
    def budget_bytes(self) -> Optional[int]:
        return self._budget

    @property
    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "models": len(self._entries),
                "resident": sum(1 for e in self._entries.values() if e.resident),
                "bytes": self._used,
                "hits": self._hits,
                "misses": self._misses,
                "evictions": self._evictions,
            }

    def check_ledger_parity(self) -> None:
        """Assert the store's byte accounting matches the memledger's
        tracked view of every resident constants tree — the same
        invariant DeviceEpochCache pins for epochs."""
        with self._lock:
            tracked = 0
            for entry in self._entries.values():
                if not entry.resident:
                    continue
                for stage in entry.stages:
                    cached = stage.__dict__.get("_device_consts")
                    if cached is not None:
                        tracked += memledger.tracked_nbytes(cached[1])
            if tracked != self._used:
                raise AssertionError(
                    f"{self.name}: ledger parity broken — tracked {tracked} "
                    f"!= accounted {self._used}"
                )
