"""Versioned zero-pause model hot-swap — train-while-serving lifecycle.

The reference publishes online-trainer output through the
`modelDataVersion` contract (OnlineKMeansModel.java bumps a version gauge
on every set_model_data). This module is that contract grown production
teeth for the fused serving path (ROADMAP item 3): the fusion planner
feeds a swap-capable model's tensors as versioned RUNTIME OPERANDS of the
compiled plan (pipeline.py drops their identities from the plan cache
key), so publication is a pointer swap between batches — zero pause, zero
recompile, and a batch in flight keeps exactly the version it was
dispatched with. On top of that swap primitive, `ModelLifecycle` adds
what a live swap must never be allowed to skip:

1. **Promotion gate** — a candidate is validated BEFORE publication:
   structural parity with the serving version (tree arity, shapes,
   dtypes), finite values (a NaN-poisoned trainer update never reaches
   traffic), and an optional canary-batch parity check — the candidate's
   outputs on a pinned canary batch must stay within
   `config.lifecycle_canary_rtol` of the OUTGOING version's. Refusals
   raise the typed `PromotionRejected`, count `lifecycle.promoteRejected`
   and leave the serving model untouched.

2. **Version ring + automatic rollback** — promoted versions are retained
   as host copies in a bounded ring (`config.model_versions_retained`).
   Serve outcomes feed a sliding health window
   (`config.lifecycle_health_window`); when the guard-error rate over a
   full window reaches `config.lifecycle_error_rate_trigger`, traffic
   rolls back to the last-good retained version — bit-exact, republished
   under its ORIGINAL version id — and the trainer's output is
   quarantined: further `promote` calls raise the typed
   `TrainerQuarantined` until an operator calls `release_quarantine()`.

3. **Preemption safety** — with a checkpoint dir, every promotion
   persists the model arrays plus the ring cursor and last-good version
   id in JobSnapshot meta (`publishedVersion` / `lastGoodVersion`), and
   the snapshot is written BEFORE the swap: a trainer killed mid-publish
   resumes by re-publishing the same version id instead of silently
   regressing to version 0.

Fault sites (ckpt/faults.py): `lifecycle.promote` fires at promote entry
(a trainer kill before anything durable happened) and `lifecycle.swap`
fires between the snapshot write and the pointer swap (the mid-publish
kill the resume contract covers). The chaos soak (tests/test_hot_swap.py)
composes both with flaky snapshot I/O, NaN-poisoned updates and overload
bursts.

Thread contract: `promote`/`rollback` are trainer-side and may run on one
trainer thread; `record_serve_ok`/`record_guard_error` are serve-side.
The published model state itself is ONE atomic reference on the model
(api.Model swap protocol) — readers never lock, writers never tear.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import config
from .api import KernelContext, Model
from .ckpt import faults
from .obs import timeline
from .utils import metrics

__all__ = [
    "LifecycleEvent",
    "ModelVersion",
    "PromotionRejected",
    "TrainerQuarantined",
    "ModelLifecycle",
]


class PromotionRejected(ValueError):
    """The promotion gate refused a candidate. Carries the machine-readable
    `reason` ("arity" | "shape" | "dtype" | "nonfinite" | "canary") so the
    trainer can distinguish divergence from a plumbing bug."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"promotion rejected ({reason}): {detail}")
        self.reason = reason
        self.detail = detail


class TrainerQuarantined(RuntimeError):
    """Raised by `promote` while the lifecycle is quarantined: a health
    trigger rolled traffic back and the trainer's output is refused until
    `release_quarantine()` — a diverged trainer must not keep publishing
    over a rollback."""

    def __init__(self, since_version: int, reason: str):
        super().__init__(
            f"trainer quarantined since rollback from version {since_version}: {reason}"
        )
        self.since_version = since_version
        self.reason = reason


@dataclass(frozen=True)
class LifecycleEvent:
    """One typed lifecycle transition, in order: kind is "promoted",
    "rejected", "rollback", "quarantined", "restored" or "released"."""

    kind: str
    version: int
    reason: str = ""
    at: float = 0.0


@dataclass(frozen=True)
class ModelVersion:
    """One retained published version: host float64 copies of the arrays
    (the rollback target — bit-exact by construction) plus provenance."""

    version_id: int
    arrays: Tuple[Optional[np.ndarray], ...]
    source: str = "trainer"  # "trainer" | "seed" | "restore" | "rollback"
    promoted_at: float = 0.0


def _host_copy(arrays: Tuple) -> Tuple[Optional[np.ndarray], ...]:
    """Host float64 copies of a candidate arrays tuple in ONE packed
    readback (device leaves) — the retained-ring / gate representation."""
    from .utils.packing import packed_device_get

    pulled = packed_device_get(*[a for a in arrays if a is not None], sync_kind="lifecycle")
    out: List[Optional[np.ndarray]] = []
    it = iter(pulled)
    for a in arrays:
        out.append(None if a is None else np.array(next(it), dtype=np.float64, copy=True))
    return tuple(out)


class ModelLifecycle:
    """Owns promotion, retention, rollback and (optionally) persistence of
    one swap-capable model's published versions.

    `model` must declare `swap_capable = True` (api.Model swap protocol).
    `canary` optionally pins a canary batch — a dict mapping the model's
    kernel input columns to arrays — enabling the gate's output-parity
    check. `checkpoint_dir`/`job_key` enable the JobSnapshot persistence
    contract (restore happens at construction)."""

    def __init__(
        self,
        model: Model,
        retained: Optional[int] = None,
        canary: Optional[Dict[str, Any]] = None,
        canary_rtol: Optional[float] = None,
        health_window: Optional[int] = None,
        error_rate_trigger: Optional[float] = None,
        checkpoint_dir: Optional[str] = None,
        job_key: Optional[str] = None,
    ):
        if not getattr(model, "swap_capable", False):
            raise TypeError(
                f"{type(model).__name__} is not swap-capable: ModelLifecycle "
                "needs the api.Model swap protocol (model_arrays / "
                "publish_model_arrays / kernel_constants_for)"
            )
        self.model = model
        self.retained = max(2, int(retained if retained is not None else config.model_versions_retained))
        self.canary = canary
        self.canary_rtol = float(
            canary_rtol if canary_rtol is not None else config.lifecycle_canary_rtol
        )
        window = int(health_window if health_window is not None else config.lifecycle_health_window)
        self.health_window = max(2, window)
        self.error_rate_trigger = float(
            error_rate_trigger
            if error_rate_trigger is not None
            else config.lifecycle_error_rate_trigger
        )
        self.checkpoint_dir = checkpoint_dir
        self.job_key = job_key
        self._ring: deque = deque(maxlen=self.retained)
        self._outcomes: deque = deque(maxlen=self.health_window)
        self.events: deque = deque(maxlen=256)
        self._quarantined: Optional[TrainerQuarantined] = None
        self._last_good: Optional[int] = None
        self._next_id = 1
        self.promote_rejected = 0
        self.swap_count = 0
        self.rollback_count = 0

        seed = model.model_arrays()
        if any(a is not None for a in seed):
            self._ring.append(
                ModelVersion(model.model_version, _host_copy(seed), "seed", time.time())
            )
            self._last_good = model.model_version
            self._next_id = model.model_version + 1
        if checkpoint_dir is not None:
            self._restore(checkpoint_dir, job_key)
        metrics.set_gauge("lifecycle.publishedVersion", self.model.model_version)

    # -- introspection -------------------------------------------------------
    @property
    def current(self) -> Optional[ModelVersion]:
        return self._ring[-1] if self._ring else None

    @property
    def last_good(self) -> Optional[int]:
        return self._last_good

    @property
    def quarantined(self) -> bool:
        return self._quarantined is not None

    def retained_versions(self) -> List[int]:
        return [v.version_id for v in self._ring]

    def _event(self, kind: str, version: int, reason: str = "") -> None:
        self.events.append(LifecycleEvent(kind, version, reason, time.time()))

    # -- the promotion gate --------------------------------------------------
    def _reject(self, reason: str, detail: str) -> None:
        self.promote_rejected += 1
        metrics.inc_counter("lifecycle.promoteRejected")
        self._event("rejected", self._next_id, f"{reason}: {detail}")
        raise PromotionRejected(reason, detail)

    def _gate(self, candidate: Tuple[Optional[np.ndarray], ...]) -> None:
        current = self.model.model_arrays()
        if len(candidate) != len(current):
            self._reject(
                "arity", f"candidate has {len(candidate)} arrays, serving model {len(current)}"
            )
        for i, (cand, cur) in enumerate(zip(candidate, current)):
            if cand is None:
                self._reject("shape", f"array {i} is None")
            if cur is not None and np.shape(cand) != np.shape(cur):
                self._reject(
                    "shape", f"array {i}: candidate {np.shape(cand)} vs serving {np.shape(cur)}"
                )
            if cur is not None and np.asarray(cur).dtype != cand.dtype:
                self._reject(
                    "dtype", f"array {i}: candidate {cand.dtype} vs serving {np.asarray(cur).dtype}"
                )
            if not np.all(np.isfinite(cand)):
                self._reject("nonfinite", f"array {i} contains NaN/Inf")
        if self.canary is not None:
            self._canary_gate(candidate, current)

    def _canary_outputs(self, arrays: Tuple) -> Dict[str, np.ndarray]:
        """Run the model's transform kernel over the pinned canary batch
        with `arrays` as the (unpublished) model operands; version is
        pinned to 0 on both sides so the comparison sees only the model."""
        import jax

        from .utils.packing import packed_device_get

        consts = jax.tree_util.tree_map(
            jax.device_put, self.model.kernel_constants_for(tuple(arrays), 0)
        )
        cols = {k: jax.numpy.asarray(v) for k, v in self.canary.items()}
        out = self.model.transform_kernel(consts, cols, KernelContext())
        names = [k for k in out if k not in self.canary]
        host = packed_device_get(*[out[k] for k in names], sync_kind="lifecycle")
        return dict(zip(names, host))

    def _canary_gate(self, candidate: Tuple, current: Tuple) -> None:
        if all(a is None for a in current):
            return  # nothing to regress against
        got = self._canary_outputs(candidate)
        want = self._canary_outputs(current)
        for name, ref in want.items():
            cand = got[name]
            if not np.allclose(
                np.asarray(cand, np.float64),
                np.asarray(ref, np.float64),
                rtol=self.canary_rtol,
                atol=self.canary_rtol,
            ):
                diff = float(
                    np.max(np.abs(np.asarray(cand, np.float64) - np.asarray(ref, np.float64)))
                )
                self._reject(
                    "canary",
                    f"output {name!r} moved {diff:.3g} past rtol {self.canary_rtol} "
                    "vs the outgoing version",
                )

    # -- promote / rollback --------------------------------------------------
    def promote(self, arrays: Tuple, version: Optional[int] = None) -> ModelVersion:
        """Gate + persist + publish one candidate. Returns the retained
        `ModelVersion`; raises `PromotionRejected` (gate) or
        `TrainerQuarantined` (post-rollback). The swap itself is the
        model's single atomic reference assignment — a serve batch
        dispatched a microsecond earlier keeps the old version."""
        if self._quarantined is not None:
            metrics.inc_counter("lifecycle.quarantineRefused")
            raise self._quarantined
        faults.tick("lifecycle.promote")
        candidate = _host_copy(tuple(arrays))
        self._gate(candidate)
        version_id = self._next_id if version is None else int(version)
        entry = ModelVersion(version_id, candidate, "trainer", time.time())
        self._persist(entry)
        # the mid-publish kill window: snapshot durable, swap not yet done —
        # a resume re-publishes version_id instead of regressing to 0
        faults.tick("lifecycle.swap")
        self.model.publish_model_arrays(candidate, version_id)
        self._ring.append(entry)
        self._next_id = version_id + 1
        self.swap_count += 1
        metrics.inc_counter("lifecycle.swap")
        metrics.set_gauge("lifecycle.publishedVersion", version_id)
        if timeline.enabled():
            timeline.record_instant(
                timeline.LANE_LIFECYCLE, "lifecycle.promote", version=version_id
            )
        self._event("promoted", version_id)
        return entry

    def rollback(self, reason: str = "manual") -> ModelVersion:
        """Republish the last-good retained version (bit-exact host copies,
        ORIGINAL version id), quarantine the trainer, clear the health
        window. Raises if nothing good is retained."""
        target = None
        for entry in reversed(self._ring):
            if self._last_good is not None and entry.version_id == self._last_good:
                target = entry
                break
        if target is None and len(self._ring) >= 2:
            target = self._ring[-2]  # newest version that predates current
        if target is None:
            raise RuntimeError("rollback impossible: no retained good version")
        bad = self.model.model_version
        self.model.publish_model_arrays(target.arrays, target.version_id)
        restored = ModelVersion(target.version_id, target.arrays, "rollback", time.time())
        self._ring.append(restored)
        self.rollback_count += 1
        self._outcomes.clear()
        metrics.inc_counter("lifecycle.rollback")
        if timeline.enabled():
            timeline.record_instant(
                timeline.LANE_LIFECYCLE,
                "lifecycle.rollback",
                version=target.version_id,
                fromVersion=bad,
            )
        metrics.set_gauge("lifecycle.publishedVersion", target.version_id)
        self._event("rollback", target.version_id, f"from {bad}: {reason}")
        self._quarantined = TrainerQuarantined(bad, reason)
        metrics.inc_counter("lifecycle.quarantined")
        self._event("quarantined", bad, reason)
        self._persist(restored)
        return restored

    def release_quarantine(self) -> None:
        """Operator override: accept trainer output again (after the
        trainer was fixed/restarted)."""
        if self._quarantined is not None:
            self._event("released", self.model.model_version)
        self._quarantined = None

    # -- serve-side health ---------------------------------------------------
    def record_serve_ok(self) -> None:
        self._outcomes.append(0)
        self._last_good = self.model.model_version

    def record_guard_error(self, error: Optional[BaseException] = None) -> None:
        """One serve batch failed validation. At `error_rate_trigger` over
        a FULL sliding window, traffic rolls back automatically."""
        self._outcomes.append(1)
        metrics.inc_counter("lifecycle.guardErrors")
        if (
            self._quarantined is None
            and len(self._outcomes) >= self.health_window
            and sum(self._outcomes) / len(self._outcomes) >= self.error_rate_trigger
            and self._last_good is not None
            and self._last_good != self.model.model_version
        ):
            self.rollback(
                f"guard-error rate {sum(self._outcomes)}/{len(self._outcomes)} "
                f">= {self.error_rate_trigger}"
            )

    # -- persistence (JobSnapshot meta contract) -----------------------------
    def _persist(self, entry: ModelVersion) -> None:
        if self.checkpoint_dir is None:
            return
        from .ckpt import snapshot as _snapshot

        _snapshot.save_job_snapshot(
            self.checkpoint_dir,
            self.job_key,
            {"model": list(entry.arrays)},
            epoch=entry.version_id,
            meta={
                "publishedVersion": entry.version_id,
                "lastGoodVersion": self._last_good if self._last_good is not None else -1,
                "ringVersions": self.retained_versions() + [entry.version_id],
            },
        )

    def _restore(self, checkpoint_dir: str, job_key: Optional[str]) -> None:
        from .ckpt import snapshot as _snapshot

        template = list(self.model.model_arrays())
        snap = _snapshot.load_job_snapshot(
            checkpoint_dir, job_key, {"model": template}
        )
        if snap is None:
            return
        arrays = tuple(snap.sections["model"])
        version = int(snap.meta.get("publishedVersion", snap.epoch))
        last_good = int(snap.meta.get("lastGoodVersion", -1))
        self.model.publish_model_arrays(arrays, version)
        self._ring.append(
            ModelVersion(version, _host_copy(arrays), "restore", time.time())
        )
        self._last_good = last_good if last_good >= 0 else None
        self._next_id = version + 1
        metrics.inc_counter("lifecycle.restored")
        self._event("restored", version)
