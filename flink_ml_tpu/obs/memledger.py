"""HBM ledger — live device-memory accounting, peaks, budgets, forensics.

The obs layer so far accounts *flows* (h2d/readback bytes, collective
payloads, dispatch walls) but not *stocks*: nothing answers "what is
resident in device memory right now, and whose is it?" — so an OOM is an
opaque XLA `RESOURCE_EXHAUSTED` with no attribution, and the ROADMAP's
memory claims (a 1e9-weight LR training where the replicated path OOMs,
an LRU byte budget paging models host↔HBM) cannot be graded. Snap ML
(PAPERS.md) makes hierarchical memory-tier management the core design
lever; this module is the measurement half of that lever.

Every sanctioned allocation funnel reports here:

- `parallel/prefetch.stage_to_device` / `stage_from_callback` (budget
  admission + OOM wrapping on every upload; residency tracking when the
  caller declares a category),
- `data/devicecache.DeviceEpochCache` (ownership accounting: register on
  insert, release on evict/replace/clear — the ledger's `batchCache`
  live bytes equal the cache's own `devicecache.bytes` gauge by
  construction, pinned by `check_ledger_parity`),
- model publication (`api.AlgoOperator.device_constants`), optimizer
  carry staging, whole-fit stacked segments, checkpoint restore
  re-staging, serving micro-batch uploads.

Two accounting modes:

1. **Ownership entries** (`register`/`release`) — the owner knows the
   allocation's lifetime exactly (the device cache's LRU). Exact by
   construction.
2. **Tracked trees** (`track`) — long-lived arrays whose release point
   is the garbage collector's (published model constants, the optimizer
   carry, stacked whole-fit segments): each device leaf gets a
   `weakref.finalize` that releases its entry when the array object
   dies. Live bytes per category therefore converge to the bytes
   actually retained — the fit-end parity the acceptance tests pin.

Surfaces:

- gauges `hbm.live.<category>` + `hbm.live` (total) + `hbm.peak`
  (global watermark) + `hbm.peak.fit` (the last fit scope's peak),
  all flowing through `utils.metrics` into BENCH deltas and the
  Prometheus exporters;
- a `memory` timeline lane of Chrome counter events (`ph: "C"`) so
  Perfetto renders an HBM track aligned with dispatch/h2d/collective;
- `mark_peak()`/`peak_since(tok)` watermark tokens (the benchmark
  runner's per-entry `peakHbmBytes`);
- **budget admission**: under `config.hbm_budget_bytes` (env
  `FLINK_ML_TPU_HBM_BUDGET_BYTES`, default off) `admit()` raises a
  typed `HbmBudgetExceeded` naming the live category breakdown BEFORE
  the allocating dispatch — deterministic OOM-path coverage on the CPU
  tier-1 mesh. Admission only raises or passes: a loose budget is
  bit-identical to no budget by construction.
- **OOM forensics**: `wrap_oom(exc)` translates a real backend
  `RESOURCE_EXHAUSTED` into `HbmExhausted` carrying the ranked ledger
  snapshot (top-N entries by bytes with categories + allocation sites),
  optionally dumped as JSON to `FLINK_ML_TPU_HBM_DUMP` for
  `scripts/obs_report.py --hbm-dump`.

See docs/observability.md "Device memory".
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import weakref
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ..utils import metrics

__all__ = [
    "CATEGORIES",
    "HbmBudgetExceeded",
    "HbmExhausted",
    "register",
    "release",
    "track",
    "tracked_nbytes",
    "admit",
    "wrap_oom",
    "live_bytes",
    "peak_bytes",
    "mark_peak",
    "peak_since",
    "fit_peak_scope",
    "record_fleet_fit_peak",
    "snapshot",
    "ranked_entries",
    "dump_snapshot",
    "load_dump",
    "reset",
]

#: The sanctioned residency categories. `scratch` is the catch-all for
#: explicitly-tracked transients (nothing auto-files under it).
CATEGORIES = (
    "model",
    "optimizer",
    "batchCache",
    "streamSegments",
    "serving",
    "fleet",
    "online.state",
    "scratch",
)

_lock = threading.Lock()
_ids = itertools.count(1)
#: handle -> (category, nbytes, shape, dtype, site)
_entries: Dict[int, Tuple[str, int, Optional[Tuple], Optional[str], str]] = {}
_live: Dict[str, int] = {}
_total = 0
_peak = 0
_marks: Dict[int, int] = {}  # mark token -> max total seen since mark
#: id(array) -> ledger handle, for dedup of `track` on the same object.
#: Entries are removed by the finalizer that releases the handle.
_tracked_ids: Dict[int, int] = {}


class HbmBudgetExceeded(RuntimeError):
    """A staging request would exceed `config.hbm_budget_bytes`.

    Raised by the admission pre-check BEFORE the allocating dispatch, so
    the failure is a clean typed error naming who holds the memory —
    never an opaque backend crash. Carries `requested_bytes`,
    `budget_bytes`, `live_bytes` and the per-category `breakdown`."""

    def __init__(
        self,
        requested_bytes: int,
        budget_bytes: int,
        live: Dict[str, int],
        category: Optional[str] = None,
    ):
        self.requested_bytes = int(requested_bytes)
        self.budget_bytes = int(budget_bytes)
        self.live_bytes = int(sum(live.values()))
        self.breakdown = dict(sorted(live.items(), key=lambda kv: -kv[1]))
        self.category = category
        held = (
            ", ".join(f"{k}={v}" for k, v in self.breakdown.items())
            or "nothing ledgered"
        )
        super().__init__(
            f"staging {self.requested_bytes} bytes"
            + (f" ({category})" if category else "")
            + f" would exceed hbm_budget_bytes={self.budget_bytes}: "
            f"{self.live_bytes} bytes live ({held})"
        )


class HbmExhausted(RuntimeError):
    """A real backend RESOURCE_EXHAUSTED, wrapped with attribution: the
    ranked ledger snapshot (`snapshot`, top entries by bytes with
    categories and allocation sites) taken at failure time. The original
    backend error is chained as `__cause__`."""

    def __init__(self, message: str, snap: Dict[str, Any]):
        self.snapshot = snap
        top = "; ".join(
            f"{e['category']}:{e['nbytes']}b@{e['site']}"
            for e in snap.get("topEntries", [])[:3]
        )
        super().__init__(
            f"device memory exhausted: {message} — ledger: "
            f"{snap.get('liveBytes', 0)} bytes live, "
            f"peak {snap.get('peakBytes', 0)}"
            + (f"; top: {top}" if top else "")
        )


# ---------------------------------------------------------------------------
# core accounting
# ---------------------------------------------------------------------------

def _call_site() -> str:
    """file:line of the nearest caller outside the funnel plumbing — the
    allocation site an OOM report blames. Cheap relative to the staging
    work it annotates (one short stack walk, no traceback objects)."""
    skip = ("memledger.py", "prefetch.py")
    f = sys._getframe(1)
    while f is not None:
        fname = f.f_code.co_filename
        if not fname.endswith(skip):
            base = os.path.basename(os.path.dirname(fname))
            return f"{base}/{os.path.basename(fname)}:{f.f_lineno}"
        f = f.f_back
    return "unknown"


def _publish_locked(category: str) -> None:
    """Refresh gauges/peaks/timeline after a live-bytes change. Caller
    holds `_lock`."""
    global _peak
    metrics.set_gauge(f"hbm.live.{category}", _live.get(category, 0))
    metrics.set_gauge("hbm.live", _total)
    if _total > _peak:
        _peak = _total
        metrics.set_gauge("hbm.peak", _peak)
    for tok in _marks:
        if _total > _marks[tok]:
            _marks[tok] = _total
    from . import timeline

    if timeline.enabled():
        timeline.record_counter(
            timeline.LANE_MEMORY,
            "hbm",
            **{c: _live.get(c, 0) for c in CATEGORIES if _live.get(c)},
        )


def register(
    category: str,
    nbytes: int,
    shape: Optional[Tuple] = None,
    dtype: Optional[str] = None,
    site: Optional[str] = None,
) -> int:
    """Open a ledger entry: `nbytes` of device memory became resident
    under `category`. Returns the handle to `release` when the owner
    frees it. Ownership mode — for allocators that know their lifetime
    exactly (the device cache); GC-lifetime arrays use `track`."""
    global _total
    if category not in CATEGORIES:
        raise ValueError(f"unknown ledger category {category!r} (see CATEGORIES)")
    nbytes = int(nbytes)
    if site is None:
        site = _call_site()
    with _lock:
        handle = next(_ids)
        _entries[handle] = (category, nbytes, shape, dtype, site)
        _live[category] = _live.get(category, 0) + nbytes
        _total += nbytes
        _publish_locked(category)
    return handle


def release(handle: Optional[int]) -> None:
    """Close a ledger entry (idempotent; None and unknown handles are
    no-ops, so double-release and post-`reset` finalizers are safe)."""
    global _total
    if handle is None:
        return
    with _lock:
        entry = _entries.pop(handle, None)
        if entry is None:
            return
        category, nbytes = entry[0], entry[1]
        _live[category] = _live.get(category, 0) - nbytes
        _total -= nbytes
        _publish_locked(category)


def _leaf_arrays(tree) -> Iterable[Any]:
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            yield leaf


def _resident_nbytes(arr) -> int:
    """PER-DEVICE resident bytes of a device array: the bytes of ONE shard
    under the array's sharding, not the global `nbytes`. The ledger models
    a single device's HBM (the budget is per-device capacity), so a
    model-axis-sharded (d,) carry on an nm-way mesh ledgers d/nm — that
    difference IS the beyond-HBM headroom the 2D mesh buys, and summing
    global bytes would erase it. Replicated and single-device arrays have
    shard shape == global shape, so their accounting is unchanged."""
    nbytes = int(getattr(arr, "nbytes", 0))
    sharding = getattr(arr, "sharding", None)
    shape = getattr(arr, "shape", None)
    if sharding is None or shape is None or not hasattr(sharding, "shard_shape"):
        return nbytes
    try:
        shard_shape = sharding.shard_shape(tuple(shape))
    except (TypeError, ValueError):
        return nbytes
    total = 1
    for s in shape:
        total *= int(s)
    if total <= 0:
        return nbytes
    shard = 1
    for s in shard_shape:
        shard *= int(s)
    return (nbytes * shard) // total


def track(tree, category: str, site: Optional[str] = None):
    """Ledger every device-array leaf of `tree` under `category`,
    auto-releasing each entry when the array object is garbage
    collected (`weakref.finalize` — verified supported on jax arrays).
    Already-tracked leaves are skipped, so re-staging or re-tracking the
    same array never double-counts. Sharded leaves ledger PER-DEVICE
    shard bytes (see `_resident_nbytes`): `hbm.live.<category>` reads as
    one device's residency, never the sum across virtual hosts. Returns
    `tree` for chaining."""
    if site is None:
        site = _call_site()
    for arr in _leaf_arrays(tree):
        key = id(arr)
        with _lock:
            if key in _tracked_ids:
                continue
        handle = register(
            category,
            _resident_nbytes(arr),
            shape=tuple(getattr(arr, "shape", ())),
            dtype=str(getattr(arr, "dtype", "")),
            site=site,
        )
        with _lock:
            _tracked_ids[key] = handle
        weakref.finalize(arr, _release_tracked, key, handle)
    return tree


def _release_tracked(key: int, handle: int) -> None:
    with _lock:
        if _tracked_ids.get(key) == handle:
            del _tracked_ids[key]
    release(handle)


def tracked_nbytes(tree) -> int:
    """Ledgered bytes of `tree`'s device leaves (0 for untracked) —
    test/debug helper for parity assertions."""
    total = 0
    with _lock:
        for arr in _leaf_arrays(tree):
            handle = _tracked_ids.get(id(arr))
            if handle is not None and handle in _entries:
                total += _entries[handle][1]
    return total


# ---------------------------------------------------------------------------
# queries, watermarks
# ---------------------------------------------------------------------------

def live_bytes(category: Optional[str] = None) -> int:
    with _lock:
        if category is None:
            return _total
        return _live.get(category, 0)


def peak_bytes() -> int:
    with _lock:
        return _peak


def mark_peak() -> int:
    """Open a watermark: `peak_since(tok)` returns the max total live
    bytes observed between the mark and the query."""
    with _lock:
        tok = next(_ids)
        _marks[tok] = _total
        return tok


def peak_since(token: int, close: bool = True) -> int:
    with _lock:
        value = _marks.get(token, 0)
        if close:
            _marks.pop(token, None)
        return value


#: Gauge-cardinality cap for per-member fleet peak gauges: fleets larger
#: than this record only the first _FLEET_MEMBER_GAUGE_CAP member gauges
#: (the aggregate `hbm.peak.fit` always lands regardless).
_FLEET_MEMBER_GAUGE_CAP = 64


class fit_peak_scope:
    """Context manager bracketing one fit: on exit, the peak live bytes
    observed inside the scope land on the `hbm.peak.fit` gauge (the
    per-fit watermark next to the global `hbm.peak`).

    `member` namespaces the watermark per fleet member index
    (`hbm.peak.fit.member.<i>`) so peaks inside a FitFleet are
    attributable to the member whose state was in flight — a bare
    `hbm.peak.fit` keyed per stage-fit would attribute every member's
    staging to whichever fit ran last. The aggregate gauge still lands
    so dashboards keyed on it see fleet fits too."""

    def __init__(self, member: Optional[int] = None):
        self._member = member

    def __enter__(self):
        self._tok = mark_peak()
        return self

    def __exit__(self, *exc):
        peak = peak_since(self._tok)
        metrics.set_gauge("hbm.peak.fit", peak)
        if self._member is not None and self._member < _FLEET_MEMBER_GAUGE_CAP:
            metrics.set_gauge(f"hbm.peak.fit.member.{self._member}", peak)
        return False


def record_fleet_fit_peak(peak: int, num_members: int) -> None:
    """Attribute one fleet program's peak to every member that rode it.

    The fleet fit is ONE resident program — all N members share a single
    HBM watermark — so the honest per-member attribution is that same
    watermark on each member's gauge (capped at `_FLEET_MEMBER_GAUGE_CAP`
    members to bound gauge cardinality)."""
    metrics.set_gauge("hbm.peak.fit", peak)
    for i in range(min(num_members, _FLEET_MEMBER_GAUGE_CAP)):
        metrics.set_gauge(f"hbm.peak.fit.member.{i}", peak)


# ---------------------------------------------------------------------------
# budget admission
# ---------------------------------------------------------------------------

def admit(nbytes: int, category: Optional[str] = None) -> None:
    """Pre-dispatch budget check: raise `HbmBudgetExceeded` when staging
    `nbytes` more would push ledgered live bytes over
    `config.hbm_budget_bytes`. Off (None) = always admit; admission
    never mutates state, so a budget that never fires is bit-identical
    to no budget."""
    from .. import config

    budget = config.hbm_budget_bytes
    if budget is None or nbytes <= 0:
        return
    with _lock:
        total = _total
        live = {c: b for c, b in _live.items() if b}
    if total + int(nbytes) > int(budget):
        metrics.inc_counter("hbm.budget.rejected")
        raise HbmBudgetExceeded(int(nbytes), int(budget), live, category)


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


def wrap_oom(exc: BaseException) -> Optional[HbmExhausted]:
    """If `exc` is a backend out-of-memory error, build the typed
    `HbmExhausted` carrying the ranked ledger snapshot (and dump it to
    `FLINK_ML_TPU_HBM_DUMP` when set); otherwise None. Callers re-raise
    the wrapped error `from exc` so the backend message is chained."""
    if isinstance(exc, (HbmExhausted, HbmBudgetExceeded)):
        return None
    msg = str(exc)
    if not any(m in msg for m in _OOM_MARKERS):
        return None
    snap = snapshot()
    metrics.inc_counter("hbm.exhausted")
    dump_path = os.environ.get("FLINK_ML_TPU_HBM_DUMP")
    if dump_path:
        try:
            dump_snapshot(dump_path, snap)
        except OSError:
            pass
    first_line = msg.splitlines()[0] if msg else type(exc).__name__
    return HbmExhausted(first_line, snap)


def ranked_entries(top_n: int = 20) -> List[Dict[str, Any]]:
    """The live ledger entries ranked by bytes, largest first."""
    with _lock:
        entries = list(_entries.values())
    entries.sort(key=lambda e: -e[1])
    return [
        {
            "category": cat,
            "nbytes": nbytes,
            "shape": list(shape) if shape else None,
            "dtype": dtype,
            "site": site,
        }
        for cat, nbytes, shape, dtype, site in entries[:top_n]
    ]


def snapshot(top_n: int = 20) -> Dict[str, Any]:
    """The forensic ledger view: per-category live bytes, totals, peaks,
    and the top-N entries by bytes with categories + allocation sites."""
    with _lock:
        live = {c: b for c, b in _live.items() if b}
        total, peak, entry_count = _total, _peak, len(_entries)
    return {
        "liveBytes": total,
        "peakBytes": peak,
        "entryCount": entry_count,
        "categories": dict(sorted(live.items(), key=lambda kv: -kv[1])),
        "topEntries": ranked_entries(top_n),
    }


def dump_snapshot(path: str, snap: Optional[Dict[str, Any]] = None) -> Dict:
    """Write the forensic snapshot as JSON (the `HbmExhausted` dump
    format `scripts/obs_report.py --hbm-dump` renders)."""
    snap = snap if snap is not None else snapshot()
    with open(path, "w") as f:
        json.dump(snap, f, indent=2)
    return snap


def load_dump(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def reset() -> None:
    """Forget every entry and watermark (tests). Finalizers of arrays
    still alive will later call `release` with unknown handles — no-ops
    by design."""
    global _total, _peak
    with _lock:
        _entries.clear()
        _live.clear()
        _tracked_ids.clear()
        _marks.clear()
        _total = 0
        _peak = 0
    for c in CATEGORIES:
        metrics.set_gauge(f"hbm.live.{c}", 0)
    metrics.set_gauge("hbm.live", 0)
    metrics.set_gauge("hbm.peak", 0)
