"""Dispatch pipeline — chunked epoch programs with bounded-depth async drains.

A warm LogisticRegression fit is a few milliseconds busy on device; the
rest of its wall is a fixed dispatch+readback latency, paid once per
host↔device synchronization (what one costs on today's machine is the
first thing the ledger measures — ROADMAP.md S2). The
reference hides the same cost with epoch watermarks + chunked all-reduce
batching (its per-epoch progress is batched through the feedback channel,
not round-tripped through the driver). The TPU-native equivalent here has
two parts:

1. **Epoch chunking** — `chunk_runner(body)` compiles `body` into a
   program that advances up to K epochs in one `lax.while_loop`, reading
   back ONE packed (epoch, criteria) scalar pair per chunk instead of one
   criteria scalar per epoch. The tol check runs *inside* the chunk at
   every epoch, in the same order as the unchunked host loop, so the stop
   epoch and the final carry are bit-identical for any K.

2. **Bounded-depth speculation** — because a chunk whose entry criteria
   already satisfies tol is an identity function (the while condition is
   false on entry), chunks can be dispatched ahead of their predecessors'
   convergence readbacks without changing semantics. `DrainQueue` holds up
   to `config.iteration_dispatch_depth` dispatched chunks whose packed
   scalars have not been read back; host Python overlaps device execution
   instead of serializing on every chunk.

Carry donation: the chunk programs ping-pong the carry in place in HBM
(`donate_argnums`) when the backend supports buffer donation and the
caller does not need to retain the pre-chunk carry (checkpoint boundaries
and listener callbacks retain; everything else donates).

Every blocking drain goes through the timed funnel `tracing.sync("drain",
...)` and counts as `iteration.host_sync` (obs/tracing).

Drain boundaries are also the job-checkpoint hook points: a drained chunk
whose end lands on a checkpoint boundary (`next_boundary` clamps chunk
ends so it always does) has its retained carry snapshotted through the
JobSnapshot API (flink_ml_tpu/ckpt/snapshot.py) by the drain handlers in
`parallel/iteration.py` and `ops/optimizer.py`, and the fault-injection
`chunk` site ticks once per drained entry (docs/fault_tolerance.md).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from ..obs import hist, timeline, tracing
from ..utils import metrics


def supports_donation() -> bool:
    """Buffer donation is a no-op (with a warning) on the CPU backend."""
    import jax

    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# whole-fit resident programs: eligibility + accounting
# ---------------------------------------------------------------------------
#
# Under `config.whole_fit == "auto"` the training loops compile the ENTIRE
# fit — epoch loop to maxIter, per-epoch convergence check, final model
# update, and the packed result — into one resident device program, so a
# fit is exactly ONE dispatch and ONE packed readback regardless of the
# chunk knobs. The compile key is the (shape-bucket x packed-hyperparam
# layout): data shapes and the loss are jit static structure, while the
# packed f32 hyper vector, maxIter, tol, and the carry are runtime
# operands — repeated fits at one shape bucket re-enter one executable.
# `whole_fit_plan` is the central eligibility decision; a fit that cannot
# be resident falls back to the chunked DrainQueue path below, counted per
# reason (docs/performance.md "Whole-fit resident programs").

#: The fallback-reason label set (`dispatch.whole_fit_fallback.<reason>`):
#: - checkpoint_interval: a snapshot boundary lands strictly inside the
#:   fit — the chunked path must surface the carry at that epoch.
#: - device_cache_budget: the stacked stream data source does not fit the
#:   `config.device_cache_bytes` HBM budget (or the cache is disabled).
#: - ragged_batches: stream batches bucket to different row counts, so no
#:   single stacked (nb, rows, cols) array exists to index in-program.
#: - listener: a per-epoch listener needs every (epoch, carry) pair on
#:   the host — resident programs have no per-epoch host boundary.
WHOLE_FIT_FALLBACK_REASONS = (
    "checkpoint_interval",
    "device_cache_budget",
    "ragged_batches",
    "listener",
)


def whole_fit_enabled() -> bool:
    """Is the whole-fit resident-program mode on (`config.whole_fit`)?"""
    from .. import config

    return config.whole_fit == "auto"


def account_whole_fit(kind: str = "fit") -> None:
    """Count a fit taking the resident-program path (`dispatch.whole_fit`
    + a per-loop kind: sgd / stream / lloyd / iterate / fleet — `fleet`
    counts ONE for the whole N-member vmapped program, which is the
    point: `fleet.modelsTrained` / `dispatch.whole_fit.fleet` is the
    amortization ratio)."""
    metrics.inc_counter("dispatch.whole_fit")
    metrics.inc_counter(f"dispatch.whole_fit.{kind}")


def account_whole_fit_fallback(reason: str) -> None:
    """Count a whole-fit-eligible loop falling back to the chunked path,
    labelled with WHY (`dispatch.whole_fit_fallback.<reason>`) — the benchmark
    runner surfaces the totals, so a config change that silently knocks
    fits off the resident path shows up as a counter jump."""
    metrics.inc_counter("dispatch.whole_fit_fallback")
    metrics.inc_counter(f"dispatch.whole_fit_fallback.{reason}")
    if timeline.enabled():
        timeline.record_instant(
            timeline.LANE_DISPATCH, "whole_fit.fallback", reason=reason
        )


def whole_fit_plan(
    *,
    start_epoch: int,
    max_iter: int,
    checkpoint_interval: Optional[int] = None,
    data_bytes: Optional[int] = None,
    uniform_batches: bool = True,
    listener: bool = False,
) -> Tuple[bool, Optional[str]]:
    """The central whole-fit eligibility decision: (take, fallback_reason).

    `checkpoint_interval` is the snapshot cadence when checkpointing is
    active (None = no checkpointing): a boundary strictly inside
    (start_epoch, max_iter) forces the chunked path; a boundary AT fit end
    stays whole-fit — the loop snapshots once after its single readback.
    `data_bytes` is the stacked stream data source's size, checked against
    the device-cache budget. Returns (False, None) with NO fallback count
    when the mode is off — fallbacks are only meaningful for fits that
    asked to be resident."""
    if not whole_fit_enabled():
        return False, None
    reason = None
    if listener:
        reason = "listener"
    if reason is None and checkpoint_interval is not None:
        boundary = next_boundary(start_epoch, checkpoint_interval)
        if boundary is not None and boundary < max_iter:
            reason = "checkpoint_interval"
    if reason is None and not uniform_batches:
        reason = "ragged_batches"
    if reason is None and data_bytes is not None:
        from ..data.devicecache import within_device_budget

        if not within_device_budget(data_bytes):
            reason = "device_cache_budget"
    if reason is not None:
        account_whole_fit_fallback(reason)
        return False, reason
    return True, None


# ---------------------------------------------------------------------------
# chunk runner: K epochs of `body` as one program
# ---------------------------------------------------------------------------

class ChunkRunner(NamedTuple):
    """Jitted chunk steppers for one body function.

    Both advance `(carry, epoch, criteria)` to `min(chunk_end, tol-fire)`
    and additionally return a packed f32 [epoch, criteria] pair for a
    single-transfer drain. `donating` consumes the input state buffers
    (in-place HBM ping-pong); `borrowing` leaves them valid — use it when
    the pre-chunk carry must stay readable (checkpoint snapshot pending,
    listener holding a reference) or on backends without donation.
    """

    donating: Callable
    borrowing: Callable


_runner_cache: Dict[Any, ChunkRunner] = {}


def chunk_runner(body) -> ChunkRunner:
    """Build (or fetch) the chunk steppers for `body(carry, epoch) ->
    (carry, criteria)`. Cached per body object so repeated loops over the
    same body reuse the compiled executables."""
    cached = _runner_cache.get(body)
    if cached is not None:
        return cached

    import jax
    import jax.numpy as jnp
    from jax import lax

    def chunk_step(carry, epoch, criteria, chunk_end, tol_value):
        def cond(state):
            _, e, crit = state
            return jnp.logical_and(e < chunk_end, crit > tol_value)

        def step(state):
            c, e, _ = state
            new_c, crit = body(c, e)
            return new_c, e + 1, jnp.asarray(crit, jnp.float32)

        carry, epoch, criteria = lax.while_loop(
            cond, step, (carry, epoch, criteria)
        )
        packed = jnp.stack([epoch.astype(jnp.float32), criteria])
        return carry, epoch, criteria, packed

    runner = ChunkRunner(
        # tpulint: disable=retrace-hazard -- wrapper pair cached per body object in _runner_cache (keyed on `body`)
        donating=jax.jit(chunk_step, donate_argnums=(0, 1, 2)),
        # tpulint: disable=retrace-hazard -- wrapper pair cached per body object in _runner_cache (keyed on `body`)
        borrowing=jax.jit(chunk_step),
    )
    _runner_cache[body] = runner
    return runner


def clear_runner_cache() -> None:
    _runner_cache.clear()


def timed_dispatch(step: Callable, *args, start: int = None, end: int = None):
    """THE accounted chunk-dispatch funnel: every chunk program launch in
    the iteration runtime rides through here, so the host-side dispatch
    cost is the always-counted `fit.launch` phase (`fit.launch.ns` and
    `.n`; `fml.fit.launch` in a profile; one a chunk where a fit is
    launched in chunks; the benchmark runner's `hostDispatchMs`) and one
    timeline `dispatch`-lane event, and the dispatch-wall
    attribution (`obs.timeline.dispatch_attribution`) can split every
    fit's wall into dispatch + device + readback + idle-gap. On an async
    backend this times the enqueue; on CPU, the synchronous execution —
    either way it is exactly the time the host thread was captive to the
    launch. `start`/`end` are the chunk's planned epoch range (drives the
    per-epoch attribution). Under a supervised fit
    (parallel/supervisor.py) every launch is also a host-health
    boundary: the supervisor's `host.die`/`host.hang` fault sites tick
    here (the mid-epoch chaos axis) and the launch duration feeds the
    hang watchdog's chunk-wall EMA."""
    from . import supervisor

    supervisor.pulse_boundary(supervisor.PHASE_DISPATCH)
    # the launch is the always-counted `fit.launch` phase: one pair of
    # clock reads feeds the phase and the timeline's one event of it (the
    # dispatch lane's, below: the phase marks no host lane)
    with tracing.phase("fit.launch", marks=False) as launch:
        try:
            out = step(*args)
        except Exception as e:
            # a backend RESOURCE_EXHAUSTED surfacing from the launch becomes
            # the typed HbmExhausted carrying the ranked ledger snapshot —
            # the OOM names who holds the memory, not just that it ran out
            from ..obs import memledger

            wrapped = memledger.wrap_oom(e)
            if wrapped is not None:
                raise wrapped from e
            raise
    t0, dur_ns = launch.start_ns, launch.dur_ns
    supervisor.note_progress(dur_ns / 1e9)
    if timeline.enabled():
        attrs = {}
        if start is not None:
            attrs["start"] = int(start)
        if end is not None:
            attrs["end"] = int(end)
        timeline.record_complete(
            timeline.LANE_DISPATCH, "dispatch.chunk", t0, dur_ns, **attrs
        )
    return out


# ---------------------------------------------------------------------------
# bounded-depth drain queue
# ---------------------------------------------------------------------------

class InFlight(NamedTuple):
    """One dispatched, undrained chunk."""

    start: int  # planned first epoch of the chunk (speculative frontier)
    end: int  # planned past-the-end epoch
    carry: Any  # device carry AFTER the chunk (None when not retained)
    packed: Any  # device f32 [epoch, criteria]


class DrainQueue:
    """Bounded-depth queue of dispatched chunks awaiting their convergence
    readback. `push` drains the oldest entry once more than `depth` chunks
    are in flight; `drain_all` empties it. Every drain is one blocking
    packed-scalar readback through the funnel (`tracing.sync("drain", ...)`:
    `iteration.host_sync`)."""

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        # tpulint: disable=unbounded-queue -- depth-bounded by construction: push() drains past self.depth in the same call, single-threaded
        self._q: deque = deque()
        tracing.set_dispatch_depth(self.depth)

    def __len__(self) -> int:
        return len(self._q)

    def push(self, entry: InFlight) -> List[Tuple[InFlight, int, float]]:
        """Queue a dispatched chunk; returns the drained (entry, epoch,
        criteria) records (empty while the queue is under its depth)."""
        self._q.append((entry, time.perf_counter_ns()))
        if timeline.enabled():  # the dispatch window is a flow channel too
            timeline.record_instant(
                timeline.LANE_FLOW, "drainqueue.push", depth=len(self._q)
            )
        drained = []
        while len(self._q) > self.depth:
            drained.append(self._drain_one())
        return drained

    def drain_all(self) -> List[Tuple[InFlight, int, float]]:
        out = []
        while self._q:
            out.append(self._drain_one())
        return out

    def _drain_one(self) -> Tuple[InFlight, int, float]:
        from . import supervisor

        entry, pushed_ns = self._q.popleft()
        # the blocking readback is where a wedged collective manifests —
        # the supervised mid-collective boundary sits right before it
        supervisor.pulse_boundary(supervisor.PHASE_COLLECTIVE)
        t0_ns = time.perf_counter_ns()
        host = tracing.sync("drain", entry.packed)
        end_ns = time.perf_counter_ns()
        # chunk wall: dispatch push -> drained scalar on host, the
        # per-chunk latency distribution of the dispatch pipeline — and
        # the hang watchdog's EMA sample under a supervised fit
        hist.record("iteration.chunkWallMs", (end_ns - pushed_ns) / 1e6)
        supervisor.note_progress((end_ns - pushed_ns) / 1e9)
        if timeline.enabled():
            # estimated device-execution interval: dispatch end to the
            # blocking readback start (exact on a synchronous backend,
            # an upper bound under async dispatch — the drain may also
            # have waited on still-running device work)
            timeline.record_complete(
                timeline.LANE_DEVICE,
                "device.chunk(est)",
                pushed_ns,
                max(0, t0_ns - pushed_ns),
                start=entry.start,
                end=entry.end,
            )
        return entry, int(host[0]), float(host[1])


def drain_packed(packed) -> Tuple[int, float]:
    """Blocking readback of one packed [epoch, criteria] pair (the
    depth-1 / tail path), with the same accounting as DrainQueue."""
    from . import supervisor

    supervisor.pulse_boundary(supervisor.PHASE_COLLECTIVE)
    t0_ns = time.perf_counter_ns()
    host = tracing.sync("drain", packed)
    supervisor.note_progress((time.perf_counter_ns() - t0_ns) / 1e9)
    return int(host[0]), float(host[1])


def next_boundary(epoch: int, interval: Optional[int]) -> Optional[int]:
    """The first checkpoint boundary strictly after `epoch` (None without
    checkpointing). Chunk ends clamp to boundaries so snapshots keep their
    exact epoch cadence under chunking."""
    if not interval or interval <= 0:
        return None
    return (epoch // interval + 1) * interval
