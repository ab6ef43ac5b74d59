"""Bounded and unbounded iteration runtime.

TPU-native replacement for flink-ml-iteration (17,323 LoC): the reference
needs HeadOperator/TailOperator, epoch watermarks, a feedback channel and a
JobManager-side SharedProgressAligner because its operators run
asynchronously on a streaming engine (Iterations.java:144-170,
HeadOperator.java:101-117, SharedProgressAligner.java:127). Under SPMD the
whole problem disappears: a jitted `lax.while_loop` whose carry is the
model state IS the feedback edge, and a `psum` inside the body IS the
globally-aligned epoch. What remains worth keeping from the reference is
the *semantics*: maxIter/tol termination criteria
(common/iteration/TerminateOnMaxIter.java:56, TerminateOnMaxIterOrTol.java:72),
per-epoch listener callbacks (IterationListener.java:75), replayed datasets
(ReplayOperator.java — here: the dataset is resident on device and every
epoch re-reads it), and checkpoint/resume (here: epoch boundary = consistent
state; a checkpoint is (carry, epoch, criteria) written at epoch boundaries,
vs the reference's in-flight feedback-record logging, Checkpoints.java:92-143).
"""

from __future__ import annotations

import json
import os
import re
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import tracing
from ..utils import metrics

BodyFn = Callable[[Any, jax.Array], Tuple[Any, jax.Array]]


class IterationListener:
    """Per-epoch callbacks (iteration/IterationListener.java:75). Using a
    listener forces the host-driven loop (one jitted epoch per host step)
    instead of the fully on-device while_loop."""

    def on_epoch_watermark_incremented(self, epoch: int, carry) -> None:
        ...

    def on_iteration_terminated(self, carry) -> None:
        ...


@dataclass
class IterationResult:
    carry: Any
    num_epochs: int
    final_criteria: float


# ---------------------------------------------------------------------------
# checkpointing: epoch-boundary snapshots of the carry pytree
# ---------------------------------------------------------------------------

def checkpoint_job_key(stage, exclude=("maxIter", "tol")) -> str:
    """Stable job-identity key for checkpoint namespacing: estimator class
    name + a hash of its params. Two jobs with identical carry STRUCTURE
    but different hyper-parameters (e.g. two OnlineKMeans runs with the
    same k and d) then write different checkpoint files under a shared
    `config.iteration_checkpoint_dir` instead of silently cross-restoring.

    Termination-schedule params (`maxIter`, `tol`) are excluded by
    default: resuming an interrupted run with a larger maxIter is the
    canonical resume pattern and must map to the SAME job."""
    import hashlib

    params = {}
    for p, v in stage.get_param_map().items():
        if p.name in exclude:
            continue
        try:
            params[p.name] = p.json_encode(v)
        except Exception:
            params[p.name] = repr(v)
    blob = json.dumps(params, sort_keys=True, default=repr)
    digest = hashlib.sha1(blob.encode()).hexdigest()[:10]
    return f"{type(stage).__name__}-{digest}"


def _checkpoint_file(path: str, job_key: Optional[str]) -> str:
    if job_key is None:
        return os.path.join(path, "ckpt.npz")
    safe = re.sub(r"[^A-Za-z0-9._-]", "_", job_key)
    return os.path.join(path, f"ckpt-{safe}.npz")


def save_iteration_checkpoint(
    path: str, carry, epoch: int, criteria: float, job_key: Optional[str] = None
) -> None:
    """LEGACY carry-only writer, kept for direct users and as the
    migration source: the iteration loops themselves now snapshot through
    the versioned JobSnapshot format (flink_ml_tpu/ckpt/snapshot.py),
    whose loader also reads files this function wrote (one-way)."""
    from ..utils.packing import packed_device_get

    leaves = jax.tree_util.tree_leaves(carry)
    # one packed D2H transfer for the whole carry (a per-leaf np.asarray
    # pull is one blocking readback PER LEAF); counted as a checkpoint
    # host sync, so that the counters separate snapshot cost from drain cost
    leaves = packed_device_get(*leaves, sync_kind="checkpoint")
    os.makedirs(path, exist_ok=True)
    target = _checkpoint_file(path, job_key)
    tmp = target[: -len(".npz")] + ".tmp.npz"  # keep .npz so savez won't rename
    np.savez(
        tmp,
        epoch=np.int64(epoch),
        criteria=np.float64(criteria),
        **{f"leaf_{i}": np.asarray(leaf) for i, leaf in enumerate(leaves)},
    )
    os.replace(tmp, target)


def load_iteration_checkpoint(path: str, carry_like, job_key: Optional[str] = None):
    """Restore (carry, epoch, criteria) from `path`, or None if absent OR
    structurally incompatible. With a `job_key` (see `checkpoint_job_key`)
    the lookup is namespaced per job, so structurally-identical jobs
    sharing a directory stay isolated; un-keyed restores WARN, because the
    structural guard alone cannot tell two same-shaped jobs apart (leaves
    restore positionally against `carry_like`'s treedef — a foreign but
    compatible checkpoint would silently train from foreign state).

    Reads the versioned JobSnapshot format first (the format the loops
    write since the ckpt/ subsystem landed) and falls back to the legacy
    carry-only npz this module used to write — both through
    `ckpt.snapshot.load_job_snapshot`, so the guards live in one place."""
    from ..ckpt import snapshot as _snapshot

    snap = _snapshot.load_job_snapshot(path, job_key, templates={"model": carry_like})
    if snap is None:
        return None
    return snap.sections["model"], snap.epoch, snap.criteria


# ---------------------------------------------------------------------------
# bounded iteration
# ---------------------------------------------------------------------------

def iterate_bounded(
    body: BodyFn,
    init_carry,
    max_iter: int,
    tol: Optional[float] = None,
    listener: Optional[IterationListener] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_interval: int = 1,
    chunk_size: Optional[int] = None,
    job_key: Optional[str] = None,
) -> IterationResult:
    """Run `body(carry, epoch) -> (carry, criteria)` until termination.

    Termination mirrors TerminateOnMaxIterOrTol.java:72: stop when
    `epoch >= max_iter` or (if `tol` is set) `criteria <= tol`. With no
    listener and no checkpointing the whole loop compiles to one XLA
    while-loop (the feedback edge never leaves the device). With a
    listener, each epoch is one jitted device step (the analogue of
    ALL_ROUND operators observing epoch watermarks); with checkpointing
    only, epochs run in K-sized chunks (`chunk_size`, default from
    config.iteration_chunk_for) with one packed convergence readback per
    chunk — the stop epoch and final carry are identical to the per-epoch
    loop for any K because the tol check still runs every epoch inside
    the chunk program (see docs/performance.md).
    """
    if listener is None and checkpoint_dir is None:
        return _iterate_on_device(body, init_carry, max_iter, tol)
    return _iterate_host_driven(
        body,
        init_carry,
        max_iter,
        tol,
        listener,
        checkpoint_dir,
        checkpoint_interval,
        chunk_size,
        job_key,
    )


def _iterate_on_device(body: BodyFn, init_carry, max_iter: int, tol: Optional[float]):
    from ..utils import packing

    tol_value = -jnp.inf if tol is None else jnp.asarray(float(tol), jnp.float32)

    def cond(state):
        _, epoch, criteria = state
        return jnp.logical_and(epoch < max_iter, criteria > tol_value)

    def step(state):
        carry, epoch, _ = state
        new_carry, criteria = body(carry, epoch)
        return new_carry, epoch + 1, jnp.asarray(criteria, jnp.float32)

    init_state = (init_carry, jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, jnp.float32))
    # the whole loop is one XLA program, so per-epoch spans are impossible
    # here by design — a single `iteration.run` span carries the per-run
    # summary (epoch count, final criteria) instead
    with tracing.span("iteration.run", mode="device") as sp:
        with metrics.timed("iteration.device_loop"):
            # body is a per-call closure: a cached wrapper can never be
            # reused at this layer (chunked loops ride dispatch.chunk_runner
            # instead, which caches per body object)
            # tpulint: disable=retrace-hazard -- per-fit body closure; one dispatch per fit, reuse impossible here
            carry, epochs, criteria = jax.jit(
                lambda s: lax.while_loop(cond, step, s)
            )(init_state)
            # the loop's one convergence drain, through the accounted
            # funnel (doubles as the barrier that keeps the timing honest)
            epochs_h, criteria_h = packing.packed_device_get(
                epochs, criteria, sync_kind="drain"
            )
        num_epochs, final = int(epochs_h), float(criteria_h)
        sp.set_attr("epochs", num_epochs)
        sp.set_attr("finalCriteria", final)
    metrics.set_gauge("iteration.epochs", num_epochs)
    return IterationResult(carry, num_epochs, final)


def _iterate_host_driven(
    body,
    init_carry,
    max_iter,
    tol,
    listener,
    checkpoint_dir,
    checkpoint_interval,
    chunk_size=None,
    job_key=None,
):
    """Pipelined host-driven loop.

    With a listener, each epoch is one dispatched program (the listener
    contract exposes every (epoch, carry) pair); with checkpointing only,
    K epochs fuse into one chunk program whose ends clamp to checkpoint
    boundaries. Either way, dispatched steps queue up to
    `config.iteration_dispatch_depth` deep before their packed
    (epoch, criteria) scalars are drained, so host Python overlaps device
    execution instead of serializing on every convergence readback.

    Exactness under speculation: every dispatched step is criteria-guarded
    on device (the chunk's while condition re-checks `criteria > tol`
    before each epoch), so steps dispatched past the tol-fire epoch are
    identity programs — the final carry, stop epoch, and stop criteria
    are bit-identical to the fully synchronous per-epoch loop.
    """
    from .. import config
    from ..ckpt import faults
    from ..ckpt import snapshot as _snapshot
    from . import dispatch

    carry, epoch, criteria = init_carry, 0, float("inf")
    if checkpoint_dir is not None:
        restored = load_iteration_checkpoint(checkpoint_dir, init_carry, job_key)
        if restored is not None:
            carry, epoch, criteria = restored

    per_epoch = listener is not None
    K = 1 if per_epoch else config.iteration_chunk_for(max_iter, chunk_size)
    # Whole-fit resident program (config.whole_fit): with no listener and
    # no snapshot boundary strictly inside the remaining loop, the chunk
    # program covers the ENTIRE fit (K = remaining epochs) — one dispatch,
    # one packed readback, and the existing fit-end-boundary snapshot
    # logic below still fires on the retained carry. A listener or a
    # mid-fit boundary falls back to the chunked path (reason-counted).
    take_whole, _ = dispatch.whole_fit_plan(
        start_epoch=epoch,
        max_iter=max_iter,
        checkpoint_interval=(
            checkpoint_interval if checkpoint_dir is not None else None
        ),
        listener=per_epoch,
    )
    if take_whole:
        dispatch.account_whole_fit("iterate")
        K = max(1, max_iter - epoch)
    runner = dispatch.chunk_runner(body)
    donate_ok = dispatch.supports_donation()
    tol_value = jnp.asarray(-jnp.inf if tol is None else float(tol), jnp.float32)

    epoch_dev = jnp.asarray(epoch, jnp.int32)
    crit_dev = jnp.asarray(criteria, jnp.float32)
    queue = dispatch.DrainQueue(config.iteration_dispatch_depth)
    final_epoch, final_crit = epoch, criteria
    stopped = tol is not None and criteria <= tol

    def handle(drained):
        nonlocal final_epoch, final_crit, stopped
        for entry, e_act, crit in drained:
            advanced = e_act > final_epoch
            final_epoch, final_crit = e_act, crit
            metrics.set_gauge("iteration.epochs", final_epoch)
            if not advanced:
                continue  # speculative identity step past the stop epoch
            if per_epoch:
                listener.on_epoch_watermark_incremented(e_act, entry.carry)
            if (
                checkpoint_dir is not None
                and e_act == entry.end
                and e_act % checkpoint_interval == 0
            ):
                _snapshot.save_job_snapshot(
                    checkpoint_dir,
                    job_key,
                    {"model": entry.carry},
                    epoch=e_act,
                    criteria=crit,
                )
            if tol is not None and crit <= tol:
                stopped = True
            faults.tick("chunk")

    mode = "host" if per_epoch else "chunked"
    with tracing.span(
        "iteration.run", mode=mode, chunk=K, depth=queue.depth
    ) as run_sp:
        planned = epoch
        donate_next = False  # never consume the caller's init carry
        while planned < max_iter and not stopped:
            end = min(planned + K, max_iter)
            boundary = dispatch.next_boundary(
                planned, checkpoint_interval if checkpoint_dir is not None else None
            )
            if boundary is not None:
                end = min(end, boundary)
            # retain the post-chunk carry when the drain handler will need
            # it on host (listener callback / checkpoint snapshot) — a
            # retained carry must not be donated into the next dispatch
            retain = per_epoch or (
                checkpoint_dir is not None and end % checkpoint_interval == 0
            )
            step = runner.donating if (donate_next and donate_ok) else runner.borrowing
            with tracing.span(
                "iteration.epoch" if per_epoch else "iteration.chunk",
                epoch=planned,
                **({} if per_epoch else {"end": end}),
            ):
                with metrics.timed("iteration.epoch" if per_epoch else "iteration.chunk"):
                    carry, epoch_dev, crit_dev, packed = dispatch.timed_dispatch(
                        step,
                        carry, epoch_dev, crit_dev,
                        jnp.asarray(end, jnp.int32), tol_value,
                        start=planned, end=end,
                    )
            handle(
                queue.push(
                    dispatch.InFlight(planned, end, carry if retain else None, packed)
                )
            )
            planned = end
            donate_next = not retain
        handle(queue.drain_all())
        run_sp.set_attr("epochs", final_epoch)
        run_sp.set_attr("finalCriteria", final_crit)

    if listener is not None:
        listener.on_iteration_terminated(carry)
    return IterationResult(carry, final_epoch, final_crit)


def scan_epochs(body: BodyFn, init_carry, num_epochs: int):
    """Fixed-epoch variant returning the per-epoch criteria history, compiled
    as one `lax.scan` (useful for loss curves / benchmarks)."""

    def step(carry, epoch):
        new_carry, criteria = body(carry, epoch)
        return new_carry, criteria

    # tpulint: disable=retrace-hazard -- per-call body closure (bench/loss-curve helper); one dispatch per call
    carry, history = jax.jit(
        lambda c: lax.scan(step, c, jnp.arange(num_epochs, dtype=jnp.int32))
    )(init_carry)
    return carry, history


# ---------------------------------------------------------------------------
# unbounded (online) iteration
# ---------------------------------------------------------------------------

_STREAM_END = object()


def _wait_for(state) -> None:
    """Block until `state`'s device arrays are whole: the funnel's sync of
    kind `fence`, a wait with no copy, so no `iteration.host_sync*` and no
    `readback.*`. A leaf a later step was given by donation is that step's
    to finish, and is passed over. Where every leaf was ready before the
    wait was asked for, the device had run out of queued work:
    `online.fence.dry`."""
    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(state)
        if isinstance(leaf, jax.Array) and not leaf.is_deleted()
    ]
    if all(leaf.is_ready() for leaf in leaves):
        metrics.inc_counter("online.fence.dry")
    tracing.sync("fence", leaves, copy=False)


def iterate_unbounded(
    batches: Iterable,
    step: Callable[[Any, Any], Any],
    init_state,
    listener: Optional[IterationListener] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_interval: Optional[int] = None,
    job_key: Optional[str] = None,
    publish: Optional[Callable[[int, Any], None]] = None,
) -> Iterable[Tuple[int, Any]]:
    """Host-driven online loop (Iterations.iterateUnboundedStreams:118-131).

    For each incoming global mini-batch, advance the model state and publish
    a new model version — the analogue of the online estimators' feedback
    loop with `countWindowAll` global batches and the `modelDataVersion`
    gauge (OnlineKMeans.java:44-60, OnlineKMeansModel.java:166). Yields
    (model_version, state) after every batch; with `publish`, the version
    is published by the loop itself, before it is yielded.

    A batch is four phases (`obs.tracing.phase`, always counted, `fml.*`
    events of a profile): `online.batch` from the moment the loop asks for
    the batch until its version is published, and inside it `online.ingest`
    (the wait for the stager), `online.launch` (`step`: the dispatch of the
    device program, which the loop does not wait for) and `online.publish`
    (`publish`, counted as `online.versions`). Nothing here reads a device
    value back. The host may run `config.iteration_dispatch_depth` batches
    ahead of the device and no further: after a batch is published the loop
    waits (`online.fence`, inside `online.batch`) until the state of that
    many batches ago is whole on the device. Left to the runtime's own limit
    on programs in flight, every step in flight holds its fresh outputs, and
    a learner whose step returns a 0.8 GB coefficient filled the chip's
    memory with them (PERF.md, PR 31).

    Checkpoint/resume: with a checkpoint dir (explicit args or the
    process-wide `config.iteration_checkpoint_dir`), the (state, version)
    pair is snapshotted at global-batch boundaries — the version IS the
    stream position in global batches, so on restart against a replayed
    source the already-folded prefix is skipped and training continues
    exactly where it stopped. This is the SPMD analogue of the reference's
    unbounded iteration riding Flink's exactly-once checkpointing
    (iteration/checkpoint/Checkpoints.java:43-143: snapshot the operator
    state + in-flight feedback records; here a batch boundary is the only
    consistent cut, so there are no in-flight records to log).
    """
    from ..ckpt import faults
    from ..ckpt import snapshot as _snapshot

    if checkpoint_dir is None:
        from .. import config

        checkpoint_dir = config.iteration_checkpoint_dir
        # an explicit interval wins even when the DIR comes from config —
        # callers tuning snapshot cadence must not depend on where the
        # directory was resolved from
        interval = checkpoint_interval or config.iteration_checkpoint_interval
    else:
        interval = checkpoint_interval or 1

    state = init_state
    version = 0
    if checkpoint_dir is not None:
        restored = load_iteration_checkpoint(checkpoint_dir, init_state, job_key)
        if restored is not None:
            state, version, _ = restored
            # republish the restored model immediately so a serving model
            # reaches the checkpointed version before the next live batch
            if publish is not None:
                publish(version, state)
            yield version, state
    skip = version
    source = iter(batches)
    from .. import config as _config

    depth = max(1, _config.iteration_dispatch_depth)
    in_flight: deque = deque(maxlen=depth + 1)
    while True:
        with tracing.phase("online.batch") as whole:
            with tracing.phase("online.ingest") as ingest:
                batch = next(source, _STREAM_END)
                if batch is _STREAM_END or skip > 0:
                    # no batch of this loop's: the stream's end, or a replayed
                    # prefix already folded into the checkpoint
                    ingest.void()
                    whole.void()
            if batch is _STREAM_END:
                break
            if skip > 0:
                skip -= 1
                continue
            with tracing.phase("online.launch"):
                with tracing.span("iteration.epoch", epoch=version, mode="unbounded"):
                    state = step(state, batch)
            version += 1
            if listener is not None:
                listener.on_epoch_watermark_incremented(version, state)
            if checkpoint_dir is not None and version % interval == 0:
                # the version IS the stream offset in global batches — stored
                # in meta so a resume against a non-replayed source is caught
                _snapshot.save_job_snapshot(
                    checkpoint_dir,
                    job_key,
                    {"model": state},
                    epoch=version,
                    meta={"streamOffset": version},
                )
            faults.tick("batch")
            if publish is not None:
                with tracing.phase("online.publish"):
                    publish(version, state)
                metrics.inc_counter("online.versions")
            in_flight.append(state)
            if len(in_flight) > depth:
                with tracing.phase("online.fence"):
                    _wait_for(in_flight.popleft())
        yield version, state
    if checkpoint_dir is not None:
        # the stream completed: clear the checkpoint so a NEW job reusing
        # this dir does not resume from (and skip past) a finished run —
        # sharded cuts (manifests + shards) included
        from ..ckpt import coordinator as _coordinator

        for file in (
            _snapshot.snapshot_file(checkpoint_dir, job_key),
            _checkpoint_file(checkpoint_dir, job_key),
        ):
            if os.path.exists(file):
                os.remove(file)
        _coordinator.purge(checkpoint_dir, job_key)
    if listener is not None:
        listener.on_iteration_terminated(state)
