"""Input staging — accounted H2D uploads, shape bucketing, async prefetch.

The round-5 story for the *output* side of the host link (every readback is a
counted `readback.*` event riding the packed funnels) applied to the
*input* side. Three pieces, shared by every training loop and the serving
runner:

1. **Accounted staging** — `stage_to_device` / `stage_from_callback` are
   the ONLY sanctioned host→device transfer calls in `models/` and `ops/`
   (`scripts/check_upload_accounting.py` fails the build on a raw
   `jax.device_put` there, the mirror of the collective-accounting gate).
   Every upload increments `h2d.bytes` / `h2d.count`, so the BENCH
   metrics delta answers "how many bytes were uploaded host→device"
   as exhaustively as it answers the readback question. Device→device
   re-placements transfer nothing and are not counted.

2. **Batch-shape bucketing** — `next_bucket` / `pad_rows`, the serving
   runner's recompile-bounding shape schedule (powers of two, pad =
   repeat the last REAL row — guard-safe by construction) promoted to a
   shared helper so the stream-training staging paths use the identical
   policy. Training paths pair the padding with weight-0 masking, which
   keeps bucketing bit-exact: a repeated row at weight 0 contributes
   +0.0 to every loss/gradient/count reduction.

3. **Double-buffered prefetch** — `Prefetcher` runs a caller-supplied
   `stage` function in ONE worker thread, up to `config.
   input_prefetch_depth` items ahead of consumption, yielding results in
   input order (a single worker keeps native-cache access serial, the
   constraint the hand-rolled loops in `ops/optimizer.py` and the KMeans
   stream fit enforced separately before this module replaced them).
   Batch b+1's cache read + pack + H2D upload ride under batch b's
   compute — the overlap the reference gets from DataCacheReader on
   Flink's async mailbox. Since the flow-control sweep the window is a
   `flow.BoundedChannel` (credit-based backpressure, per-consumer
   overload policies — the online estimators run their ingest through
   the same class with `shed_oldest`/`sample`), the worker is spawned by
   `flow.pump` (a worker error closes the channel with the error, so it
   re-raises at the consumer instead of silently stalling it), and every
   stage execution is timed by a `flow.StragglerWatchdog`.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

from .. import flow
from ..obs import memledger, timeline
from ..utils import metrics

__all__ = [
    "stage_to_device",
    "stage_from_callback",
    "next_bucket",
    "pad_rows",
    "slice_rows",
    "Prefetcher",
]


# ---------------------------------------------------------------------------
# accounted H2D staging
# ---------------------------------------------------------------------------

def _host_nbytes(tree) -> int:
    """Bytes that will actually cross host→device: numpy leaves only —
    already-device-resident (jax) leaves re-place without a host upload."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, np.ndarray):
            total += leaf.nbytes
        elif not isinstance(leaf, jax.Array) and hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


def _admit_nbytes(tree, sharding) -> int:
    """Bytes the staging will make RESIDENT on one device — what budget
    admission must check, as distinct from `_host_nbytes` (bytes uploaded
    from the host). With a sharding that splits the arrays, each device
    receives only its shard: a model-axis-sharded (d,) carry admits d/nm
    bytes against the per-device `config.hbm_budget_bytes`, which is
    exactly how the 2D mesh trains models whose replicated staging is
    rejected. No sharding (or a replicated one) admits the full bytes —
    identical to the pre-2D behaviour."""
    if sharding is None or not hasattr(sharding, "shard_shape"):
        return _host_nbytes(tree)
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            continue  # already resident: re-placement, not new residency
        nbytes = int(getattr(leaf, "nbytes", 0))
        shape = tuple(getattr(leaf, "shape", ()))
        size = 1
        for s in shape:
            size *= int(s)
        try:
            shard_shape = sharding.shard_shape(shape)
        except (TypeError, ValueError):
            total += nbytes
            continue
        ssize = 1
        for s in shard_shape:
            ssize *= int(s)
        total += (nbytes * ssize) // size if size > 0 else nbytes
    return total


def account_h2d(nbytes: int, arrays: int = 1, seconds: Optional[float] = None) -> None:
    """Fold one host→device transfer into the registry — the upload-side
    sibling of the readback funnel `obs.tracing.sync`. When the caller measured
    the staging call (`seconds`), the transfer also lands on the
    timeline's `h2d` lane (on an async backend that duration is the
    submit cost, not the wire time)."""
    import time

    metrics.inc_counter("h2d.count", arrays)
    metrics.inc_counter("h2d.bytes", int(nbytes))
    if timeline.enabled():
        dur_ns = int((seconds or 0.0) * 1e9)
        timeline.record_complete(
            timeline.LANE_H2D,
            "h2d",
            time.perf_counter_ns() - dur_ns,
            dur_ns,
            bytes=int(nbytes),
            arrays=arrays,
        )


def stage_to_device(tree, sharding=None, category: Optional[str] = None):
    """Accounted `jax.device_put`: upload a host array (or pytree of
    arrays; dtypes canonicalize exactly as `device_put` does) and count
    the host bytes moved. The one H2D funnel `models/` and `ops/` are
    allowed to call (see `scripts/check_upload_accounting.py`).

    Every call is budget-admitted against `config.hbm_budget_bytes`
    (typed `HbmBudgetExceeded` BEFORE the allocating dispatch) and a
    real backend OOM is re-raised as `HbmExhausted` with the ranked
    ledger snapshot. `category` additionally ledgers the staged arrays'
    *residency* (obs/memledger.py) — declare it for long-lived uploads
    (model constants, the optimizer carry, stacked whole-fit segments,
    serving batches); leave it None for transients and for batches the
    DeviceEpochCache will own (the cache does its own exact
    register/release accounting, so a category here would double
    count)."""
    import time

    import jax

    if sharding is None and all(
        isinstance(leaf, jax.Array) for leaf in jax.tree_util.tree_leaves(tree)
    ):
        # already resident (a device-born stream's batch): nothing crosses the
        # host link and nothing is copied, so there is nothing to place, to
        # admit or to count; a `category` still ledgers the residency
        if category is not None:
            memledger.track(tree, category)
        return tree
    nbytes = _host_nbytes(tree)
    memledger.admit(_admit_nbytes(tree, sharding), category)
    t0 = time.perf_counter()
    try:
        if sharding is not None:
            out = jax.device_put(tree, sharding)
        else:
            out = jax.device_put(tree)
    except Exception as e:
        wrapped = memledger.wrap_oom(e)
        if wrapped is not None:
            raise wrapped from e
        raise
    if nbytes:
        account_h2d(nbytes, seconds=time.perf_counter() - t0)
    if category is not None:
        memledger.track(out, category)
    return out


def stage_from_callback(shape, sharding, data_callback, category: Optional[str] = None):
    """Accounted `jax.make_array_from_callback` (the per-shard zero-copy
    staging path of `_batchify`); bytes are counted from the staged
    array's own dtype, so callers need not precompute it. Budget
    admission, OOM wrapping and optional residency tracking exactly as
    `stage_to_device` (the byte estimate for admission uses the shape's
    float32 size when the dtype is only known post-staging)."""
    import time

    import jax

    admit_shape = tuple(shape)
    if hasattr(sharding, "shard_shape"):
        try:
            admit_shape = sharding.shard_shape(tuple(shape))
        except (TypeError, ValueError):
            pass
    memledger.admit(int(np.prod(admit_shape)) * 4, category)
    t0 = time.perf_counter()
    try:
        out = jax.make_array_from_callback(tuple(shape), sharding, data_callback)
    except Exception as e:
        wrapped = memledger.wrap_oom(e)
        if wrapped is not None:
            raise wrapped from e
        raise
    account_h2d(
        int(np.prod(shape)) * out.dtype.itemsize, seconds=time.perf_counter() - t0
    )
    if category is not None:
        memledger.track(out, category)
    return out


# ---------------------------------------------------------------------------
# batch-shape bucketing (shared with serving.MicroBatchServer)
# ---------------------------------------------------------------------------

def next_bucket(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest bucket >= n. Default schedule: powers of two (>= 8), the
    classic recompile-bounding shape schedule; an explicit sorted bucket
    list wins when the traffic distribution is known."""
    if n <= 0:
        return n  # empty batch: nothing to pad
    if buckets:
        for b in buckets:
            if b >= n:
                return int(b)
        return int(n)  # beyond the largest bucket: exact shape
    b = 8
    while b < n:
        b <<= 1
    return b


def pad_rows(col, n: int, bucket: int):
    """Pad a column from n to bucket rows by repeating its final row (a
    real row: guard-safe — a copy of real data can never fire a
    validation guard the real data would not). Works for host numpy,
    device arrays and SparseBatch; training callers mask the padding
    with weight 0, which keeps the pad bit-invisible to every reduction."""
    if bucket == n:
        return col
    from ..table import SparseBatch

    if isinstance(col, SparseBatch):
        return SparseBatch(
            col.size,
            pad_rows(col.indices, n, bucket),
            pad_rows(col.values, n, bucket),
        )
    try:
        import jax

        if isinstance(col, jax.Array):
            import jax.numpy as jnp

            reps = jnp.broadcast_to(col[n - 1 :], (bucket - n,) + col.shape[1:])
            return jnp.concatenate([col, reps])
    except ImportError:  # pragma: no cover
        pass
    col = np.asarray(col)
    reps = np.broadcast_to(col[n - 1 :], (bucket - n,) + col.shape[1:])
    return np.concatenate([col, reps])


def slice_rows(col, n: int):
    """Undo `pad_rows` on an output column (device slice, no host pull)."""
    from ..table import SparseBatch

    if isinstance(col, SparseBatch):
        return SparseBatch(col.size, col.indices[:n], col.values[:n])
    return col[:n]


# ---------------------------------------------------------------------------
# bounded-depth single-worker prefetch
# ---------------------------------------------------------------------------

class Prefetcher:
    """Run `stage(item)` in one worker thread up to `depth` items ahead.

    The staging window is a `flow.BoundedChannel`: with the default
    `block` policy, `iterate(items)` yields staged results strictly in
    input order — no drops, no reordering, whatever the relative speed of
    producer and consumer (credit-based backpressure: the worker stalls
    once `depth` items wait unconsumed). The online estimators pass
    `policy="shed_oldest"`/`"sample"` for bounded-memory, tracked-
    staleness ingest instead (see docs/flow_control.md). The worker is
    created per iteration and torn down when the generator closes
    (including early exits: a training loop that stops on tol simply
    abandons the generator and the speculative staging work is
    cancelled). An exception raised inside `stage` — or by the source
    iterable — surfaces to the consuming iterator, re-raised at the next
    `__next__` after the items staged before it; a dead worker can never
    silently stall the consumer. `depth` defaults to
    `config.input_prefetch_depth`.
    """

    def __init__(
        self,
        stage: Callable[[Any], Any],
        depth: Optional[int] = None,
        policy: str = flow.BLOCK,
        name: str = "prefetch",
    ):
        from .. import config

        self.stage = stage
        self.depth = max(1, int(depth if depth is not None else config.input_prefetch_depth))
        self.policy = policy
        self.name = name
        self.watchdog = flow.StragglerWatchdog(name)
        self.channel: Optional[flow.BoundedChannel] = None  # latest iterate()'s window

    def iterate(self, items: Iterable) -> Iterator:
        metrics.set_gauge("prefetch.depth", self.depth)
        channel = flow.BoundedChannel(self.depth, policy=self.policy, name=self.name)
        self.channel = channel
        flow.pump(items, channel, transform=self.stage, watchdog=self.watchdog)
        try:
            yield from channel
        finally:
            channel.cancel()  # early exit: stop the speculative staging
