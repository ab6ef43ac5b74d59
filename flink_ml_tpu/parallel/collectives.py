"""Distributed communication primitives.

The reference builds three comm primitives on Flink's netty shuffle
(SURVEY.md §5): a chunked emulated all-reduce
(common/datastream/AllReduceImpl.java:56-103, 32KB chunks over two
partitionCustom shuffles), broadcast variables (BroadcastUtils.java:64),
and the statefun in-JVM feedback channel (operator/TailOperator.java:76-79).
On TPU these are hardware collectives over ICI; `psum` IS the all-reduce
and replication IS the broadcast — but the reference's chunk decomposition
is kept for the one route that reduces by hand. This module carries two
tiers:

- thin accounted wrappers over the hardware collectives (`all_reduce_sum`
  … `ppermute_ring`) — every collective a model dispatches rides one of
  these, so `collective.*` counters answer "what traffic does this program
  move";
- the gradient reductions of the feature-sharded loss
  (`ops.losses.feature_sharded_variant`, run by the 2-D route in
  parallel/sgd2d.py): `all_reduce_sum_chunked` (bucketed
  reduce_scatter+all_gather) and `sparse_all_reduce_sum` (SparCML-style
  index-value reduction, wire bytes ∝ nnz instead of dim —
  arXiv:1802.08021), chosen by `sparse_reduce_wins`. The chunked reduce is
  bit-identical to a single `lax.psum` of the same operand (pinned across
  chunk sizes and shard counts by tests/test_collective_chunks.py, and
  seen on four v5e chips); the sparse reduce sums the same addends but
  agrees with the psum of the densified operand to float rounding, not
  bitwise (see its docstring).

Everywhere else a data-parallel fit reduces with one psum: XLA places it
in a GSPMD program, or the program calls `all_reduce_sum` under
`shard_map` (Lloyd's fit on row shards).

These wrappers are used inside `shard_map`-ped functions; outside
`shard_map`, prefer sharding annotations and let XLA insert collectives.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import sanitizer as _sanitizer
from ..obs import hist, tracing
from ..utils import metrics

# the axis-name constants are DECLARED in mesh.py and re-exported here so
# model/ops code that already imports collectives needs no second import —
# the mesh-axis lint rule resolves either path to the same constant
from .mesh import DATA_AXIS, MODEL_AXIS  # noqa: F401  (MODEL_AXIS re-export)


def _iter_array_leaves(x):
    """Every array-like leaf of a possibly-nested structure. Unlike a bare
    `tree_leaves` + hasattr filter, this also descends containers that are
    not registered pytrees and never drops a level: a sparse (indices,
    values) tuple nested inside a gradient pytree contributes BOTH leaves
    to the byte count (the round-5 accounting undercounted these)."""
    if isinstance(x, (tuple, list)):
        for item in x:
            yield from _iter_array_leaves(item)
    elif isinstance(x, dict):
        for item in x.values():
            yield from _iter_array_leaves(item)
    elif hasattr(x, "shape") and hasattr(x, "dtype"):
        yield x
    else:
        try:
            for leaf in jax.tree_util.tree_leaves(x):
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                    yield leaf
        except Exception:
            pass


def payload_bytes(x) -> int:
    """Per-participant payload bytes of a pytree: the sum over every array
    leaf, including leaves of nested non-pytree containers."""
    return sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in _iter_array_leaves(x)
    )


def _account(op: str, x, axis_name: str, chunks: int = None, dense_equiv_bytes: int = None) -> None:
    """Record one collective call: op, per-participant payload bytes and
    chunk (bucket/leaf) count. These wrappers run INSIDE jitted/shard_map
    code, so this fires at TRACE time — once per compiled program, not per
    execution — which is exactly when the op's shape is known; the
    counters answer "what collective traffic does this program dispatch",
    the device profile answers how long it took."""
    leaves = list(_iter_array_leaves(x))
    nbytes = sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize for leaf in leaves)
    # sanitizer collective-sequence ledger (FLINK_ML_TPU_SANITIZE=1): the
    # per-shard (op, axis, shape, dtype) sequence must match across shard
    # scopes at exit — the dynamic dual of the collective-divergence rule
    if leaves:
        _sanitizer.record_collective(
            op, axis_name, leaves[0].shape, np.dtype(leaves[0].dtype).name
        )
    else:
        _sanitizer.record_collective(op, axis_name, (), "none")
    # payload-SIZE distribution (SparCML-style evaluation: per-collective
    # size histograms, not just byte sums — a p99 payload far above p50
    # says the bucketing layer is emitting stragglers)
    hist.record("collective.payloadBytes", nbytes)
    # per-AXIS attribution: on a 2D (data, model) mesh the two axes carry
    # different traffic classes (nnz-proportional gradient pairs over
    # `data`, active-feature slices over `model`), so the wire-byte
    # evidence must not collapse into one counter — the sparse2dMesh
    # BENCH entry reads these to report per-axis wire bytes, and the
    # per-axis sparse ratio keeps a model-axis reduce from diluting the
    # data-axis traffic-proportionality claim
    metrics.inc_counter(f"collective.axis.{axis_name}.calls")
    metrics.inc_counter(f"collective.axis.{axis_name}.bytes", int(nbytes))
    if dense_equiv_bytes:
        metrics.inc_counter(
            f"collective.axis.{axis_name}.sparse.bytes", int(nbytes)
        )
        metrics.inc_counter(
            f"collective.axis.{axis_name}.sparse.dense_equiv_bytes",
            int(dense_equiv_bytes),
        )
        metrics.set_gauge(
            f"collective.sparse_ratio.{axis_name}",
            metrics.get_counter(f"collective.axis.{axis_name}.sparse.bytes")
            / max(
                metrics.get_counter(
                    f"collective.axis.{axis_name}.sparse.dense_equiv_bytes"
                ),
                1,
            ),
        )
    tracing.account_collective(
        op,
        nbytes,
        chunks if chunks is not None else len(leaves),
        axis_name,
        dense_equiv_bytes=dense_equiv_bytes,
    )


def axis_wire_bytes(snapshot_delta: dict = None) -> Dict[str, int]:
    """Per-axis collective wire bytes from the (delta) metrics counters:
    {axis: bytes}. Pass a `metrics.snapshot_delta` to scope to one entry;
    defaults to the live registry."""
    counters = (
        snapshot_delta.get("counters", {})
        if snapshot_delta is not None
        else metrics.snapshot()["counters"]
    )
    out: Dict[str, int] = {}
    for name, value in counters.items():
        parts = name.split(".")
        if len(parts) == 4 and parts[:2] == ["collective", "axis"] and parts[3] == "bytes":
            out[parts[2]] = int(value)
    return out


def axis_size(axis_name: str = DATA_AXIS) -> int:
    """Static participant count of a mapped axis, as a Python int (legal
    only inside shard_map/pmap tracing). pre-graft jax lacks lax.axis_size;
    psum of the constant 1 folds to the static size on both versions."""
    if hasattr(lax, "axis_size"):
        # tpulint: disable=host-sync-leak -- static mapped-axis size, folded at trace time; no device value crosses
        return int(lax.axis_size(axis_name))
    # tpulint: disable=host-sync-leak -- psum of the constant 1 folds to the static axis size at trace time
    return int(lax.psum(1, axis_name))


def all_reduce_sum(x, axis_name: str = DATA_AXIS):
    """MPI-style all-reduce-sum: each participant gets the global sum.

    Replaces DataStreamUtils.allReduceSum (AllReduceImpl.java:71) as one
    monolithic hardware collective; `all_reduce_sum_chunked` below is the
    decomposed equivalent of the reference's 32KB chunk loop.
    """
    _account("psum", x, axis_name)
    return lax.psum(x, axis_name)


def all_reduce_mean(x, axis_name: str = DATA_AXIS):
    _account("pmean", x, axis_name)
    return lax.pmean(x, axis_name)


def all_reduce_max(x, axis_name: str = DATA_AXIS):
    _account("pmax", x, axis_name)
    return lax.pmax(x, axis_name)


def all_reduce_min(x, axis_name: str = DATA_AXIS):
    _account("pmin", x, axis_name)
    return lax.pmin(x, axis_name)


def all_gather(x, axis_name: str = DATA_AXIS, axis: int = 0, tiled: bool = True):
    """Gather shards onto every participant — the analogue of broadcast-
    collecting a distributed result (e.g. countWindowAll funnel + rebroadcast,
    KMeans.java:168-173, without the parallelism-1 funnel bottleneck)."""
    _account("all_gather", x, axis_name)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_to_all(x, mesh: Mesh, src_axis: int, dst_axis: int, axis_name: str = DATA_AXIS):
    """Exchange over a whole (global) array inside a jitted program: `x`
    comes sharded over the mesh's `axis_name` on `src_axis` and leaves
    sharded on `dst_axis`, so every participant sends piece j of what it
    holds to participant j — the reference's partitionCustom shuffle as one
    hardware collective (the batch layout of a row-sharded table,
    ops/optimizer.py). Asked of XLA's partitioner by a sharding constraint
    and not through `lax.all_to_all` under `shard_map`: the collective is
    the same, but the partitioner's carries XLA's own name (`all-to-all`) in
    a device trace, where readers of collective time look for it; the
    traced primitive's is named `all_to_all.N`."""
    local = list(x.shape)
    local[src_axis] //= mesh.shape[axis_name]
    _account("all_to_all", jax.ShapeDtypeStruct(tuple(local), x.dtype), axis_name)
    spec = [None] * x.ndim
    spec[dst_axis] = axis_name
    return lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def reduce_scatter(x, axis_name: str = DATA_AXIS, scatter_dimension: int = 0):
    _account("psum_scatter", x, axis_name)
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def ppermute_ring(x, axis_name: str = DATA_AXIS, shift: int = 1):
    """Ring shift along an axis — building block for ring pipelines
    (ring attention / pipelined all-reduce patterns)."""
    _account("ppermute", x, axis_name)
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# bucketed all-reduce (the chunked-AllReduceImpl analogue)
# ---------------------------------------------------------------------------

#: Bucket size of `all_reduce_sum_chunked`. The reference hand-rolls its
#: decomposition at 32KB per chunk over netty shuffles (AllReduceImpl.java:
#: 56-103, tuned for TCP framing); ICI moves MB-class buckets at line rate.
CHUNK_BYTES = 4 << 20
#: `sparse_reduce_wins` takes the index-value reduction when its pair bytes
#: are at most this share of the dense payload.
SPARSE_THRESHOLD = 0.5


def _reduce_bucket_rs_ag(vec, axis_name: str, n: int):
    """One bucket via reduce_scatter + all_gather — the bandwidth-optimal
    decomposition (each element crosses each link ~2(n-1)/n times). The
    bucket is zero-padded to an n-divisible length for the tiled scatter;
    padding reduces to zero and is sliced off. Elementwise this computes
    exactly what `psum` computes (same participant set, same per-element
    reduction), so the result is bit-identical to the monolithic op."""
    m = vec.shape[0]
    pad = (-m) % n
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    shard = lax.psum_scatter(vec, axis_name, scatter_dimension=0, tiled=True)
    out = lax.all_gather(shard, axis_name, axis=0, tiled=True)
    return out[:m] if pad else out


def _bucket_sizes(total: int, itemsize: int, chunk_bytes) -> list:
    """Split `total` elements into size-targeted bucket lengths."""
    if not chunk_bytes or chunk_bytes <= 0:
        return [total] if total else []
    per = max(1, int(chunk_bytes) // max(1, itemsize))
    sizes = []
    off = 0
    while off < total:
        sizes.append(min(per, total - off))
        off += sizes[-1]
    return sizes


def all_reduce_sum_chunked(
    x,
    axis_name: str = DATA_AXIS,
    chunk_bytes: int = None,
):
    """Bucketed all-reduce-sum of a pytree: bit-identical to `lax.psum(x)`.

    The decomposition the reference hand-rolls at 32KB per chunk
    (AllReduceImpl.java:56-103), rebuilt for ICI: leaves are grouped by
    dtype, flattened, and split into `chunk_bytes`-targeted buckets
    (`CHUNK_BYTES` when None; 0 or less is one bucket); each bucket is
    reduced independently by reduce_scatter+all_gather. Because the
    per-element reduction is unchanged, chunking changes *when bytes move*,
    never the result; the parity suite pins bit-identity for chunk_bytes ∈
    {1KB, 32KB, ∞} on 1/2/8-shard meshes.
    """
    if chunk_bytes is None:
        chunk_bytes = CHUNK_BYTES
    n = axis_size(axis_name)

    leaves, treedef = jax.tree_util.tree_flatten(x)
    if not leaves:
        return x
    if n == 1:
        _account("chunked", x, axis_name, chunks=len(leaves))
        return x

    # group leaves by dtype so buckets stay homogeneous
    by_dtype: Dict[Any, list] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    out_leaves = list(leaves)
    num_buckets = 0
    for dtype, idxs in by_dtype.items():
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        sizes = _bucket_sizes(flat.shape[0], dtype.itemsize, chunk_bytes)
        num_buckets += len(sizes)
        reduced, off = [], 0
        for size in sizes:
            reduced.append(_reduce_bucket_rs_ag(flat[off : off + size], axis_name, n))
            off += size
        flat_red = reduced[0] if len(reduced) == 1 else jnp.concatenate(reduced)
        off = 0
        for i in idxs:
            count = int(np.prod(leaves[i].shape))
            out_leaves[i] = flat_red[off : off + count].reshape(leaves[i].shape)
            off += count
    _account("chunked", x, axis_name, chunks=num_buckets)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


# ---------------------------------------------------------------------------
# sparse index-value all-reduce (SparCML, arXiv:1802.08021)
# ---------------------------------------------------------------------------


def sparse_all_reduce_sum(
    indices,
    values,
    dim: int,
    axis_name: str = DATA_AXIS,
):
    """All-reduce a gradient carried as per-shard (index, value) pairs;
    returns the dense `(dim,)` sum of exactly the addends of
    `psum(zeros(dim).at[indices].add(values))` of the densified operand.

    Wire bytes are the pairs, not the dim: each shard contributes its
    `nnz_local * (4 + itemsize)` pair bytes to one all_gather, and the
    dense vector never crosses a link — the SparCML index-value exchange
    that makes sparseWideLR gradient traffic scale with nnz instead of
    dim. The cross-shard combine scatters each shard's gathered pairs into
    its own dense partial and folds the partials in replica order, as
    the dense path does (per-shard scatter-add, then psum). The result is
    equal to float rounding, NOT bitwise: XLA may fold a shard's
    scatter-add into the running sum, so where one shard holds duplicate
    indices `acc + (a + b)` becomes `(acc + a) + b` — 1-2 ulp on the CPU
    backend of jax 0.9.0 (tests/test_collective_chunks.py pins 1e-6). One
    shard is the same expression as the dense path and is exact.

    Out-of-range / negative indices are dropped (`mode="drop"`), matching
    the padded-CSR convention of ops/losses.py. Callers pick sparse vs
    dense at trace time via `sparse_reduce_wins` below.
    """
    n = axis_size(axis_name)
    indices = jnp.ravel(indices)
    values = jnp.ravel(values)
    itemsize = values.dtype.itemsize
    _account(
        "sparse_allreduce",
        (indices, values),
        axis_name,
        chunks=1,
        dense_equiv_bytes=int(dim) * itemsize,
    )
    if n == 1:
        return jnp.zeros((dim,), values.dtype).at[indices].add(values, mode="drop")
    gi = lax.all_gather(indices, axis_name, axis=0, tiled=False)  # (n, m)
    gv = lax.all_gather(values, axis_name, axis=0, tiled=False)

    def scatter_partial(r):
        return jnp.zeros((dim,), values.dtype).at[gi[r]].add(gv[r], mode="drop")

    acc = scatter_partial(0)
    for r in range(1, n):
        acc = acc + scatter_partial(r)
    return acc


def sparse_reduce_wins(
    nnz_local: int, dim: int, itemsize: int = 4, threshold: float = None
) -> bool:
    """Trace-time decision: use the index-value reduction when its
    per-shard pair bytes are at most `threshold` × the dense psum payload
    (`SPARSE_THRESHOLD` when None). Static shapes only — the choice is
    baked into the compiled program."""
    if threshold is None:
        threshold = SPARSE_THRESHOLD
    pair_bytes = int(nnz_local) * (4 + int(itemsize))
    return pair_bytes <= threshold * int(dim) * int(itemsize)


def axis_index(axis_name: str = DATA_AXIS):
    return lax.axis_index(axis_name)


def shard_map_over(mesh: Mesh, in_specs, out_specs, fn=None, check_vma: bool = False):
    """Decorator: run `fn` SPMD over `mesh` with explicit per-shard code.

    The moral equivalent of the reference's per-subtask operator functions;
    collectives above are legal inside.
    """

    def wrap(f):
        return jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )

    return wrap(fn) if fn is not None else wrap


# One jitted reducer per (mesh, stacked shape, dtype): defining the jit
# inside host_all_reduce_sum built a fresh closure per call, so jax's
# executable cache (keyed on function identity) missed every time and every
# call RECOMPILED — ~10ms of XLA work per reduce on the host-driven loops.
_HOST_REDUCE_CACHE: Dict[Tuple, Callable] = {}


def _host_reduce_fn(mesh: Mesh, shape: Tuple[int, ...], dtype) -> Callable:
    key = (mesh, tuple(shape), np.dtype(dtype).str)
    fn = _HOST_REDUCE_CACHE.get(key)
    if fn is None:
        sharding = NamedSharding(mesh, P())

        def _sum(stacked):
            return jnp.sum(stacked, axis=0)

        # tpulint: disable=retrace-hazard -- cached in _HOST_REDUCE_CACHE keyed (mesh, shape, dtype); compile count pinned by test_collective_chunks
        fn = jax.jit(_sum, out_shardings=sharding)
        _HOST_REDUCE_CACHE[key] = fn
    return fn


def host_all_reduce_sum(mesh: Mesh, xs):
    """Sum per-shard host arrays into one replicated device array.

    Host-driven (unbounded) loops accumulate per-data-shard partials on host
    (the analogue of the reference's per-subtask accumulators funneled through
    countWindowAll, OnlineKMeans.java pattern); this reduces them with one
    device-side tree-sum and publishes the result replicated over `mesh`.
    The reducer is cached per (mesh, shape, dtype) — repeated reduces of the
    same shape re-enter the same compiled executable (compile-count pinned
    by tests/test_collective_chunks.py)."""
    # host-driven (not inside a trace): this span measures the real
    # per-call stack+upload+reduce wall time
    with tracing.span("collective.host_all_reduce_sum", category="collective") as sp:
        stacked = jnp.stack([jnp.asarray(x) for x in xs])
        sp.set_attr("bytes", int(stacked.size * stacked.dtype.itemsize))
        sp.set_attr("chunks", len(xs))
        metrics.inc_counter("collective.host_all_reduce_sum.calls")
        metrics.inc_counter(
            "collective.host_all_reduce_sum.bytes",
            int(stacked.size * stacked.dtype.itemsize),
        )
        return _host_reduce_fn(mesh, stacked.shape, stacked.dtype)(stacked)
