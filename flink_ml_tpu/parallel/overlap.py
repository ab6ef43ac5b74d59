"""Comm/compute overlap scheduling for the iterative training loops.

The default training programs let GSPMD place one monolithic all-reduce
per batch at the point the gradient contraction completes: the reduction
sits on the critical path between batch b's backward and batch b+1's
forward, and nothing overlaps it. This module rebuilds the hot loops as
explicit-SPMD (`shard_map`) programs with a **carry-delayed apply**: the
loop carries the UNREDUCED per-shard gradient, and the reduction is
deferred to the top of the next epoch — batch b's gradient buckets reduce
(`collectives.all_reduce_sum_chunked`, ring-pipelined when configured)
while batch b+1's batch slice/gather work is already in flight, and on
hardware the async-collective pass hoists the bucket transfers under the
forward compute. Snap ML (arXiv:1803.06333) motivates exactly this
hierarchical chunk-and-overlap schedule.

Bit-parity is by construction, the same way the dispatch pipeline pins
chunked epochs (docs/performance.md §1): the reduction still happens
before the apply that consumes it, the chunked reduction is
bit-identical to the monolithic psum, and the per-epoch update order is
unchanged — so overlap mode produces bit-identical coefficients, stop
epochs, and criteria (pinned by tests/test_collective_chunks.py for dense
losses, tol early-stop included, and for sparse losses while their
gradient densifies onto the chunked path).

Sparse gradients additionally ride the SparCML index-value reduction
(`collectives.sparse_all_reduce_sum`) when their per-shard pair bytes are
below `config.collective_sparse_threshold` × the dense payload: the
(indices, values) pairs of the batch cross the links instead of the
densified `(dim,)` vector, so sparseWideLR gradient traffic scales with
nnz, not dim. That reduction matches the densified psum to float rounding
rather than bitwise (see its docstring), and so does a fit that uses it.

Gated by `config.collective_overlap` (see ops/optimizer.py and the KMeans
driver); compiled programs are cached per (mesh, loss, flags) so repeated
fits re-enter the same executable.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import collectives
from . import mesh as mesh_lib

_SGD_CACHE: Dict[Tuple, Callable] = {}
_SGD2D_CACHE: Dict[Tuple, Callable] = {}
_LLOYD_CACHE: Dict[Tuple, Callable] = {}


def clear_program_cache() -> None:
    _SGD_CACHE.clear()
    _SGD2D_CACHE.clear()
    _LLOYD_CACHE.clear()


def _config_key():
    """The trace-relevant collective knobs; part of every program cache key
    so flipping config recompiles instead of serving a stale schedule."""
    from .. import config

    return (
        config.resolve_chunk_bytes(None),
        bool(config.collective_ring),
        float(config.collective_sparse_threshold),
    )


def _local_pieces(X, y, w, coeff, loss_func, sparse_pairs: bool):
    """Per-shard loss pieces for one batch: (loss_sum_local, grad_local,
    wsum_local). `grad_local` is either the dense per-shard scatter/matmul
    partial — the exact local operand GSPMD would feed its psum — or, with
    `sparse_pairs`, the flattened (indices, values) contribution pairs for
    the index-value reduction."""
    if loss_func.sparse:
        from ..ops.losses import sparse_dot

        indices, values = X
        dot, safe, vals = sparse_dot(indices, values, coeff)
        loss, mult = loss_func.pointwise(dot, y, w)
        contrib = vals * mult[:, None]
        if sparse_pairs:
            grad_local = (jnp.ravel(safe), jnp.ravel(contrib))
        else:
            grad_local = (
                jnp.zeros_like(coeff).at[safe].add(contrib, mode="drop")
            )
    else:
        from ..ops.losses import dense_dot, dense_grad

        loss, mult = loss_func.pointwise(dense_dot(X, coeff), y, w)
        grad_local = dense_grad(X, mult)
    return jnp.sum(loss), grad_local, jnp.sum(w)


def _init_grad_local(coeff, num_rows: int, nnz: int, dtype, sparse_pairs: bool):
    """Zero gradient carry matching `_local_pieces`' output structure; a
    reduce of it is exactly the dense path's zero init gradient."""
    if sparse_pairs:
        return (
            jnp.zeros((num_rows * nnz,), jnp.int32),
            jnp.zeros((num_rows * nnz,), dtype),
        )
    return jnp.zeros_like(coeff)


def sgd_use_sparse_pairs(X_b, d: int, mesh: Mesh) -> bool:
    """Trace-time routing for the sparse SGD gradient: index-value pairs
    when the mesh actually reduces (>1 data shard) and the per-shard pair
    bytes beat the density threshold."""
    if not isinstance(X_b, tuple):
        return False
    shards = mesh_lib.num_data_shards(mesh)
    if shards <= 1:
        return False
    _, b_pad, nnz = X_b[0].shape
    itemsize = np.dtype(X_b[1].dtype).itemsize
    return collectives.sparse_reduce_wins(
        (b_pad // shards) * nnz, d, itemsize=itemsize
    )


def overlapped_sgd_train(
    mesh: Mesh,
    X_b,
    y_b,
    w_b,
    init_coeff,
    loss_func,
    hyper,
    check_labels: bool,
):
    """The bounded SGD iteration as one explicit-SPMD program with
    overlap-scheduled gradient reduction. Same contract as
    `ops.optimizer._sgd_train`: returns the packed
    [flag?, coeff, criteria, epochs] result vector.

    Schedule per epoch (vs. the eager program's reduce-at-batch-end):

        eager:    forward_b -> backward_b -> ALL-REDUCE -> apply -> fwd_{b+1}
        overlap:  forward_b -> backward_b -> carry local grad
                  ALL-REDUCE(grad_b) ∥ batch-slice/gather of b+1 -> apply -> fwd

    The per-epoch tol check still needs the reduced loss, so the (loss,
    wsum) SCALARS reduce every epoch (8 bytes — latency, not bandwidth);
    only the dim-proportional gradient is deferred and bucketed."""
    key = (
        mesh,
        loss_func,
        bool(check_labels),
        sgd_use_sparse_pairs(X_b, int(np.shape(init_coeff)[0]), mesh),
        _config_key(),
    )
    fn = _SGD_CACHE.get(key)
    if fn is None:
        fn = _build_sgd_program(mesh, loss_func, key[2], key[3])
        _SGD_CACHE[key] = fn
    return fn(X_b, y_b, w_b, init_coeff, hyper)


def _build_sgd_program(mesh: Mesh, loss_func, check_labels: bool, sparse_pairs: bool):
    from ..ops.optimizer import (
        _binomial_labels_ok,
        _index_batch,
        _pack_train_result,
        _unpack_hyper,
        _update_model,
    )

    axis = mesh_lib.DATA_AXIS
    batched = P(None, axis, None)
    x_spec = (batched, batched) if loss_func.sparse else batched
    in_specs = (x_spec, P(None, axis), P(None, axis), P(), P())

    def train(X_b, y_b, w_b, init_coeff, hyper):
        num_batches, b_local = y_b.shape
        d = init_coeff.shape[0]
        dtype = X_b[1].dtype if isinstance(X_b, tuple) else X_b.dtype
        nnz = X_b[0].shape[-1] if isinstance(X_b, tuple) else 0
        max_iter, tol, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)

        def reduce_grad(g_local):
            if sparse_pairs:
                return collectives.sparse_all_reduce_sum(
                    g_local[0], g_local[1], d, axis
                )
            return collectives.all_reduce_sum_chunked(g_local, axis)

        def cond(state):
            _, _, _, epoch, criteria = state
            return jnp.logical_and(epoch < max_iter, criteria > tol)

        def body(state):
            coeff, g_local, wsum, epoch, _ = state
            # carry-delayed apply: batch (epoch-1)'s gradient reduces here,
            # where its buckets overlap this epoch's batch staging
            coeff = _update_model(
                coeff, reduce_grad(g_local), wsum, lr, reg, elastic_net
            )
            k = jnp.mod(epoch, num_batches)
            Xk = _index_batch(X_b, k)
            yk = lax.dynamic_index_in_dim(y_b, k, axis=0, keepdims=False)
            wk = lax.dynamic_index_in_dim(w_b, k, axis=0, keepdims=False)
            loss_local, g_local, wsum_local = _local_pieces(
                Xk, yk, wk, coeff, loss_func, sparse_pairs
            )
            # the tol check needs the reduced criteria every epoch: reduce
            # the two scalars now, leave the gradient in the carry
            sums = collectives.all_reduce_sum(
                jnp.stack([loss_local.astype(jnp.float32), wsum_local.astype(jnp.float32)]),
                axis,
            )
            wsum = sums[1].astype(dtype)
            criteria = sums[0] / jnp.maximum(sums[1], 1e-30)
            return (coeff, g_local, wsum, epoch + 1, criteria)

        init_state = (
            jnp.asarray(init_coeff, dtype),
            _init_grad_local(jnp.zeros((d,), dtype), b_local, nnz, dtype, sparse_pairs),
            jnp.asarray(0.0, dtype),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(jnp.inf, jnp.float32),
        )
        coeff, g_local, wsum, epochs, criteria = lax.while_loop(cond, body, init_state)
        # the one-extra-update-after-termination of the reference
        # (SGD.java onIterationTerminated) reduces the final carry
        coeff = _update_model(coeff, reduce_grad(g_local), wsum, lr, reg, elastic_net)
        flag = None
        if check_labels:
            ok = _binomial_labels_ok(y_b)
            flag = collectives.all_reduce_min(ok, axis)
        return _pack_train_result(coeff, criteria, epochs, flag)

    mapped = collectives.shard_map_over(mesh, in_specs, P(), fn=train)
    # tpulint: disable=retrace-hazard -- overlap mode builds one program per fit by design (opt-in; caching keyed on mesh/shape is ROADMAP item 2)
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# true 2D (data × model) sparse SGD programs
# ---------------------------------------------------------------------------
# The feature-sharded training loop as explicit SPMD: the coefficient and
# gradient carries live as (d_local,) MODEL-axis slices (the per-device
# residency that makes beyond-HBM dims fit), batches stay DATA-sharded, and
# the per-epoch math is `ops.optimizer._sgd_chunk_impl` verbatim over the
# 2D loss variant (`ops.losses.feature_sharded_variant`) — whose collectives
# are axis-restricted: active-feature assembly psums over `model`, the
# SparCML gradient reduce over `data` only. The whole-fit flavor keeps the
# PR 13 ONE-dispatch + ONE-readback contract under sharding by packing the
# result as ONE MODEL-SHARDED array (per-shard block = [flag?, coeff_slice,
# criteria, epochs]) instead of `_pack_train_result`'s replicated
# concatenate: a full-d replicated pack would re-materialize the very
# vector the mesh exists to split (and `utils.packing.packed_device_get`'s
# device-side concatenate of mixed shardings is the GSPMD multi-axis
# miscompile `_pack_train_result` documents). `sgd2d_unpack_host` is the
# host-side inverse.


def sgd2d_whole_fit(mesh, X_b, y_b, w_b, carry, criteria, loss_func, hyper,
                    check_labels=False):
    """The entire 2D fit as ONE resident program: epoch loop to maxIter,
    barrier-pinned final update, model-sharded packed result. Returns
    (carry, criteria, packed) with the carry device-resident and sharded
    (coeff/grad = model-axis slices) for the fit-end snapshot — the PR 14
    coordinator's model-tag case."""
    key = (mesh, loss_func, "whole", bool(check_labels), _config_key())
    fn = _SGD2D_CACHE.get(key)
    if fn is None:
        fn = _build_sgd2d_program(mesh, loss_func, "whole", bool(check_labels))
        _SGD2D_CACHE[key] = fn
    return fn(X_b, y_b, w_b, carry, criteria, hyper)


def sgd2d_chunk(mesh, X_b, y_b, w_b, carry, criteria, loss_func, hyper, chunk_end):
    """Host-driven 2D epochs up to `chunk_end` for the checkpointed loop:
    same contract as `ops.optimizer._sgd_chunk` ((carry, criteria,
    packed[epoch, criteria])) with the carry staying model-sharded across
    snapshot boundaries. Always borrowing — the pre-chunk carry must stay
    readable for a pending snapshot write."""
    key = (mesh, loss_func, "chunk", False, _config_key())
    fn = _SGD2D_CACHE.get(key)
    if fn is None:
        fn = _build_sgd2d_program(mesh, loss_func, "chunk", False)
        _SGD2D_CACHE[key] = fn
    return fn(X_b, y_b, w_b, carry, criteria, hyper, chunk_end)


def sgd2d_unpack_host(host, num_model_shards: int, d_local: int,
                      has_flag: bool):
    """Host-side inverse of the model-sharded result pack: the readback is
    (num_model_shards * block,) with block = [flag?, coeff_slice, criteria,
    epochs]. The scalars are uniform across shards (they were psum'd over
    `data` and identical on every model shard); block 0's copies are
    authoritative. Returns (coeff, criteria, epochs, flag?)."""
    block = d_local + 2 + (1 if has_flag else 0)
    blocks = np.asarray(host).reshape(num_model_shards, block)
    off = 1 if has_flag else 0
    coeff = np.concatenate([blocks[s, off:off + d_local] for s in range(num_model_shards)])
    criteria = float(blocks[0, off + d_local])
    epochs = int(blocks[0, off + d_local + 1])
    flag = float(blocks[0, 0]) if has_flag else None
    return coeff, criteria, epochs, flag


def _build_sgd2d_program(mesh: Mesh, loss_func, flavor: str, check_labels: bool):
    from ..ops.losses import feature_sharded_variant
    from ..ops.optimizer import (
        _binomial_labels_ok,
        _sgd_chunk_impl,
        _unpack_hyper,
        _update_model,
    )

    data, model = mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS
    loss2d = feature_sharded_variant(loss_func)
    batched = P(None, data, None)
    carry_spec = (P(model), P(model), P(), P())
    base_in = ((batched, batched), P(None, data), P(None, data), carry_spec, P())

    if flavor == "chunk":

        def chunk(X_b, y_b, w_b, carry, criteria, hyper, chunk_end):
            return _sgd_chunk_impl(
                X_b, y_b, w_b, carry, criteria, loss2d, hyper, chunk_end
            )

        mapped = collectives.shard_map_over(
            mesh, base_in + (P(), P()), (carry_spec, P(), P()), fn=chunk
        )
    else:

        def whole(X_b, y_b, w_b, carry, criteria, hyper):
            dtype = X_b[1].dtype
            max_iter, _, lr, reg, elastic_net = _unpack_hyper(hyper, dtype)
            carry, criteria, _ = _sgd_chunk_impl(
                X_b, y_b, w_b, carry, criteria, loss2d, hyper, max_iter
            )
            # barrier-pinned final update, exactly `_sgd_whole_fit_impl`:
            # the one-extra-update must consume the MATERIALIZED loop carry
            # for bit-parity with the chunked path's host-side apply
            coeff, grad, wsum, epochs = lax.optimization_barrier(carry)
            final = _update_model(coeff, grad, wsum, lr, reg, elastic_net)
            dt = jnp.promote_types(final.dtype, jnp.float32)
            parts = [
                final.astype(dt),
                jnp.reshape(jnp.asarray(criteria).astype(dt), (1,)),
                jnp.reshape(jnp.asarray(epochs).astype(dt), (1,)),
            ]
            if check_labels:
                ok = collectives.all_reduce_min(_binomial_labels_ok(y_b), data)
                parts.insert(0, jnp.reshape(ok.astype(dt), (1,)))
            return carry, criteria, jnp.concatenate(parts)

        mapped = collectives.shard_map_over(
            mesh, base_in + (P(),), (carry_spec, P(), P(model)), fn=whole
        )
    # tpulint: disable=retrace-hazard -- one 2D program per (mesh, loss, flavor); cached in _SGD2D_CACHE so repeated fits re-enter the same executable
    return jax.jit(mapped)


def overlapped_lloyd_train(
    mesh: Mesh, X, weights, init_centroids, max_iter, measure_name: str
):
    """Lloyd's loop with the same carry-delayed schedule: the (k, d)+(k,)
    centroid-partial reduction of epoch e rides the chunked collective at
    the top of epoch e+1, overlapping the blocks of the next assignment.
    Each shard's partials are the eager fit's own blocked pass
    (`kmeans._accumulate_batch_impl`), the reduce is psum-bit-equal and the
    update order is unchanged. `weights` is None where no row is padding.
    Returns the eager `_lloyd_fit`'s packed [centroids.ravel | counts]."""
    key = (mesh, measure_name, weights is None, _config_key())
    fn = _LLOYD_CACHE.get(key)
    if fn is None:
        fn = _build_lloyd_program(mesh, measure_name, weights is None)
        _LLOYD_CACHE[key] = fn
    rows = (X,) if weights is None else (X, weights)
    return fn(init_centroids, max_iter, *rows)


def _build_lloyd_program(mesh: Mesh, measure_name: str, unweighted: bool):
    from ..models.clustering.kmeans import _accumulate_batch_impl, _new_centroids

    axis = mesh_lib.DATA_AXIS

    def train(init_centroids, max_iter, X, *w):
        k = init_centroids.shape[0]
        weights = w[0] if w else None

        def reduce_partials(sums, counts):
            return collectives.all_reduce_sum_chunked((sums, counts), axis)

        def cond(state):
            return state[3] < max_iter

        def step(state):
            centroids, local_sums, local_counts, epoch = state
            # epoch e-1's partials reduce here, overlapping this epoch's
            # distance matmul on hardware; epoch 0 reduces the zero init
            # (counts 0 -> centroids keep their init values, exactly the
            # eager loop's first assignment)
            sums, counts = reduce_partials(local_sums, local_counts)
            centroids = _new_centroids(centroids, sums, counts)
            sums, counts = _accumulate_batch_impl(X, weights, centroids, measure_name)
            return (centroids, sums, counts, epoch + 1)

        init = (
            init_centroids,
            jnp.zeros_like(init_centroids),
            jnp.zeros((k,), X.dtype),
            jnp.asarray(0, jnp.int32),
        )
        centroids, local_sums, local_counts, _ = lax.while_loop(cond, step, init)
        sums, counts = reduce_partials(local_sums, local_counts)
        return jnp.concatenate([_new_centroids(centroids, sums, counts).ravel(), counts])

    row_specs = (P(axis, None),) if unweighted else (P(axis, None), P(axis))
    mapped = collectives.shard_map_over(mesh, (P(), P()) + row_specs, P(), fn=train)
    # tpulint: disable=retrace-hazard -- overlap mode builds one program per fit by design (opt-in; caching keyed on mesh/shape is ROADMAP item 2)
    return jax.jit(mapped)


def fleet_overlap_supported() -> bool:
    """Whether fleet training (fleet.py) can ride the overlap-scheduled
    shard_map programs. Currently False: the overlap programs are built
    per-mesh-shard with `shard_map`, and vmapping a shard_map body over a
    fleet axis would batch the deferred-reduction carry — the exact
    cross-epoch pipelining the scheme relies on — per member, which XLA
    re-serializes. A FitFleet therefore always trains on the plain
    vmapped resident kernels and counts the downgrade under
    `dispatch.whole_fit_fallback.fleet_overlap` so an overlap-tuned
    deployment notices fleet fits leaving the overlap path."""
    return False
