"""The explicit 2-D (data x model) route of a feature-sharded sparse fit.

`SGD(shard_features=True)` over padded-CSR rows, on a mesh with a model
axis and `config.sparse_2d == "auto"`, trains through the programs here
(`ops.optimizer.SGD._use_2d` decides). They are `shard_map` programs: the
coefficient and gradient carries live as (d_local,) MODEL-axis slices, the
residency that lets a dimension beyond one device's memory fit, and the
batches stay DATA-sharded. The epoch math is `ops.optimizer._sgd_chunk_impl`
over the feature-sharded loss (`ops.losses.feature_sharded_variant`), whose
collectives are restricted to an axis: the active features are assembled by
a psum over `model`, and the gradient reduces over `data` alone, as
index-value pairs or as the densified slice in buckets
(`collectives.sparse_all_reduce_sum`, `collectives.all_reduce_sum_chunked`).

Compiled programs are cached per (mesh, loss, flavor, labels checked), so
repeated fits re-enter the same executable.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import collectives
from . import mesh as mesh_lib

_SGD2D_CACHE: Dict[Tuple, Callable] = {}


def clear_program_cache() -> None:
    _SGD2D_CACHE.clear()


# The whole-fit flavor keeps the one dispatch and one readback of a whole fit
# under sharding by packing the result as ONE MODEL-SHARDED array (per-shard
# block = [flag?, coeff_slice, criteria, epochs]) instead of
# `_pack_train_result`'s replicated concatenate: a full-d replicated pack
# would re-materialize the very vector the mesh exists to split (and
# `utils.packing.packed_device_get`'s device-side concatenate of mixed
# shardings is the GSPMD multi-axis miscompile `_pack_train_result`
# documents). `sgd2d_unpack_host` is the host-side inverse.


def sgd2d_whole_fit(mesh, X_b, y_b, w_b, carry, criteria, loss_func, hyper,
                    check_labels=False):
    """The entire 2D fit as ONE resident program: epoch loop to maxIter,
    barrier-pinned final update, model-sharded packed result. Returns
    (carry, criteria, packed) with the carry device-resident and sharded
    (coeff/grad = model-axis slices) for the fit-end snapshot — the
    checkpoint coordinator's model-tag case."""
    key = (mesh, loss_func, "whole", bool(check_labels))
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
    key = (mesh, loss_func, "chunk", False)
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
