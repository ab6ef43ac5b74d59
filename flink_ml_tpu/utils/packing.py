"""One-transfer device→host readback of multiple arrays.

Every array's first readback is its own blocking host round trip
regardless of size, so a fit that pulls (centroids, counts) or
(mean, std) separately pays it twice.
`packed_device_get` flattens and concatenates the arrays device-side and
performs ONE explicit `jax.device_get`, then splits on host.

The transfer is explicit on purpose: tests pin the one-readback-per-fit
contract by running fits under `jax.transfer_guard("disallow")`, which
blocks implicit transfers (stray `np.asarray` on a device array) while
letting this helper's `device_get` through.

Caveat: values are packed in the promoted common dtype (float32 when x64
is off). Integer inputs above 2**24 would lose precision — callers on
those paths keep their own packing (see ops/optimizer._pack_result).
"""

from __future__ import annotations

from typing import List

import numpy as np


def packed_device_get(*arrays, sync_kind: str = "readback") -> List[np.ndarray]:
    """Return host copies of ``arrays`` via at most one D2H transfer.

    Host inputs pass through as-is (never uploaded just to be pulled
    back); device inputs are flattened into one concatenated transfer and
    restored to their original shapes AND dtypes on the host. A call with
    any device input is one blocking host↔device synchronization point and
    goes through the timed funnel ``obs.tracing.sync(sync_kind, ...)`` (the
    wait apart from the copy; ``iteration.host_sync.<sync_kind>``) — callers
    on named paths (fit results, checkpoint snapshots) pass their kind."""
    import jax
    import jax.numpy as jnp

    from ..obs import tracing

    device_idx = [i for i, a in enumerate(arrays) if isinstance(a, jax.Array)]
    out: List = [None] * len(arrays)
    for i, a in enumerate(arrays):
        if i not in device_idx:
            out[i] = np.asarray(a)
    if not device_idx:
        return out
    if len(device_idx) == 1:
        i = device_idx[0]
        out[i] = tracing.sync(sync_kind, arrays[i])
        return out
    devs = [arrays[i] for i in device_idx]
    shapes = [a.shape for a in devs]
    dtypes = [a.dtype for a in devs]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dt = dtypes[0]
    for d in dtypes[1:]:
        dt = jnp.promote_types(dt, d)
    packed = jnp.concatenate([jnp.ravel(a).astype(dt) for a in devs])
    host = tracing.sync(sync_kind, packed, arrays=len(device_idx))
    off = 0
    for i, shape, size, dtype in zip(device_idx, shapes, sizes, dtypes):
        out[i] = host[off : off + size].reshape(shape).astype(dtype)
        off += size
    return out
