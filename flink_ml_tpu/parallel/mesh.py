"""Device-mesh construction and sharding helpers.

The reference scales by Flink task parallelism with netty shuffles between
subtasks (SURVEY.md §2.3 parallelism table). The TPU-native analogue is a
`jax.sharding.Mesh` over the chip topology: the `data` axis carries data
parallelism (the reference's rebalance()+allReduceSum), the optional
`model` axis feature-shards wide linear models (the TP analogue for sparse
high-dim LR). Collectives ride ICI; multi-host extends the same mesh over
DCN via `jax.distributed.initialize` (see `init_distributed`).
"""

from __future__ import annotations

import math
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_default_mesh: Optional[Mesh] = None


def create_mesh(
    axis_names: Sequence[str] = (DATA_AXIS,),
    shape: Optional[Sequence[int]] = None,
    devices=None,
) -> Mesh:
    """Build a Mesh over the given (default: all) devices.

    If `shape` is omitted, all devices go on the first axis and the rest get
    size 1. Uses jax's device order, which follows the ICI topology on TPU
    so neighbouring mesh coordinates are ICI neighbours.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = [len(devices)] + [1] * (len(axis_names) - 1)
    if math.prod(shape) != len(devices):
        raise ValueError(f"Mesh shape {shape} does not match {len(devices)} devices")
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


def default_mesh() -> Mesh:
    """The process-wide default mesh: all devices on the `data` axis."""
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = create_mesh()
    return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


@contextmanager
def use_mesh(mesh: Mesh):
    global _default_mesh
    prev = _default_mesh
    _default_mesh = mesh
    try:
        yield mesh
    finally:
        _default_mesh = prev


def create_mesh_2d(
    model_shards: int,
    devices=None,
    num_hosts: Optional[int] = None,
) -> Mesh:
    """Build the true 2D `(data, model)` training mesh: the device grid
    factorized as (device_count / model_shards) × model_shards with the
    MODEL axis innermost.

    Innermost-model is the layout that keeps the factorization host-group
    aware: `host_groups` (and real multi-host process boundaries) slice the
    flat device order into contiguous slabs, and with the model axis minor
    each slab owns WHOLE data-axis rows — a feature-axis all-gather stays
    inside one host's ICI domain while the data-axis gradient reduce is
    the only collective that crosses host slabs (the Snap ML hierarchy:
    TP inside the node, DP across nodes). With `num_hosts` the alignment
    is validated up front: every host slab must hold a multiple of
    `model_shards` devices, otherwise a data row straddles hosts and the
    cheap-axis/expensive-axis split silently inverts.
    """
    devices = list(devices if devices is not None else jax.devices())
    model_shards = int(model_shards)
    if model_shards < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    if len(devices) % model_shards:
        raise ValueError(
            f"model_shards={model_shards} does not divide {len(devices)} devices"
        )
    if num_hosts is not None:
        if num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
        for h, group in enumerate(
            np.array_split(np.arange(len(devices)), num_hosts)
        ):
            if len(group) % model_shards:
                raise ValueError(
                    f"host {h} owns {len(group)} of {len(devices)} devices — "
                    f"not a multiple of model_shards={model_shards}; a "
                    "data-axis row would straddle hosts (re-factor the grid "
                    "or change the host count)"
                )
    return create_mesh(
        (DATA_AXIS, MODEL_AXIS),
        shape=(len(devices) // model_shards, model_shards),
        devices=devices,
    )


def num_data_shards(mesh: Mesh) -> int:
    return int(mesh.shape.get(DATA_AXIS, 1))


def num_model_shards(mesh: Mesh) -> int:
    return int(mesh.shape.get(MODEL_AXIS, 1))


def data_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard leading (batch) dim over the data axis, replicate the rest —
    the layout of training examples."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def model_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the trailing (feature) dim over the model axis — the layout of
    feature-sharded wide model vectors."""
    if MODEL_AXIS not in mesh.axis_names:
        return replicated_sharding(mesh)
    return NamedSharding(mesh, P(*([None] * (ndim - 1)), MODEL_AXIS))


def data_model_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """The 2D training layout for rank >= 2 operands: leading (batch) dim
    over `data`, trailing (feature) dim over `model`, middle dims
    replicated — batches split across data shards while each data row's
    feature slice splits across the model axis. Falls back to the plain
    data layout when the mesh has no model axis."""
    if ndim < 2:
        raise ValueError(
            f"data_model_sharding needs ndim >= 2 (got {ndim}); rank-1 "
            "operands are either data_sharding or model_sharding"
        )
    if MODEL_AXIS not in mesh.axis_names:
        return data_sharding(mesh, ndim)
    return NamedSharding(
        mesh, P(DATA_AXIS, *([None] * (ndim - 2)), MODEL_AXIS)
    )


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated — the analogue of the reference's broadcast variables
    (BroadcastUtils.withBroadcastStream, BroadcastUtils.java:64)."""
    return NamedSharding(mesh, P())


def fleet_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading FLEET axis of per-member state ([N, ...] carries,
    [N, pack] hypers/results) over the mesh data axis.

    The fleet-sharded regime (fleet.py) inverts the usual layout: when
    N x per-member state exceeds one device, the fleet axis rides the
    `data` mesh axis — each device owns N/shards whole members — and the
    training DATA is replicated instead (each member still sees every
    example, so member math is untouched and solo-fit bit-parity holds).
    The spec is identical to `data_sharding`; the distinct helper exists
    because the two axes mean different things: a reduce over `data` in
    the fleet regime would SUM ACROSS MEMBERS, which no fleet kernel may
    ever emit."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def fleet_axis_shardable(mesh: Mesh, fleet_size: int) -> bool:
    """Whether a fleet of `fleet_size` members can shard its member axis
    over this mesh's data axis: the axis must exist with >1 shards and
    divide the fleet evenly (ragged member shards would force padded
    members whose dead lanes still burn flops in every vmapped epoch)."""
    shards = num_data_shards(mesh)
    return shards > 1 and fleet_size % shards == 0


def pad_to_multiple(array, multiple: int, axis: int = 0, pad_value=0):
    """Pad `axis` up to a multiple so it divides evenly across shards.

    TPUs need static, evenly divisible shapes; the reference instead lets
    Flink deal ragged partitions. Returns (padded, original_length).
    """
    n = array.shape[axis]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return array, n
    pad_width = [(0, 0)] * array.ndim
    pad_width[axis] = (0, target - n)
    return np.pad(np.asarray(array), pad_width, constant_values=pad_value), n


def shard_batch(mesh: Mesh, array, pad_value=0) -> Tuple[jax.Array, int]:
    """Device-put a host array sharded over the data axis (padding as needed).

    Returns (device_array, original_row_count). The padding rows must be
    masked out by the caller (weight 0 in training math).
    """
    shards = num_data_shards(mesh)
    padded, n = pad_to_multiple(np.asarray(array), shards, axis=0, pad_value=pad_value)
    return jax.device_put(padded, data_sharding(mesh, padded.ndim)), n


def replicate(mesh: Mesh, array) -> jax.Array:
    return jax.device_put(np.asarray(array), replicated_sharding(mesh))


# ---------------------------------------------------------------------------
# how and where a device keeps an array (read by the batch layout's exchange
# and by the dense epoch's choice of form, ops/optimizer.py)
# ---------------------------------------------------------------------------
# the TPU's tile over the two minor axes of an array of a 32-bit type:
# 8 sublanes by 128 lanes (narrower types pack along the sublanes)
SUBLANES, LANES = 8, 128


def rows_minor(arr: jax.Array) -> bool:
    """Whether the device keeps a table's rows on its minor-most axis: the
    TPU's layout for a table narrower than a tile's 128 lanes (the
    reference's 100 features, a padded-CSR leaf), where a row is no
    contiguous run of memory and a reshape that splits the rows is not
    free. Read off the array; the CPU and a wide table keep rows major."""
    return arr.ndim == 2 and arr.format.layout.major_to_minor[-1] == 0


def on_tpu(arr: jax.Array) -> bool:
    """Whether every shard of the array lies on a TPU: where a Pallas TPU
    kernel over it compiles, and anywhere else is interpreted. Read off the
    array (`ops/optimizer._can_one_pass`)."""
    return all(device.platform == "tpu" for device in arr.devices())


# ---------------------------------------------------------------------------
# host-group mapping (multi-host snapshot coordination, ckpt/coordinator.py)
# ---------------------------------------------------------------------------
# On real DCN hardware `jax.devices()` spans processes and each host owns a
# contiguous slab of the device order (jax's device order follows the ICI
# topology, and process boundaries align with it). The virtual-device
# substrate models the same shape: a "host" is a contiguous group of mesh
# devices, and a leaf's per-host shard is the slice of the FULL array that
# host's devices would hold under the leaf's sharding tag. The tag->axis
# mapping lives here, next to the `<tag>_sharding` constructors it mirrors:
# `data` shards the leading (batch) dim, `model` the trailing (feature)
# dim, `replicated`/`host` leaves are whole-array and owned by host 0.

def host_groups(mesh: Mesh, num_hosts: int):
    """The mesh's devices as `num_hosts` contiguous groups (host i owns
    group i). Host counts need not divide the device count — trailing
    groups may be one device short (np.array_split semantics), and a host
    count above the device count leaves the surplus hosts empty-handed
    for devices but still shard OWNERS for snapshot writes."""
    if num_hosts < 1:
        raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
    devices = list(mesh.devices.flat)
    return [list(g) for g in np.array_split(np.array(devices), num_hosts)]


def form_mesh_over(groups: Sequence[Sequence], axis_names: Sequence[str] = (DATA_AXIS,)) -> Mesh:
    """Re-form a mesh over the concatenation of the given host device
    groups — the survivor mesh after the elastic supervisor
    (parallel/supervisor.py) quarantines a failed host. Groups come from
    `host_groups`; empty groups (surplus hosts) contribute nothing."""
    devices = [d for g in groups for d in g]
    if not devices:
        raise ValueError("cannot form a mesh over zero surviving devices")
    return create_mesh(axis_names, devices=devices)


def shard_axis_for_tag(tag: str, ndim: int) -> Optional[int]:
    """The array axis a sharding-spec tag splits across hosts, or None for
    whole-array tags (`replicated` / `host`). Mirrors `data_sharding`
    (leading dim) and `model_sharding` (trailing dim)."""
    if ndim <= 0:
        return None
    if tag == "data":
        return 0
    if tag == "model":
        return ndim - 1
    return None


def host_slice_bounds(length: int, num_hosts: int):
    """Per-host [start, stop) bounds splitting `length` rows/cols across
    `num_hosts` (np.array_split semantics: uneven lengths allowed, empty
    trailing slices when hosts outnumber elements)."""
    if num_hosts < 1:
        raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
    base, extra = divmod(int(length), int(num_hosts))
    bounds = []
    start = 0
    for h in range(num_hosts):
        stop = start + base + (1 if h < extra else 0)
        bounds.append((start, stop))
        start = stop
    return bounds


def init_distributed(coordinator_address: Optional[str] = None, **kwargs) -> None:
    """Multi-host bring-up over DCN (the analogue of the reference's cluster
    deployment). No-op when running single-process."""
    if coordinator_address is None:
        return
    jax.distributed.initialize(coordinator_address=coordinator_address, **kwargs)
