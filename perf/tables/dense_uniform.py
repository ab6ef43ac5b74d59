"""Dense labelled table made on the device from a seed.

What the reference's LabeledPointWithWeightGenerator draws: features uniform
in [0, 1), labels uniform over `label_arity` classes. One jitted call; each
device of the mesh makes its own rows, block by block and written in place,
so the temporaries are one block and never a second table (a stacked
`lax.map` output takes another layout on the v5e and costs one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 100_000


def block_rows(local_rows: int) -> int:
    """The largest block that divides a device's rows, at most BLOCK_ROWS."""
    block = min(BLOCK_ROWS, local_rows)
    while local_rows % block:
        block -= 1
    return block


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"features": f32[rows, dim], "label": f32[rows]} sharded by rows over
    the mesh's `data` axis."""
    dim, arity = int(data["dim"]), int(data["label_arity"])
    shards = mesh.shape["data"]
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} devices")
    local = rows // shards
    block = block_rows(local)

    def local_rows(key):
        shard_key = jax.random.fold_in(key, lax.axis_index("data"))

        def one(k):
            kx, ky = jax.random.split(jax.random.fold_in(shard_key, k))
            x = jax.random.uniform(kx, (block, dim), jnp.float32)
            y = jax.random.randint(ky, (block,), 0, arity).astype(jnp.float32)
            return x, y

        def write(k, table):
            x, y = one(k)
            return (
                lax.dynamic_update_slice_in_dim(table[0], x, k * block, 0),
                lax.dynamic_update_slice_in_dim(table[1], y, k * block, 0),
            )

        empty = (jnp.zeros((local, dim), jnp.float32), jnp.zeros((local,), jnp.float32))
        return lax.fori_loop(0, local // block, write, empty)

    fn = jax.jit(
        jax.shard_map(
            local_rows, mesh=mesh, in_specs=P(), out_specs=(P("data", None), P("data")),
            check_vma=False,
        ),
        out_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P("data")),
        ),
    )
    features, label = fn(key)
    return {"features": features, "label": label}


def to_table(arrays: dict, data: dict):
    """The program's Table over the same device arrays (no copy)."""
    from flink_ml_tpu.table import Table

    return Table({"features": arrays["features"], "label": arrays["label"]})


def from_table(table) -> dict:
    return {"features": table.column("features"), "label": table.column("label")}
