"""Hashed click-log rows in padded-CSR form, made on the device from a seed.

A row has `integer_fields` count features and one categorical feature for each
entry of `cardinalities`, as a Criteo row has 13 and 26. An integer field keeps
a fixed id, the field's own number, and a value uniform in [0, 1) (counts
scaled to the unit interval). A categorical field draws a category uniformly
over its published cardinality and is one-hot: the category, numbered through
all the fields, is hashed into the ids above the integer fields', with the
value 1. So the fields with 3 to 30 categories give ids that a third to a
thirtieth of a batch's rows share, and the fields with millions spread over
the whole dimension: hot rows in the gather, collisions in the scatter-add.
Two fields of a row may hash to one id, and then both values count. Labels are
uniform over `label_arity` classes. One jitted call, block by block and written
in place, as the dense maker does.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 100_000


def block_rows(local_rows: int) -> int:
    block = min(BLOCK_ROWS, local_rows)
    while local_rows % block:
        block -= 1
    return block


def hashed(category, buckets: int):
    """A 32-bit mix (Murmur3's finalizer) of a uint32 category number, folded
    into [0, buckets)."""
    h = category.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h % jnp.uint32(buckets)).astype(jnp.int32)


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"indices": i32[rows, nnz], "values": f32[rows, nnz], "label":
    f32[rows]} sharded by rows over the mesh's `data` axis."""
    dim, nnz, arity = int(data["dim"]), int(data["nnz"]), int(data["label_arity"])
    counts = int(data["integer_fields"])
    cards = np.asarray(data["cardinalities"], np.int64)
    if counts + len(cards) != nnz:
        raise ValueError(f"{counts} integer and {len(cards)} categorical fields are not {nnz} a row")
    if int(cards.sum()) >= 2**32:
        raise ValueError("the categories of all fields together do not number in 32 bits")
    first = np.asarray([0, *itertools.accumulate(cards[:-1].tolist())], np.uint32)
    shards = mesh.shape["data"]
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} devices")
    local = rows // shards
    block = block_rows(local)

    def local_rows(key):
        shard_key = jax.random.fold_in(key, lax.axis_index("data"))
        count_ids = jnp.broadcast_to(jnp.arange(counts, dtype=jnp.int32), (block, counts))
        ones = jnp.ones((block, len(cards)), jnp.float32)

        def write(k, table):
            kc, kv, ky = jax.random.split(jax.random.fold_in(shard_key, k), 3)
            # 32 random bits modulo the cardinality: uniform to within cardinality / 2**32 (0.24%
            # at the widest field), and a program the chip's compiler takes 2 s over; `randint`
            # with a bound a field took it 27 s
            category = jax.random.bits(kc, (block, len(cards)), jnp.uint32) % jnp.asarray(cards, jnp.uint32)
            ids = counts + hashed(category + jnp.asarray(first), dim - counts)
            parts = (
                jnp.concatenate([count_ids, ids], axis=1),
                jnp.concatenate([jax.random.uniform(kv, (block, counts), jnp.float32), ones], axis=1),
                jax.random.randint(ky, (block,), 0, arity).astype(jnp.float32),
            )
            return tuple(
                lax.dynamic_update_slice_in_dim(whole, part, k * block, 0)
                for whole, part in zip(table, parts)
            )

        empty = (
            jnp.zeros((local, nnz), jnp.int32),
            jnp.zeros((local, nnz), jnp.float32),
            jnp.zeros((local,), jnp.float32),
        )
        return lax.fori_loop(0, local // block, write, empty)

    by_rows = P("data", None)
    fn = jax.jit(
        jax.shard_map(
            local_rows, mesh=mesh, in_specs=P(), out_specs=(by_rows, by_rows, P("data")),
            check_vma=False,
        ),
        out_shardings=(
            NamedSharding(mesh, by_rows), NamedSharding(mesh, by_rows), NamedSharding(mesh, P("data")),
        ),
    )
    indices, values, label = fn(key)
    return {"indices": indices, "values": values, "label": label}


def to_table(arrays: dict, data: dict):
    """The program's Table over the same device arrays (no copy)."""
    from flink_ml_tpu.table import SparseBatch, Table

    features = SparseBatch(int(data["dim"]), arrays["indices"], arrays["values"])
    return Table({"features": features, "label": arrays["label"]})


def from_table(table) -> dict:
    features = table.column("features")
    return {"indices": features.indices, "values": features.values, "label": table.column("label")}
