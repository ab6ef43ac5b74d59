"""Click-log rows over the fields' own tables, skewed inside a field, in
padded-CSR form, made on the device from a seed.

A row has `integer_fields` count features and one categorical feature for each
entry of `cardinalities`, as a Criteo row has 13 and 26. Nothing is hashed: the
model has one coefficient an integer field (ids 0 .. integer_fields - 1, value
uniform in [0, 1)) and one a category, a field's ids offset by the fields before
it, so `dim` = integer_fields + sum(cardinalities). A categorical field of N
categories draws a RANK r with p(r) proportional to 1/r, r = floor((N+1)**u)
for uniform u (Zipf with exponent 1, truncated at N): rank 1 takes
ln 2 / ln(N+1) of the rows, 4% of a field of 40,000,000. A fixed bijection of
the field's range, id = ((r-1) * 101 mod N) * 103 mod N, spreads the ranks, so
that hot categories are 10,403 coefficients apart and not neighbours in memory.
The value is 1. Labels are uniform over `label_arity` classes. One jitted call,
block by block and written in place, as `sparse_criteo` does.

`sparse_criteo` (hashed into `dim` bins, uniform inside a field) stays as it is
for the configuration that names it.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 100_000
SPREAD = (101, 103)  # two multiplications mod N, each inside 32 bits for N < 2**32 / 103


def block_rows(local_rows: int) -> int:
    block = min(BLOCK_ROWS, local_rows)
    while local_rows % block:
        block -= 1
    return block


def offsets(data: dict) -> np.ndarray:
    """The first id of every categorical field: the integer fields' ids, then
    the fields' tables one after another."""
    cards = [int(c) for c in data["cardinalities"]]
    counts = int(data["integer_fields"])
    return np.asarray([counts + s for s in itertools.accumulate([0, *cards[:-1]])], np.int64)


def spread(rank0, cards):
    """The bijection of [0, N): rank - 1 -> id inside the field (uint32)."""
    out = rank0
    for factor in SPREAD:
        out = (out * jnp.uint32(factor)) % cards
    return out


def skew_of(data: dict) -> str:
    """`zipf1`, the configuration's, or `uniform`: every category of a field
    as likely as another, the other side of what the source leaves open (a
    batch then holds about 62,000 distinct coordinates and not 37,000). No
    cell names it; `probe_stream.py --skew uniform` reads the learner there."""
    skew = data.get("skew", "zipf1")
    if skew not in ("zipf1", "uniform"):
        raise ValueError(f"skew {skew!r}: zipf1 or uniform")
    return skew


def checked(data: dict):
    counts, nnz = int(data["integer_fields"]), int(data["nnz"])
    cards = np.asarray(data["cardinalities"], np.int64)
    if counts + len(cards) != nnz:
        raise ValueError(f"{counts} integer and {len(cards)} categorical fields are not {nnz} a row")
    if counts + int(cards.sum()) != int(data["dim"]):
        raise ValueError(f"the fields' tables hold {counts + int(cards.sum())} coefficients, not dim = {data['dim']}")
    if int(data["dim"]) >= 2**31:
        raise ValueError("the ids do not number in int32")
    for card in cards.tolist():
        if card * max(SPREAD) >= 2**32 or any(math.gcd(card, f) != 1 for f in SPREAD):
            raise ValueError(f"a field of {card} categories: the spread {SPREAD} is no bijection of it in 32 bits")
    return counts, nnz, cards


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"indices": i32[rows, nnz], "values": f32[rows, nnz], "label":
    f32[rows]} sharded by rows over the mesh's `data` axis."""
    counts, nnz, cards = checked(data)
    arity = int(data["label_arity"])
    first = jnp.asarray(offsets(data), jnp.uint32)
    shards = mesh.shape["data"]
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} devices")
    local = rows // shards
    block = block_rows(local)
    log_n1 = jnp.asarray(np.log(cards + 1.0), jnp.float32)
    cards_u = jnp.asarray(cards, jnp.uint32)
    cards_f = jnp.asarray(cards, jnp.float32)
    uniform = skew_of(data) == "uniform"

    def local_rows(key):
        shard_key = jax.random.fold_in(key, lax.axis_index("data"))
        count_ids = jnp.broadcast_to(jnp.arange(counts, dtype=jnp.int32), (block, counts))
        ones = jnp.ones((block, len(cards)), jnp.float32)

        def write(k, table):
            kc, kj, kv, ky = jax.random.split(jax.random.fold_in(shard_key, k), 4)
            # u on a 2**-23 grid, and a second draw inside the grid's step: without it the
            # ranks above 10**5 of a field of 40,000,000 would fall on a twentieth of the categories
            u = jax.random.uniform(kc, (block, len(cards)), jnp.float32)
            within = jax.random.uniform(kj, (block, len(cards)), jnp.float32) * 2.0**-23
            if uniform:
                rank = jnp.floor((u + within) * cards_f).astype(jnp.uint32) + 1
            else:
                rank = jnp.floor(jnp.exp(u * log_n1) * (1.0 + within * log_n1)).astype(jnp.uint32)
            rank0 = jnp.minimum(jnp.maximum(rank, 1), cards_u) - 1
            ids = (first + spread(rank0, cards_u)).astype(jnp.int32)
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
