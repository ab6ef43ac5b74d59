"""Indexed click-log rows as raw columns, made on the device from a seed.

A partition of the Criteo Display Advertising Challenge's log as
facebookresearch/dlrm's `data_utils.py` leaves it: `numeric`, f32[rows,
integer_fields], the counts fed as log(1 + count); one int32 column for each
entry of `cardinalities`, `C1` ... `C26`, the field's categories already
indexed against the whole log's dictionary; `label`, f32[rows]. Nothing is
encoded, offset or assembled: that is the pipeline's work.

A count is floor(exp(sigma_f * z)) for a standard normal z, a log-normal with
the field's own `count_sigmas` entry. A category is drawn as
`sparse_criteo_1tb` draws it: a RANK r with p(r) proportional to 1/r,
r = floor((N+1)**u), spread over the field's range by a fixed bijection (the
multipliers here are 103 and 107: 101 divides this log's field of 2,202,608).
Labels are uniform over `label_arity` classes. Row f of every partition holds
field f's LAST index, cardinality - 1: the dictionary was built over the whole
log, so a partition's encoder has to come to the published sizes, and every
partition to one model dimension. One jitted call, block by block and written
in place, as the other makers do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 100_000
SPREAD = (103, 107)  # two multiplications mod N, each inside 32 bits for N < 2**32 / 107


def block_rows(local_rows: int) -> int:
    block = min(BLOCK_ROWS, local_rows)
    while local_rows % block:
        block -= 1
    return block


def category_columns(data: dict) -> list:
    return [f"C{j + 1}" for j in range(len(data["cardinalities"]))]


def checked(data: dict):
    counts = int(data["integer_fields"])
    cards = np.asarray(data["cardinalities"], np.int64)
    sigmas = np.asarray(data["count_sigmas"], np.float32)
    if len(sigmas) != counts:
        raise ValueError(f"{len(sigmas)} count_sigmas for {counts} integer fields")
    if counts + len(cards) != int(data["nnz"]):
        raise ValueError(f"{counts} integer and {len(cards)} categorical fields are not {data['nnz']} a row")
    if counts + int((cards - 1).sum()) != int(data["dim"]):
        raise ValueError(
            f"one-hot with the last category dropped holds {counts + int((cards - 1).sum())} coefficients, "
            f"not dim = {data['dim']}"
        )
    for card in cards.tolist():
        if card * max(SPREAD) >= 2**32 or any(np.gcd(card, f) != 1 for f in SPREAD):
            raise ValueError(f"a field of {card} categories: the spread {SPREAD} is no bijection of it in 32 bits")
    return counts, cards, sigmas


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"numeric": f32[rows, integer_fields], "C1": i32[rows], ..., "label":
    f32[rows]} sharded by rows over the mesh's `data` axis."""
    counts, cards, sigmas = checked(data)
    arity, fields = int(data["label_arity"]), len(cards)
    shards = mesh.shape["data"]
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} devices")
    local = rows // shards
    if local < fields:
        raise ValueError(f"{local} rows a shard cannot hold the last index of {fields} fields")
    block = block_rows(local)
    log_n1 = jnp.asarray(np.log(cards + 1.0), jnp.float32)
    cards_u = jnp.asarray(cards, jnp.uint32)

    def local_rows(key):
        shard_key = jax.random.fold_in(key, lax.axis_index("data"))

        def write(k, table):
            kn, kc, kj, ky = jax.random.split(jax.random.fold_in(shard_key, k), 4)
            count = jnp.floor(jnp.exp(jnp.asarray(sigmas) * jax.random.normal(kn, (block, counts), jnp.float32)))
            # u on a 2**-23 grid, and a second draw inside the grid's step, as `sparse_criteo_1tb`
            u = jax.random.uniform(kc, (block, fields), jnp.float32)
            within = jax.random.uniform(kj, (block, fields), jnp.float32) * 2.0**-23
            rank = jnp.floor(jnp.exp(u * log_n1) * (1.0 + within * log_n1)).astype(jnp.uint32)
            index = jnp.minimum(jnp.maximum(rank, 1), cards_u) - 1
            for factor in SPREAD:
                index = (index * jnp.uint32(factor)) % cards_u
            index = index.astype(jnp.int32)
            parts = (
                jnp.log1p(count),
                *(index[:, j] for j in range(fields)),
                jax.random.randint(ky, (block,), 0, arity).astype(jnp.float32),
            )
            return tuple(
                lax.dynamic_update_slice_in_dim(whole, part, k * block, 0)
                for whole, part in zip(table, parts)
            )

        empty = (
            jnp.zeros((local, counts), jnp.float32),
            *(jnp.zeros((local,), jnp.int32) for _ in range(fields)),
            jnp.zeros((local,), jnp.float32),
        )
        numeric, *columns, label = lax.fori_loop(0, local // block, write, empty)
        # the whole log's dictionary: row f holds field f's last index
        columns = [column.at[f].set(int(cards[f]) - 1) for f, column in enumerate(columns)]
        return (numeric, *columns, label)

    by_rows = (P("data", None), *([P("data")] * (fields + 1)))
    fn = jax.jit(
        jax.shard_map(local_rows, mesh=mesh, in_specs=P(), out_specs=by_rows, check_vma=False),
        out_shardings=tuple(NamedSharding(mesh, spec) for spec in by_rows),
    )
    numeric, *columns, label = fn(key)
    return {"numeric": numeric, **dict(zip(category_columns(data), columns)), "label": label}


def to_table(arrays: dict, data: dict):
    """The program's Table over the same device arrays (no copy)."""
    from flink_ml_tpu.table import Table

    return Table(arrays)


def from_table(table) -> dict:
    return {name: table.column(name) for name in table.column_names}
