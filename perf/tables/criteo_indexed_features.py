"""Indexed click-log fields as one features matrix, made on the device from a seed.

The categorical fields of the Criteo Display Advertising Challenge's log as
`criteo_indexed` draws them, laid out as a classifier over categories reads
them: `features`, f32[rows, fields], each value a field's category already
indexed against the whole log's dictionary (facebookresearch/dlrm
`data_utils.py`), and `label`, f32[rows]. The count fields are not drawn.

A block of rows is drawn as `criteo_indexed` draws it from the same key (the
same four keys split from it, the same Zipf(1) rank r = floor((N+1)**u)
spread over the field's range by its bijection `SPREAD`, the same uniform
labels), and row f holds field f's LAST index, cardinality - 1, as there.
The two makers differ in how rows fall into blocks: `criteo_indexed` takes
the largest divisor of a shard's rows up to `BLOCK_ROWS`, which for the
Kaggle log's 45,840,617 rows (no divisor from 2 to 100,000) is one row a
block; here every block holds `BLOCK_ROWS` rows, and the last one ends at the
table's last row and writes over the rows it shares with the block before
it. Where a shard's rows are a whole number of `BLOCK_ROWS`, the two give the
same categories and labels for one key, value for value
(tests/test_naivebayes_indexed.py). One jitted call, block by block and
written in place.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("perf_tables_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_INDEXED = _sibling("criteo_indexed")
BLOCK_ROWS, SPREAD = _INDEXED.BLOCK_ROWS, _INDEXED.SPREAD


def checked(data: dict) -> np.ndarray:
    cards = np.asarray(data["cardinalities"], np.int64)
    for card in cards.tolist():
        if card * max(SPREAD) >= 2**32 or any(np.gcd(card, f) != 1 for f in SPREAD):
            raise ValueError(f"a field of {card} categories: the spread {SPREAD} is no bijection of it in 32 bits")
    return cards


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"features": f32[rows, fields], "label": f32[rows]} sharded by rows over
    the mesh's `data` axis."""
    features, label = program(rows, data, mesh)(key)
    return {"features": features, "label": label}


def program(rows: int, data: dict, mesh):
    """The jitted maker of `make`, a function of the key."""
    cards = checked(data)
    arity, fields = int(data["label_arity"]), len(cards)
    shards = mesh.shape["data"]
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide over {shards} devices")
    local = rows // shards
    if local < fields:
        raise ValueError(f"{local} rows a shard cannot hold the last index of {fields} fields")
    block = min(BLOCK_ROWS, local)
    log_n1 = jnp.asarray(np.log(cards + 1.0), jnp.float32)
    cards_u = jnp.asarray(cards, jnp.uint32)
    last = jnp.asarray(cards - 1, jnp.float32)

    def local_rows(key):
        shard_key = jax.random.fold_in(key, lax.axis_index("data"))

        def write(k, table):
            features, label = table
            _, kc, kj, ky = jax.random.split(jax.random.fold_in(shard_key, k), 4)
            u = jax.random.uniform(kc, (block, fields), jnp.float32)
            within = jax.random.uniform(kj, (block, fields), jnp.float32) * 2.0**-23
            rank = jnp.floor(jnp.exp(u * log_n1) * (1.0 + within * log_n1)).astype(jnp.uint32)
            index = jnp.minimum(jnp.maximum(rank, 1), cards_u) - 1
            for factor in SPREAD:
                index = (index * jnp.uint32(factor)) % cards_u
            start = jnp.minimum(k * block, local - block)
            drawn = jax.random.randint(ky, (block,), 0, arity).astype(jnp.float32)
            return (
                lax.dynamic_update_slice_in_dim(features, index.astype(jnp.float32), start, 0),
                lax.dynamic_update_slice_in_dim(label, drawn, start, 0),
            )

        empty = (jnp.zeros((local, fields), jnp.float32), jnp.zeros((local,), jnp.float32))
        features, label = lax.fori_loop(0, -(-local // block), write, empty)
        # the whole log's dictionary: row f holds field f's last index
        diagonal = jnp.arange(fields)
        return features.at[diagonal, diagonal].set(last), label

    by_rows = (P("data", None), P("data"))
    return jax.jit(
        jax.shard_map(local_rows, mesh=mesh, in_specs=P(), out_specs=by_rows, check_vma=False),
        out_shardings=tuple(NamedSharding(mesh, spec) for spec in by_rows),
    )


def to_table(arrays: dict, data: dict):
    """The program's Table over the same device arrays (no copy)."""
    from flink_ml_tpu.table import Table

    return Table(arrays)


def from_table(table) -> dict:
    return {name: table.column(name) for name in table.column_names}
