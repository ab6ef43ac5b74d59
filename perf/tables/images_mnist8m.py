"""Image table in the shape of MNIST8m, made on the device from a seed.

The set itself (Loosli, Canu, Bottou: 60,000 originals x 135 deformations,
8.1M rows of 784 pixels) cannot be fetched from a sealed machine, so its shape
is drawn: `rows / variants` base images on the 28 x 28 grid, each a few
strokes (elongated blobs with a saturated core, as a pen leaves them), and
`variants` rows a base, each the base moved by up to two pixels, turned and
thickened a little, with noise on the pixels that are lit. Pixels are whole
numbers 0..255 in float32 and about a fifth of a row's pixels are non-zero.
Row r is variant r // bases of base r % bases, as the set cycles through its
originals. Classes are not drawn: k-means ignores them.

The two levels are what the comparison with a reference needs: rows that are
independent noise are all nearly as far from every centroid, and the
assignment then turns on the last bit of a distance.

One jitted call; the rows are made block by block and written in place, so
the temporaries are one block and never a second table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 25_000
STROKES = 3
SIDE = 28


def block_rows(rows: int) -> int:
    """The largest block that divides the rows, at most BLOCK_ROWS."""
    block = min(BLOCK_ROWS, rows)
    while rows % block:
        block -= 1
    return block


def _bases(key, bases: int):
    """Each base's strokes: centre (x, y), direction, half-length and
    half-width of each, in pixels."""
    kc, kt, kl, kw = jax.random.split(key, 4)
    shape = (bases, STROKES)
    return {
        "cx": jax.random.uniform(kc, shape + (2,), jnp.float32, 8.0, 20.0),
        "theta": jax.random.uniform(kt, shape, jnp.float32, 0.0, jnp.pi),
        "length": jax.random.uniform(kl, shape, jnp.float32, 4.0, 8.0),
        "width": jax.random.uniform(kw, shape, jnp.float32, 1.1, 1.7),
    }


def _render(strokes, key):
    """(block, 784) pixels of the rows whose strokes are given: each row's
    base deformed by its own draw, then lit, then noised where lit."""
    block = strokes["theta"].shape[0]
    ks, kr, kg, kn = jax.random.split(key, 4)
    shift = jax.random.uniform(ks, (block, 1, 2), jnp.float32, -2.0, 2.0)
    turn = jax.random.uniform(kr, (block, 1), jnp.float32, -0.15, 0.15)
    grow = jax.random.uniform(kg, (block, 1), jnp.float32, 0.9, 1.15)
    centre = strokes["cx"] + shift
    theta = strokes["theta"] + turn
    grid = jnp.arange(SIDE, dtype=jnp.float32)
    # pixel (x, y) relative to each stroke's centre: (block, strokes, 28, 28)
    dx = grid[None, None, None, :] - centre[..., 0][..., None, None]
    dy = grid[None, None, :, None] - centre[..., 1][..., None, None]
    cos, sin = jnp.cos(theta)[..., None, None], jnp.sin(theta)[..., None, None]
    along = (dx * cos + dy * sin) / strokes["length"][..., None, None]
    across = (dy * cos - dx * sin) / (strokes["width"] * grow)[..., None, None]
    ink = jnp.sum(jnp.exp(-0.5 * (along**4 + across**2)), axis=1)  # (block, 28, 28)
    lit = jnp.clip((ink - 0.3) * 2.2, 0.0, 1.0).reshape(block, SIDE * SIDE)
    noise = jax.random.uniform(kn, lit.shape, jnp.float32, -24.0, 24.0)
    return jnp.where(lit > 0, jnp.clip(jnp.round(lit * 255.0 + noise), 1.0, 255.0), 0.0)


def make(key, rows: int, data: dict, mesh) -> dict:
    """{"features": f32[rows, 784]} on the mesh's one device."""
    dim, variants = int(data["dim"]), int(data["variants"])
    if dim != SIDE * SIDE:
        raise ValueError(f"the images are {SIDE} x {SIDE}; the configuration says dim {dim}")
    if mesh.shape["data"] != 1:
        raise ValueError("this table is made for one device (the whole set over four chips is a cell yet to come)")
    if rows % variants:
        raise ValueError(f"{rows} rows are not a whole number of bases with {variants} variants each")
    bases = rows // variants
    block = block_rows(rows)
    sharding = NamedSharding(mesh, P("data", None))

    def table(key):
        key_bases, key_rows = jax.random.split(key)
        strokes = _bases(key_bases, bases)

        def write(i, out):
            base = (i * block + jnp.arange(block)) % bases
            mine = {name: jnp.take(value, base, axis=0) for name, value in strokes.items()}
            pixels = _render(mine, jax.random.fold_in(key_rows, i))
            return lax.dynamic_update_slice_in_dim(out, pixels, i * block, 0)

        return lax.fori_loop(0, rows // block, write, jnp.zeros((rows, dim), jnp.float32))

    return {"features": jax.jit(table, out_shardings=sharding)(key)}


def to_table(arrays: dict, data: dict):
    """The program's Table over the same device array (no copy)."""
    from flink_ml_tpu.table import Table

    return Table({"features": arrays["features"]})


def from_table(table) -> dict:
    return {"features": table.column("features")}
