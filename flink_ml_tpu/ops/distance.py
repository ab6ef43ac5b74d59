"""Pluggable distance measures, batched for TPU.

Mirrors common/distance/DistanceMeasure.java:64 (getInstance dispatch,
euclidean/manhattan/cosine variants, VectorWithNorm fast paths). The
reference computes point-to-centroid distances one pair at a time; here
`pairwise` computes the full (n_points, n_centroids) matrix as one MXU
matmul (plus norms), which is the KMeans/Knn hot loop.

Precision. Distances are float32 arithmetic: every cross term goes through
`cross_term`, a float32 product. On a TPU the default for a float32 matmul
is ONE bfloat16 pass (operands rounded to 8 bits), which moves assignments
between near centroids. The float32 product is a sum over bfloat16 pieces:
each operand is hi + mid + lo, 8 significant bits each, and the products
whose weight float32 can hold are added, six of them for two operands of
three pieces (`lax.Precision.HIGHEST`, the compiler's form). How many passes
buy float32 is this module's business, and what decides it is how many
pieces the POINTS have (`point_pieces`): not known, the six passes; one
(every value is its own bfloat16: pixel bytes, byte descriptors), the three
products of the points with the second operand's pieces, the other three of
the six being products of zeros. No term that holds anything is dropped,
whatever the table (`Precision.HIGH` drops three whatever they hold), so
the product is float32's either way and the comparison with the plain
float32 reference polices it. The caller has to KNOW: `KMeans.fit` looks at
every row of its table, every fit; nothing else passes anything. An
assignment goes through `first_minimum`, which keeps the compiler from
comparing the distances at a lower precision than they were computed in.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


# How many bfloat16 pieces a float32 value has: all three where nothing is
# known of it, one where it is exact in bfloat16.
ALL_PIECES, ONE_PIECE = 3, 1


def in_bfloat16(values):
    """The float32 values rounded to bfloat16's 8 significant bits and kept
    in float32. `lax.reduce_precision` and not `astype(bfloat16).astype(
    float32)`: inside a fusion the v5e's compiler carries the pair of casts
    out in float32 and rounds nothing (PERF.md, PR 35: a table scaled by
    1/255 compared equal to its own round trip, and the pieces below came
    out as (hi, 0, 0))."""
    return lax.reduce_precision(values, exponent_bits=8, mantissa_bits=7)


def bfloat16_pieces(C):
    """(hi, mid, lo) in bfloat16 with hi + mid + lo == C to float32's last
    bit: each piece rounds what the ones before it left."""
    hi = in_bfloat16(C)
    mid = in_bfloat16(C - hi)
    return tuple(piece.astype(jnp.bfloat16) for piece in (hi, mid, C - hi - mid))


def cross_term(X, C, point_pieces=ALL_PIECES):
    """X @ C.T, (n, d) x (k, d) -> (n, k), in float32 arithmetic (see the
    module docstring): the one product every measure, KMeans, Knn and the
    model's `transform` share. `point_pieces` is static and says what the
    caller knows of X: `ONE_PIECE` promises that every value of X is exact in
    bfloat16, and the product is then X against each piece of C, one
    bfloat16 pass each. The three are ONE product with the piece as a second
    contracted axis, the points three times against (lo, mid, hi), so that
    they are added where the compiler adds its six, in the matrix unit's
    float32 accumulators: three products added afterwards become three
    fusions on the v5e, each handed the (n, k) sum of the ones before
    through HBM (PERF.md, PR 35: 2.02 s of a fit's device time against
    1.64)."""
    if point_pieces == ALL_PIECES:
        return jnp.matmul(X, C.T, precision=lax.Precision.HIGHEST)
    if point_pieces != ONE_PIECE:
        raise ValueError(f"points of {point_pieces} bfloat16 pieces have no product here")
    points = X.astype(jnp.bfloat16)  # exact, by the caller's word
    hi, mid, lo = bfloat16_pieces(C)
    return lax.dot_general(
        jnp.broadcast_to(points, (ALL_PIECES,) + points.shape),
        jnp.stack([lo, mid, hi]),
        (((0, 2), (0, 2)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def first_minimum(values):
    """The index of each row's smallest value, the lowest on a tie -> (n,).

    The values are held whole before they are compared. On a TPU (v5e,
    measured at 8,192 x 4,096 x 784 against float64 on the host, PR 27) an
    argmin that the compiler fuses into the matrix product that made its
    operand compares to about bfloat16's precision whatever precision the
    product was asked for: 1.2% of the rows went to a centroid up to 0.3% of
    the distance further away, while the same product read back whole was
    right in every row. The barrier costs one write and one read of the
    (n, k) values, a block at a time where the caller walks blocks."""
    return jnp.argmin(lax.optimization_barrier(values), axis=1)


EUCLIDEAN = "euclidean"
MANHATTAN = "manhattan"
COSINE = "cosine"


class DistanceMeasure:
    name: str = ""

    @staticmethod
    def get_instance(name: str) -> "DistanceMeasure":
        for cls in (EuclideanDistanceMeasure, ManhattanDistanceMeasure, CosineDistanceMeasure):
            if cls.name == name:
                return cls()
        raise ValueError(f"Unsupported distance measure {name!r}")

    def pairwise(self, X, C, point_pieces=ALL_PIECES):
        """Distances between rows of X (n, d) and rows of C (k, d) -> (n, k).
        `point_pieces` is `cross_term`'s; a measure without a product has
        no use for it."""
        raise NotImplementedError

    def distance(self, a, b):
        return self.pairwise(jnp.atleast_2d(a), jnp.atleast_2d(b))[0, 0]

    def closeness(self, X, C, point_pieces=ALL_PIECES):
        """An (n, k) matrix that orders each row's centroids as `pairwise`
        does, for an argmin: a measure may leave out what is the same along
        a row or monotone (euclidean drops the point's norm and the root)."""
        return self.pairwise(X, C, point_pieces)

    def find_closest(self, X, C):
        """Index of the closest centroid for each row of X -> (n,) int32;
        the lowest index on a tie."""
        return first_minimum(self.closeness(X, C)).astype(jnp.int32)


class EuclideanDistanceMeasure(DistanceMeasure):
    name = EUCLIDEAN

    def pairwise(self, X, C, point_pieces=ALL_PIECES):
        # ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2 ; the cross term is the matmul.
        x2 = jnp.sum(X * X, axis=1, keepdims=True)
        c2 = jnp.sum(C * C, axis=1)[None, :]
        sq = x2 - 2.0 * cross_term(X, C, point_pieces) + c2
        return jnp.sqrt(jnp.maximum(sq, 0.0))

    def closeness(self, X, C, point_pieces=ALL_PIECES):
        # ||c||^2 - 2 x.c: no root of an (n, k) matrix, no norm of the rows
        return jnp.sum(C * C, axis=1)[None, :] - 2.0 * cross_term(X, C, point_pieces)


class ManhattanDistanceMeasure(DistanceMeasure):
    name = MANHATTAN

    def pairwise(self, X, C, point_pieces=ALL_PIECES):
        return jnp.sum(jnp.abs(X[:, None, :] - C[None, :, :]), axis=-1)


class CosineDistanceMeasure(DistanceMeasure):
    name = COSINE

    def pairwise(self, X, C, point_pieces=ALL_PIECES):
        xn = jnp.linalg.norm(X, axis=1, keepdims=True)
        cn = jnp.linalg.norm(C, axis=1)[None, :]
        sim = cross_term(X, C, point_pieces) / jnp.maximum(xn * cn, 1e-12)
        return 1.0 - sim


from ..utils.lazyjit import keyed_jit  # noqa: E402

# One jitted find_closest kernel per measure name, created once at first
# use. `jax.jit(measure.find_closest)` at each transform call would build a
# fresh wrapper (and retrace) per call — the lazyjit keying audit moved
# every such per-call wrapper to a module-level cache.
jit_find_closest = keyed_jit(
    lambda name: DistanceMeasure.get_instance(name).find_closest
)
