"""Pluggable distance measures, batched for TPU.

Mirrors common/distance/DistanceMeasure.java:64 (getInstance dispatch,
euclidean/manhattan/cosine variants, VectorWithNorm fast paths). The
reference computes point-to-centroid distances one pair at a time; here
`pairwise` computes the full (n_points, n_centroids) matrix as one MXU
matmul (plus norms), which is the KMeans/Knn hot loop.

Precision. Distances are float32 arithmetic: every cross term goes through
`cross_term`, a float32 product at `lax.Precision.HIGHEST`. On a TPU the
default for a float32 matmul is ONE bfloat16 pass (operands rounded to 8
bits), which moves assignments between near centroids; `HIGHEST` is the
compiler's six-pass form. How many passes buy float32 is this module's
business, and the comparison with the plain float32 reference polices it.
An assignment goes through `first_minimum`, which keeps the compiler from
comparing the distances at a lower precision than they were computed in.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def cross_term(X, C):
    """X @ C.T, (n, d) x (k, d) -> (n, k), in float32 arithmetic (see the
    module docstring): the one product every measure, KMeans, Knn and the
    model's `transform` share."""
    return jnp.matmul(X, C.T, precision=lax.Precision.HIGHEST)



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

    def pairwise(self, X, C):
        """Distances between rows of X (n, d) and rows of C (k, d) -> (n, k)."""
        raise NotImplementedError

    def distance(self, a, b):
        return self.pairwise(jnp.atleast_2d(a), jnp.atleast_2d(b))[0, 0]

    def closeness(self, X, C):
        """An (n, k) matrix that orders each row's centroids as `pairwise`
        does, for an argmin: a measure may leave out what is the same along
        a row or monotone (euclidean drops the point's norm and the root)."""
        return self.pairwise(X, C)

    def find_closest(self, X, C):
        """Index of the closest centroid for each row of X -> (n,) int32;
        the lowest index on a tie."""
        return first_minimum(self.closeness(X, C)).astype(jnp.int32)


class EuclideanDistanceMeasure(DistanceMeasure):
    name = EUCLIDEAN

    def pairwise(self, X, C):
        # ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2 ; the cross term is the matmul.
        x2 = jnp.sum(X * X, axis=1, keepdims=True)
        c2 = jnp.sum(C * C, axis=1)[None, :]
        sq = x2 - 2.0 * cross_term(X, C) + c2
        return jnp.sqrt(jnp.maximum(sq, 0.0))

    def closeness(self, X, C):
        # ||c||^2 - 2 x.c: no root of an (n, k) matrix, no norm of the rows
        return jnp.sum(C * C, axis=1)[None, :] - 2.0 * cross_term(X, C)


class ManhattanDistanceMeasure(DistanceMeasure):
    name = MANHATTAN

    def pairwise(self, X, C):
        return jnp.sum(jnp.abs(X[:, None, :] - C[None, :, :]), axis=-1)


class CosineDistanceMeasure(DistanceMeasure):
    name = COSINE

    def pairwise(self, X, C):
        xn = jnp.linalg.norm(X, axis=1, keepdims=True)
        cn = jnp.linalg.norm(C, axis=1)[None, :]
        sim = cross_term(X, C) / jnp.maximum(xn * cn, 1e-12)
        return 1.0 - sim


from ..utils.lazyjit import keyed_jit  # noqa: E402

# One jitted find_closest kernel per measure name, created once at first
# use. `jax.jit(measure.find_closest)` at each transform call would build a
# fresh wrapper (and retrace) per call — the lazyjit keying audit moved
# every such per-call wrapper to a module-level cache.
jit_find_closest = keyed_jit(
    lambda name: DistanceMeasure.get_instance(name).find_closest
)
