"""Batched loss functions for linear-model training.

The reference computes per-sample loss/gradient with scalar BLAS calls
(common/lossfunc/BinaryLogisticLoss.java, HingeLoss.java,
LeastSquareLoss.java, LossFunc.java). Here each loss is a *batched* pure
function over (X[B,d], y[B], w[B], coeff[d]) returning
(loss_sum, grad_sum[d], weight_sum): the per-sample dot products become one
batched row contraction and the gradient accumulation one batched column
reduction. Formulas match the reference exactly (labels in {0,1},
scaled to ±1 internally) so training losses are comparable.

The dense contractions are written as broadcast-multiply + `jnp.sum`
(`dense_dot` / `dense_grad`) rather than `X @ coeff` / `X.T @ mult`
matvecs on purpose: a gemv and the gemm it becomes under `jax.vmap`
batching accumulate the contraction dimension in different orders on the
CPU backend (1–2 ULP drift for d >= 8), which would break the fleet
training contract — every fleet member bit-identical to its solo fit
(fleet.py, pinned by tests/test_fleet.py). The reduce form lowers to the
same per-row accumulation order whether or not a leading batch dimension
is present, so solo and vmapped fits share bits.

Which form runs where. XLA fuses the multiply into each reduction, and on
the TPU they stay what they are, two VPU reductions (`multiply_reduce_fusion`
in a device trace; nothing is rewritten to the MXU), each of which streams
the batch from HBM: 93% of a one-chip dense fit's device time went to
reading X twice (PERF.md §5, §6, PR 30). So on the TPU the one-shard flat
loop takes its epoch's sums from `ops/dense_epoch.one_pass` instead, a
Pallas kernel that reads the batch once and forms the row-dot, this
module's `pointwise` and the gradient from the tile in fast memory, for a
narrow float32 table the device keeps rows-minor
(`optimizer._can_one_pass` decides, from the array alone). The kernel sums
the same float32 products in another order (rows by lane, then the lanes),
so its fits agree with the reduce form's to rounding, not to the bit.

On the TPU a fleet's epoch is handed `product_variant(loss)` instead, whose
contractions are `jnp.dot` at `Precision.HIGHEST` (`_dense_product`): under
the fleet's member `vmap` the batched coefficient becomes a free dimension,
so the N members' row-dots are ONE [B, d] x [d, N] float32 product and
their gradients ONE [N, B] x [B, d], on the matrix unit, where the reduce
form's two vector-unit reductions took 94% of a 100-member fleet's epoch
(PERF.md §5, PR 39; `optimizer._fleet_multiplies` decides, from the table
and the loss, and a fleet fit counts `fleet.product.matrix` or
`fleet.product.reduce`). Its members agree with their solo fits to
rounding, as the kernel's do. So the reduce form is now the CPU's, where
the fleet's bit-parity contract lives and a second read of X costs nothing
like it does on the chip, and the solo general form's: laid-out batches on
several shards (GSPMD; that program reads its batch once already), sparse
rows, a wide or 16-bit table.

A fleet over sparse rows on a TPU is handed `rows_variant(loss)` instead
(`optimizer._fleet_rows`, counted `fleet.product.rows`): the members'
coefficients held member-minor, [d, N], so that an entry's N coefficients
are ONE gathered row and its N gradients ONE row segment-summed
(`sparse_rows_dot`, `_sparse_rows`), where `_sparse` under the member `vmap`
gathers and scatters N single values an entry; on the TPU both cost by the
entry. Its program is written out for the member axis
(`optimizer._sgd_fleet_rows_whole_fit_impl`), and its members agree with
their solo fits to rounding.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax.numpy as jnp
from jax import lax

LossOut = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # (loss_sum, grad_sum, weight_sum)


class LossFunc(NamedTuple):
    """A batched loss: name + callable(X, y, w, coeff) -> (loss_sum, grad_sum, weight_sum).

    `pointwise(dot, y, w) -> (per-row loss, per-row multiplier)` is the
    shared per-row form every layout is built from. `sparse` marks the
    padded-CSR (indices, values) input layout."""

    name: str
    fn: Callable[..., LossOut]
    pointwise: Callable = None
    sparse: bool = False

    def __call__(self, X, y, w, coeff) -> LossOut:
        return self.fn(X, y, w, coeff)


def _logistic_pointwise(dot, y, w):
    """-> (per-row loss, per-row multiplier); grad = X^T multiplier."""
    label_scaled = 2.0 * y - 1.0
    margin = dot * label_scaled
    # log(1 + exp(-margin)) computed stably
    loss = w * jnp.logaddexp(0.0, -margin)
    multiplier = w * (-label_scaled / (jnp.exp(margin) + 1.0))
    return loss, multiplier


def _hinge_pointwise(dot, y, w):
    label_scaled = 2.0 * y - 1.0
    margin = 1.0 - label_scaled * dot
    loss = w * jnp.maximum(0.0, margin)
    multiplier = jnp.where(margin > 0.0, -label_scaled * w, 0.0)
    return loss, multiplier


def _least_square_pointwise(dot, y, w):
    diff = dot - y
    loss = w * 0.5 * diff * diff
    multiplier = w * diff
    return loss, multiplier


def dense_dot(X, coeff):
    """Per-row dot products X[B,d] · coeff[d] -> [B], in the
    vmap-batching-stable reduce form (see module docstring). Every dense
    training-path dot that a bit-parity contract couples MUST go through
    this helper (or `dense_grad`): a solo fit and its fleet on the CPU,
    whole-fit and chunked programs. Mixing it with a `X @ coeff` matvec in
    such a path reintroduces the gemv/gemm accumulation split. The fleet's
    matrix form (`product_variant`) is taken on the TPU alone, where no
    such contract holds: a solo fit there takes the one-read kernel or this
    form, and neither sums in the product's order."""
    return jnp.sum(X * coeff, axis=-1)


def dense_grad(X, multiplier):
    """Gradient accumulation sum_B multiplier[B] * X[B,d] -> [d], the
    reduce-form twin of `dense_dot` (same vmap-stability contract)."""
    return jnp.sum(X * multiplier[..., None], axis=-2)


def _dense(pointwise):
    """Dense batched loss over (B, d) X; contractions via the
    vmap-stable `dense_dot`/`dense_grad` forms."""

    def fn(X, y, w, coeff) -> LossOut:
        loss, multiplier = pointwise(dense_dot(X, coeff), y, w)
        return jnp.sum(loss), dense_grad(X, multiplier), jnp.sum(w)

    return fn


def _dense_product(pointwise):
    """Dense batched loss whose contractions are float32 products at
    `Precision.HIGHEST`: a matvec each for one coefficient, and under the
    fleet's member `vmap`, whose coefficient carries the member axis, ONE
    [B, d] x [d, N] product for the N members' row-dots and ONE [N, B] x
    [B, d] for their gradients (`product_variant`)."""

    def fn(X, y, w, coeff) -> LossOut:
        dot = jnp.dot(X, coeff, precision=lax.Precision.HIGHEST)
        loss, multiplier = pointwise(dot, y, w)
        grad = jnp.dot(multiplier, X, precision=lax.Precision.HIGHEST)
        return jnp.sum(loss), grad, jnp.sum(w)

    return fn


def sparse_dot(indices, values, coeff):
    """Masked padded-CSR row dots: -1 indices are padding. The single
    definition of the padding/masking convention shared by training
    losses and inference (the batched analogue of the reference's
    dense x sparse BLAS.dot, BLAS.java:99-117). Returns (dot, safe, vals)
    so gradient callers reuse the masked operands."""
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    vals = jnp.where(valid, values, 0.0).astype(coeff.dtype)
    return jnp.sum(vals * coeff[safe], axis=1), safe, vals


def _sparse(pointwise):
    """Padded-CSR batched loss: X = (indices[B, k] int32 with -1 padding,
    values[B, k]). The per-row dot is a masked gather-and-sum and the
    gradient a scatter-add — the batched analogue of the reference's
    dense x sparse BLAS kernels (flink-ml-core/.../linalg/BLAS.java:69-117
    axpy/dot over SparseVector indices)."""

    def fn(X, y, w, coeff) -> LossOut:
        indices, values = X
        dot, safe, vals = sparse_dot(indices, values, coeff)
        loss, multiplier = pointwise(dot, y, w)
        grad = jnp.zeros_like(coeff).at[safe].add(
            vals * multiplier[:, None], mode="drop"
        )
        return jnp.sum(loss), grad, jnp.sum(w)

    return fn


BINARY_LOGISTIC_LOSS = LossFunc(
    "binary_logistic", _dense(_logistic_pointwise), _logistic_pointwise
)
HINGE_LOSS = LossFunc("hinge", _dense(_hinge_pointwise), _hinge_pointwise)
LEAST_SQUARE_LOSS = LossFunc(
    "least_square", _dense(_least_square_pointwise), _least_square_pointwise
)

#: dense loss name -> its matrix-product form, a DISTINCT LossFunc object
#: (the loss is a jit static argument), handed to the fleet programs alone.
PRODUCT_VARIANTS = {
    loss.name: LossFunc(loss.name + "_product", _dense_product(loss.pointwise), loss.pointwise)
    for loss in (BINARY_LOGISTIC_LOSS, HINGE_LOSS, LEAST_SQUARE_LOSS)
}

SPARSE_BINARY_LOGISTIC_LOSS = LossFunc(
    "sparse_binary_logistic", _sparse(_logistic_pointwise), _logistic_pointwise, True
)
SPARSE_HINGE_LOSS = LossFunc(
    "sparse_hinge", _sparse(_hinge_pointwise), _hinge_pointwise, True
)
SPARSE_LEAST_SQUARE_LOSS = LossFunc(
    "sparse_least_square", _sparse(_least_square_pointwise), _least_square_pointwise, True
)

SPARSE_VARIANTS = {
    BINARY_LOGISTIC_LOSS.name: SPARSE_BINARY_LOGISTIC_LOSS,
    HINGE_LOSS.name: SPARSE_HINGE_LOSS,
    LEAST_SQUARE_LOSS.name: SPARSE_LEAST_SQUARE_LOSS,
}


def sparse_rows_dot(indices, values, coeff):
    """`sparse_dot` for a member-minor coefficient [d, N]: every entry's N
    members' coefficients are ONE gathered row, and the row-dots come out
    [B, N]. Returns (dot, safe, vals) as `sparse_dot` does."""
    valid = indices >= 0
    safe = jnp.where(valid, indices, 0)
    vals = jnp.where(valid, values, 0.0).astype(coeff.dtype)
    return jnp.sum(vals[:, :, None] * coeff[safe], axis=1), safe, vals


def _sparse_rows(pointwise):
    """Padded-CSR batched loss of N members at once, their coefficients
    held member-minor, [d, N] (the fleet's row form, `rows_variant`): an
    entry's row-dots are ONE gather of an N-wide row and its gradients ONE
    N-wide row segment-summed into [d, N], where `_sparse` under the member
    `vmap` gathers and scatters N single values an entry. Returns
    (loss_sum [N], grad_sum [d, N], weight_sum)."""

    def fn(X, y, w, coeff) -> LossOut:
        indices, values = X
        dot, safe, vals = sparse_rows_dot(indices, values, coeff)
        loss, multiplier = pointwise(dot, y[:, None], w[:, None])
        grad = jnp.zeros_like(coeff).at[safe].add(
            vals[:, :, None] * multiplier[:, None, :], mode="drop"
        )
        return jnp.sum(loss, axis=0), grad, jnp.sum(w)

    return fn


#: sparse loss name -> its member-row form, a DISTINCT LossFunc object (the
#: loss is a jit static argument), handed to the fleet's row program alone.
ROW_VARIANTS = {
    loss.name: LossFunc(loss.name + "_rows", _sparse_rows(loss.pointwise), loss.pointwise, True)
    for loss in SPARSE_VARIANTS.values()
}


def _feature_sharded(pointwise):
    """Padded-CSR batched loss for the explicit 2D `(data, model)` mesh
    (parallel/sgd2d.py): runs INSIDE a shard_map body where
    `coeff` is this MODEL shard's contiguous feature slice (d_local,) at
    offset `axis_index(model) * d_local`, and (indices, values, y, w) are
    this DATA shard's batch rows with GLOBAL feature indices.

    Forward — active-feature all-gather over the model axis: each shard
    gathers only the active slots it OWNS (masked local gather) and the
    per-(row, slot) psum assembles the full active slice, since exactly
    one shard contributes a non-zero per slot (0 + x == x exactly). Wire
    bytes over `model` are B*nnz*itemsize — the dense (d,) vector never
    crosses a link, which is what makes beyond-HBM dims affordable.

    Gradient — data-axis-restricted reduce: the per-row multiplier
    contributions scatter into LOCAL slice coordinates (non-owned slots
    get index -1, dropped by the scatter), and reduce over `data` alone
    via the SparCML index-value exchange (pair bytes ∝ nnz) or, above the
    density threshold, the densified (d_local,) chunked reduce. The
    returned (loss_sum, weight_sum) are psum'd over `data` so the carry
    criteria are uniform — `_epoch_step` then applies the same update
    math as every other layout, on this shard's slice."""

    def fn(X, y, w, coeff) -> LossOut:
        import numpy as np

        from ..parallel import collectives
        from ..parallel.collectives import DATA_AXIS, MODEL_AXIS

        indices, values = X
        d_local = coeff.shape[0]
        lo = collectives.axis_index(MODEL_AXIS) * d_local
        valid = indices >= 0
        vals = jnp.where(valid, values, 0.0).astype(coeff.dtype)
        owned = valid & (indices >= lo) & (indices < lo + d_local)
        # the 1D sparse_dot masking convention, restricted to OWNED slots:
        # slot 0 with value +0.0 for everything this shard does not own
        # (a negative scatter index would WRAP to d_local-1, not drop)
        safe = jnp.where(owned, indices - lo, 0)
        owned_vals = jnp.where(owned, vals, 0.0)
        coeff_active = collectives.all_reduce_sum(
            jnp.where(owned, coeff[safe], 0.0), MODEL_AXIS
        )
        dot = jnp.sum(vals * coeff_active, axis=1)
        loss, multiplier = pointwise(dot, y, w)
        contrib = owned_vals * multiplier[:, None]
        rows, nnz = indices.shape
        itemsize = np.dtype(values.dtype).itemsize
        if collectives.sparse_reduce_wins(rows * nnz, d_local, itemsize=itemsize):
            grad = collectives.sparse_all_reduce_sum(
                safe, contrib, d_local, DATA_AXIS
            )
        else:
            grad = collectives.all_reduce_sum_chunked(
                jnp.zeros_like(coeff).at[safe].add(contrib, mode="drop"),
                DATA_AXIS,
            )
        sums = collectives.all_reduce_sum(
            jnp.stack([jnp.sum(loss), jnp.sum(w).astype(loss.dtype)]), DATA_AXIS
        )
        return sums[0], grad, sums[1].astype(w.dtype)

    return fn


FEATURE_SHARDED_BINARY_LOGISTIC_LOSS = LossFunc(
    "sparse_binary_logistic_2d", _feature_sharded(_logistic_pointwise),
    _logistic_pointwise, True,
)
FEATURE_SHARDED_HINGE_LOSS = LossFunc(
    "sparse_hinge_2d", _feature_sharded(_hinge_pointwise), _hinge_pointwise, True
)
FEATURE_SHARDED_LEAST_SQUARE_LOSS = LossFunc(
    "sparse_least_square_2d", _feature_sharded(_least_square_pointwise),
    _least_square_pointwise, True,
)

#: sparse loss name -> its 2D feature-sharded variant.
FEATURE_SHARDED_VARIANTS = {
    SPARSE_BINARY_LOGISTIC_LOSS.name: FEATURE_SHARDED_BINARY_LOGISTIC_LOSS,
    SPARSE_HINGE_LOSS.name: FEATURE_SHARDED_HINGE_LOSS,
    SPARSE_LEAST_SQUARE_LOSS.name: FEATURE_SHARDED_LEAST_SQUARE_LOSS,
}


def feature_sharded_variant(loss_func: LossFunc) -> LossFunc:
    """The 2D (data, model) LossFunc for a sparse loss. A DISTINCT cached
    LossFunc object per base loss (the loss is a jit static argument), so
    the 2D programs never collide with the 1D executables."""
    return FEATURE_SHARDED_VARIANTS[loss_func.name]


def product_variant(loss_func: LossFunc) -> LossFunc:
    """The matrix-product LossFunc for the dense loss `loss_func`."""
    return PRODUCT_VARIANTS[loss_func.name]


def rows_variant(loss_func: LossFunc) -> LossFunc:
    """The member-row LossFunc for the sparse loss `loss_func`."""
    return ROW_VARIANTS[loss_func.name]


def sparse_variant(name: str) -> LossFunc:
    """The padded-CSR LossFunc for the dense loss `name`."""
    return SPARSE_VARIANTS[name]


def predict_raw(X, coeff):
    """Raw linear prediction X @ coeff — the inference hot loop
    (LogisticRegressionModel.java:131 PredictLabelFunction)."""
    return X @ coeff


def sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))
