"""Bucketizer — maps continuous columns into bucket indices by split points.

TPU-native re-design of feature/bucketizer/Bucketizer.java +
BucketizerParams.java (`splitsArray`: per-column strictly-increasing split
points; `handleInvalid` error/skip/keep for values outside all buckets —
`keep` maps them to the extra bucket numSplits-1). Columnar searchsorted
instead of a per-row scan.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...api import Transformer
from ...common.param import HasHandleInvalid, HasInputCols, HasOutputCols
from ...param import DoubleArrayArrayParam, ParamValidators
from ...table import Table
from ...utils.lazyjit import lazy_jit


def _bucketize_impl(arr, splits):
    """Device bucket assignment: value in [splits[i], splits[i+1]) -> i,
    last bucket right-closed (Bucketizer.java findBucket). The few split
    points broadcast down lanes, so the 'searchsorted' is one compare-sum
    sweep — no gather. Returns (idx, bad) with idx float for the output."""
    import jax.numpy as jnp

    num_buckets = splits.shape[0] - 1
    idx = jnp.sum(arr[:, None] >= splits[None, :], axis=1) - 1
    idx = jnp.where(arr == splits[-1], num_buckets - 1, idx)
    bad = (arr < splits[0]) | (arr > splits[-1]) | jnp.isnan(arr)
    return idx.astype(jnp.float32), bad


_bucketize_kernel = lazy_jit(_bucketize_impl)


class BucketizerParams(HasInputCols, HasOutputCols, HasHandleInvalid):
    SPLITS_ARRAY = DoubleArrayArrayParam(
        "splitsArray",
        "Array of split points for mapping continuous features into buckets.",
        None,
        ParamValidators.non_empty_array(),
    )

    def get_splits_array(self):
        return self.get(self.SPLITS_ARRAY)

    def set_splits_array(self, value):
        for splits in value:
            if len(splits) < 3 or np.any(np.diff(splits) <= 0):
                raise ValueError(
                    "Each splits array should have at least 3 strictly increasing points"
                )
        return self.set(self.SPLITS_ARRAY, [list(map(float, s)) for s in value])


class Bucketizer(Transformer, BucketizerParams):
    fusable = True

    def supports_fusion(self) -> bool:
        # 'skip' drops invalid rows — a data-dependent row count no pure
        # static-shape kernel can express
        return self.get_handle_invalid() != HasHandleInvalid.SKIP_INVALID

    def kernel_ready(self, cols) -> bool:
        # mirror the eager fallback: when a split point has no exact
        # representation in the column dtype the device compare would move
        # boundary values into the wrong bucket — host path only
        splits_array = self.get_splits_array() or []
        for name, splits in zip(self.get_input_cols() or [], splits_array):
            col = cols.get(name)
            if col is None:
                return False
            splits = np.asarray(splits, dtype=np.float64)
            cast = splits.astype(np.dtype(col.dtype))
            if not np.array_equal(cast.astype(np.float64), splits):
                return False
        return True

    def transform_kernel(self, consts, cols, ctx):
        import jax.numpy as jnp

        in_cols, out_cols = self.get_input_cols(), self.get_output_cols()
        splits_array = self.get_splits_array()
        if len(in_cols) != len(splits_array):
            raise ValueError(
                "Bucketizer: number of splits arrays must match number of input columns"
            )
        handle = self.get_handle_invalid()
        for name, out_name, splits in zip(in_cols, out_cols, splits_array):
            col = cols[name]
            splits = np.asarray(splits, dtype=np.float64)
            num_buckets = len(splits) - 1
            idx, bad = _bucketize_impl(col, jnp.asarray(splits, col.dtype))
            if handle == HasHandleInvalid.KEEP_INVALID:
                idx = jnp.where(bad, float(num_buckets), idx)
            else:  # error: deferred to the fused guard drain
                ctx.guard(
                    bad.any(),
                    "The input contains invalid value. See "
                    + self.HANDLE_INVALID.name
                    + " parameter for more options.",
                )
            cols[out_name] = idx
        return cols

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        in_cols, out_cols = self.get_input_cols(), self.get_output_cols()
        splits_array = self.get_splits_array()
        if len(in_cols) != len(splits_array):
            raise ValueError(
                "Bucketizer: number of splits arrays must match number of input columns"
            )
        handle = self.get_handle_invalid()
        from .._linear import is_device_column

        updates = {}
        invalid_mask = np.zeros(table.num_rows, dtype=bool)
        bad_devs = []
        for name, out_name, splits in zip(in_cols, out_cols, splits_array):
            col = table.column(name)
            splits = np.asarray(splits, dtype=np.float64)
            num_buckets = len(splits) - 1
            if is_device_column(col):
                cast = splits.astype(np.dtype(col.dtype))
                if np.array_equal(cast.astype(np.float64), splits):
                    import jax
                    import jax.numpy as jnp

                    idx, bad = _bucketize_kernel(
                        col, jnp.asarray(splits, col.dtype)
                    )
                    if handle == HasHandleInvalid.KEEP_INVALID:
                        idx = jnp.where(bad, float(num_buckets), idx)
                    else:
                        bad_devs.append(bad)
                    updates[out_name] = idx
                    continue
                # splits do not survive the column dtype (e.g. a float64
                # boundary with no exact float32 representation): the device
                # compare would move boundary values into the wrong bucket,
                # so this column falls back to the exact host path
                col = np.asarray(col)
            arr = np.asarray(col, dtype=np.float64)
            # value in [splits[i], splits[i+1]) -> bucket i; last bucket is
            # closed on the right (Bucketizer.java findBucket semantics).
            idx = np.searchsorted(splits, arr, side="right") - 1
            idx = np.where(arr == splits[-1], num_buckets - 1, idx)
            bad = (arr < splits[0]) | (arr > splits[-1]) | np.isnan(arr)
            if handle == HasHandleInvalid.KEEP_INVALID:
                idx = np.where(bad, num_buckets, idx)
            else:
                invalid_mask |= bad
            updates[out_name] = idx.astype(np.float64)
        if bad_devs:
            combined = bad_devs[0]
            for b in bad_devs[1:]:
                combined = combined | b
            # scalar probe first: the full mask is read back only
            # when a row is actually invalid
            from ...obs import tracing

            if bool(tracing.sync("transform", combined.any())):
                invalid_mask |= np.asarray(combined)
        out = table.with_columns(updates)
        if invalid_mask.any():
            if handle == HasHandleInvalid.ERROR_INVALID:
                raise ValueError(
                    "The input contains invalid value. See "
                    + self.HANDLE_INVALID.name
                    + " parameter for more options."
                )
            out = out.take(np.nonzero(~invalid_mask)[0])
        return [out]
