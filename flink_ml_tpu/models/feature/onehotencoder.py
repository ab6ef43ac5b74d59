"""OneHotEncoder — encodes index columns as one-hot sparse vectors.

TPU-native re-design of feature/onehotencoder/OneHotEncoder.java:246 and
OneHotEncoderModel.java (`dropLast` default true: stored vector size =
numCategories - 1 and the last category encodes as the empty vector;
handleInvalid error/keep). Output is a SparseBatch per encoded column.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...api import Estimator, Model
from ...common.param import HasHandleInvalid, HasInputCols, HasOutputCols
from ...param import BooleanParam
from ...table import SparseBatch, Table
from ...utils import metrics, read_write
from ...utils.lazyjit import keyed_jit, lazy_jit
from ...utils.param_utils import update_existing_params

_BAD_INDEX = (
    "The input contains an invalid (non-integer, negative "
    "or out-of-range) index in column {}."
)


def _no_index(col):
    """Where a device column holds what is no index: a negative number, or
    no whole one (a NaN is neither)."""
    import jax.numpy as jnp

    return (col.astype(jnp.int32).astype(col.dtype) != col) | (col < 0)


def _onehot_impl(col, vec_size: int, drop: bool):
    import jax.numpy as jnp

    int_idx = col.astype(jnp.int32)
    not_int = _no_index(col)
    limit = vec_size if drop else vec_size - 1
    out_of_range = int_idx > limit
    bad = (not_int | out_of_range).any()
    # index == vec_size (the dropped last category) -> empty vector
    indices = jnp.where(int_idx < vec_size, int_idx, -1)[:, None]
    values = jnp.where(indices >= 0, 1.0, 0.0).astype(jnp.float32)
    return indices, values, bad


def _encode_all(vec_sizes, drop: bool):
    """Every column of a table encoded by one program: the (indices, values)
    pairs, and one validity flag a column as ONE vector, the transform's
    single readback."""

    def fn(cols):
        import jax.numpy as jnp

        encoded = [_onehot_impl(col, size, drop) for col, size in zip(cols, vec_sizes)]
        return [pair[:2] for pair in encoded], jnp.stack([pair[2] for pair in encoded])

    return fn


_encode_all_keyed = keyed_jit(_encode_all)


@lazy_jit
def _fit_sizes(cols):
    """i32[2, columns]: each column's largest index, and whether it holds a
    value that is no index. One program over all the columns and one array,
    so that a fit reads back once whatever the number of columns."""
    import jax.numpy as jnp

    return jnp.stack(
        [
            jnp.stack([jnp.max(col).astype(jnp.int32) for col in cols]),
            jnp.stack([_no_index(col).any() for col in cols]).astype(jnp.int32),
        ]
    )


class OneHotEncoderModelParams(HasInputCols, HasOutputCols, HasHandleInvalid):
    DROP_LAST = BooleanParam("dropLast", "Whether to drop the last category.", True)

    def get_drop_last(self) -> bool:
        return self.get(self.DROP_LAST)

    def set_drop_last(self, value: bool):
        return self.set(self.DROP_LAST, value)


class OneHotEncoderParams(OneHotEncoderModelParams):
    pass


class OneHotEncoderModel(Model, OneHotEncoderModelParams):
    fusable = True
    kernel_emits_sparse = True
    kernel_exact = True  # comparisons and selections of 0, 1 and whole indices

    def __init__(self):
        self.category_sizes: np.ndarray = None  # per-column max index + 1

    def kernel_static(self):
        return tuple(int(size) for size in self.category_sizes)

    def supports_fusion(self) -> bool:
        # only handleInvalid='error' exists (reference contract); anything
        # else raises eagerly before any device work
        return self.get_handle_invalid() == HasHandleInvalid.ERROR_INVALID

    def _constant_sources(self):
        return (self.category_sizes,)

    def transform_kernel(self, consts, cols, ctx):
        drop = 1 if self.get_drop_last() else 0
        for i, (name, out_name) in enumerate(
            zip(self.get_input_cols(), self.get_output_cols())
        ):
            vec_size = int(self.category_sizes[i]) - drop
            indices, values, bad = _onehot_impl(cols[name], vec_size, bool(drop))
            ctx.guard(bad, _BAD_INDEX.format(name))
            cols[out_name] = SparseBatch(vec_size, indices, values)
        return cols

    def set_model_data(self, *inputs: Table) -> "OneHotEncoderModel":
        (model_data,) = inputs
        rows = model_data.collect()
        sizes = {}
        for row in rows:
            sizes[int(row["columnIndex"])] = int(row["categorySize"])
        self.category_sizes = np.asarray(
            [sizes[i] for i in range(len(sizes))], dtype=np.int64
        )
        return self

    def get_model_data(self) -> List[Table]:
        return [
            Table(
                {
                    "columnIndex": np.arange(len(self.category_sizes)),
                    "categorySize": np.asarray(self.category_sizes),
                }
            )
        ]

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        # The reference supports only handleInvalid=error
        # (OneHotEncoderModel.java:73 checkArgument).
        if self.get_handle_invalid() != HasHandleInvalid.ERROR_INVALID:
            raise ValueError("OneHotEncoder only supports handleInvalid = 'error'")
        drop = 1 if self.get_drop_last() else 0
        in_cols, out_cols = self.get_input_cols(), self.get_output_cols()
        vec_sizes = tuple(int(size) - drop for size in self.category_sizes[: len(in_cols)])
        from .._linear import is_device_column

        cols = [table.column(name) for name in in_cols]
        if cols and all(is_device_column(col) for col in cols):
            # device columns: one program encodes them all, and its one
            # vector of flags (an index that is no whole number, negative or
            # out of range, a column) is the transform's only readback
            from ...utils.packing import packed_device_get

            encoded, bad = _encode_all_keyed(vec_sizes, bool(drop))(cols)
            (bad,) = packed_device_get(bad, sync_kind="transform")
            for name, flag in zip(in_cols, bad):
                if flag:
                    raise ValueError(_BAD_INDEX.format(name))
            return [
                table.with_columns(
                    {
                        out_name: SparseBatch(size, indices, values)
                        for out_name, size, (indices, values) in zip(out_cols, vec_sizes, encoded)
                    }
                )
            ]
        updates = {}
        for name, out_name, vec_size, col in zip(in_cols, out_cols, vec_sizes, cols):
            idx = np.asarray(col, dtype=np.float64)
            int_idx = idx.astype(np.int64)
            if np.any(int_idx != idx) or np.any(int_idx < 0):
                raise ValueError(f"Value cannot be parsed as indexed integer in column {name}")
            if np.any(int_idx > vec_size if drop else int_idx >= vec_size):
                raise ValueError(f"The input contains invalid index in column {name}.")
            # index == vec_size (the dropped last category) -> empty vector.
            indices = np.where(int_idx < vec_size, int_idx, -1).astype(np.int32)[:, None]
            values = np.where(indices >= 0, 1.0, 0.0)
            updates[out_name] = SparseBatch(vec_size, indices, values)
        return [table.with_columns(updates)]

    def _save_extra(self, path: str) -> None:
        read_write.save_model_arrays(path, categorySizes=self.category_sizes)

    def _load_extra(self, path: str) -> None:
        from ...utils import javacodec

        self.category_sizes = read_write.load_arrays_or_reference(
            path, javacodec.load_reference_onehotencoder
        )["categorySizes"]


class OneHotEncoder(Estimator, OneHotEncoderParams):
    checkpointable = False
    checkpoint_reason = "single-pass category-count aggregation; a restart recomputes the fit"
    def fit(self, *inputs: Table) -> OneHotEncoderModel:
        (table,) = inputs
        names = self.get_input_cols()
        cols = [table.column(name) for name in names]
        from .._linear import is_device_column

        if cols and all(is_device_column(col) and col.ndim == 1 for col in cols):
            # columns that live on the device are counted there: one program,
            # one readback of two numbers a column (exact: an int32 array)
            from ...utils.packing import packed_device_get

            ((largest, bad),) = packed_device_get(_fit_sizes(cols), sync_kind="fit")
            metrics.inc_counter("onehot.fit.device", len(cols))
            for name, flag in zip(names, bad):
                if flag:
                    raise ValueError(f"Value cannot be parsed as indexed integer in column {name}")
            sizes = [int(m) + 1 for m in largest]
        else:
            metrics.inc_counter("onehot.fit.host", len(cols))
            sizes = []
            for name, col in zip(names, cols):
                idx = np.asarray(col, dtype=np.float64)
                int_idx = idx.astype(np.int64)
                if np.any(int_idx != idx) or np.any(int_idx < 0):
                    raise ValueError(f"Value cannot be parsed as indexed integer in column {name}")
                sizes.append(int(int_idx.max()) + 1)
        model = OneHotEncoderModel()
        model.category_sizes = np.asarray(sizes, dtype=np.int64)
        update_existing_params(model, self)
        return model
