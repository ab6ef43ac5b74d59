"""VectorAssembler — concatenates number/vector columns into one vector.

TPU-native re-design of feature/vectorassembler/VectorAssembler.java
(AssemblerFunction: per-row concat in inputCols order; `handleInvalid`
error/skip/keep over NaN values and null entries; `inputSizes` declares
per-column widths for validation and null filling). Columnar hstack
instead of a per-row flatMap.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...api import Transformer
from ...common.param import HasHandleInvalid, HasInputCols, HasOutputCol
from ...param import IntArrayParam
from ...table import SparseBatch, Table, as_dense_matrix, register_device_pytrees
from ...utils import metrics
from ...utils.lazyjit import lazy_jit


# the reference's rule (VectorAssembler.java): a row is assembled sparse when
# its stored entries, times this, are fewer than the vector's size
SPARSE_RATIO = 1.5
_NAN_MESSAGE = (
    "Encountered NaN while assembling a row with handleInvalid = 'error'. "
    "Consider removing NaNs from dataset or using handleInvalid = 'keep' or 'skip'."
)


def _width(col) -> int:
    """A column's width in the assembled vector."""
    if isinstance(col, SparseBatch):
        return col.size
    return int(col.shape[1]) if col.ndim > 1 else 1


def _stored(col) -> int:
    """The entries a row of the column stores."""
    return int(col.indices.shape[1]) if isinstance(col, SparseBatch) else _width(col)


def assembles_sparse(cols) -> bool:
    """Whether these inputs assemble into a `SparseBatch`: one of them is
    sparse, and the rows' stored entries times `SPARSE_RATIO` are fewer than
    the assembled size. The reference decides row by row; a column here has
    one static shape, so the padded width stands for every row's count."""
    if not any(isinstance(col, SparseBatch) for col in cols):
        return False
    return sum(map(_stored, cols)) * SPARSE_RATIO < sum(map(_width, cols))


def _dense(col, xp):
    """A column as an (n, width) matrix of `xp` (numpy or jax.numpy); a
    sparse one is scattered out where it lies."""
    if not isinstance(col, SparseBatch):
        return col if col.ndim > 1 else col[:, None]
    if xp is np:
        return col.to_dense()
    slot = xp.where(col.indices >= 0, col.indices, col.size)  # padding: past the end, dropped
    rows = xp.arange(col.indices.shape[0])[:, None]
    return xp.zeros((col.indices.shape[0], col.size), col.values.dtype).at[rows, slot].set(
        col.values, mode="drop"
    )


def _assemble_dense(cols, xp):
    return xp.concatenate([_dense(col, xp) for col in cols], axis=1)


def _assemble_sparse(cols, xp) -> SparseBatch:
    """The inputs side by side as ONE padded-CSR batch: a sparse input's ids
    shifted by the widths before it (its padding stays -1, so a one-hot
    column's dropped category stays an empty slot), a dense input at
    consecutive ids with its values, zeros included. Nothing is densified
    and no entry moves: entry j of input i lies at a fixed column."""
    dtype = np.result_type(*[(col.values if isinstance(col, SparseBatch) else col).dtype for col in cols])
    if dtype.kind != "f":
        dtype = np.dtype(np.float64 if xp is np else np.float32)
    ids, values, offset = [], [], 0
    for col in cols:
        if isinstance(col, SparseBatch):
            ids.append(xp.where(col.indices >= 0, col.indices + offset, -1).astype(np.int32))
            values.append(col.values.astype(dtype))
        else:
            mat = _dense(col, xp)
            span = xp.arange(offset, offset + mat.shape[1], dtype=np.int32)
            ids.append(xp.broadcast_to(span, mat.shape))
            values.append(mat.astype(dtype))
        offset += _width(col)
    return SparseBatch(offset, xp.concatenate(ids, axis=1), xp.concatenate(values, axis=1))


def _assemble_impl(*cols):
    """Device inputs assembled by the reference's rule, and whether a NaN
    came through."""
    import jax.numpy as jnp

    if assembles_sparse(cols):
        out = _assemble_sparse(cols, jnp)
        return out, jnp.isnan(out.values).any()
    out = _assemble_dense(cols, jnp)
    return out, jnp.isnan(out).any()


_assemble_kernel = lazy_jit(_assemble_impl)


class VectorAssemblerParams(HasInputCols, HasOutputCol, HasHandleInvalid):
    INPUT_SIZES = IntArrayParam(
        "inputSizes", "Sizes of the input elements to be assembled.", None
    )

    def get_input_sizes(self):
        return self.get(self.INPUT_SIZES)

    def set_input_sizes(self, *values: int):
        if any(v <= 0 for v in values):
            raise ValueError("Input sizes must be positive")
        return self.set(self.INPUT_SIZES, list(values))


class VectorAssembler(Transformer, VectorAssemblerParams):
    fusable = True
    kernel_supports_sparse = True

    def supports_fusion(self) -> bool:
        # 'skip' drops NaN rows — a data-dependent row count
        return self.get_handle_invalid() != HasHandleInvalid.SKIP_INVALID

    def kernel_output_sparse(self, sparse_inputs: bool) -> bool:
        # a plan knows its columns' kinds, not their sizes: a sparse input may
        # still assemble dense (`assembles_sparse`), which a stage that takes
        # sparse columns takes too
        return sparse_inputs

    def _checked_inputs(self, column_of) -> list:
        """The input columns in `inputCols` order, each held to its declared
        `inputSizes` entry."""
        in_cols = self.get_input_cols()
        if not in_cols:
            raise ValueError("Parameter inputCols must be set")
        sizes = self.get_input_sizes()
        cols = [column_of(name) for name in in_cols]
        for i, (name, col) in enumerate(zip(in_cols, cols)):
            if sizes is not None and _width(col) != sizes[i]:
                raise ValueError(
                    f"Input column {name} has size {_width(col)}, "
                    f"declared inputSizes[{i}] = {sizes[i]}"
                )
        return cols

    def transform_kernel(self, consts, cols, ctx):
        out, any_nan = _assemble_impl(*self._checked_inputs(cols.__getitem__))
        if self.get_handle_invalid() == HasHandleInvalid.ERROR_INVALID:
            ctx.guard(any_nan, _NAN_MESSAGE)
        cols[self.get_output_col()] = out
        return cols

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        from .._linear import is_device_column

        def column_of(name):
            col = table.column(name)
            if isinstance(col, SparseBatch):
                return col
            return as_dense_matrix(col, allow_device=True)

        cols = self._checked_inputs(column_of)
        if all(is_device_column(col) for col in cols):
            # all-device inputs: assembly + NaN scan on device; the invalid
            # flag is the only readback unless rows must be skipped
            from ...utils.packing import packed_device_get

            register_device_pytrees()
            out, any_nan = _assemble_kernel(*cols)
            # the flag pull IS the transform's one sync; packed_device_get
            # accounts it (host_sync.transform + readback bytes) in one place
            any_nan = bool(packed_device_get(any_nan, sync_kind="transform")[0])
        else:
            cols = [
                SparseBatch(col.size, np.asarray(col.indices), np.asarray(col.values))
                if isinstance(col, SparseBatch)
                else np.asarray(col)
                for col in cols
            ]
            out = _assemble_sparse(cols, np) if assembles_sparse(cols) else _assemble_dense(cols, np)
            any_nan = bool(np.isnan(_values(out)).any())
        result = table.with_column(self.get_output_col(), out)
        self.kernel_ran({self.get_output_col(): out})
        if any_nan:
            handle = self.get_handle_invalid()
            if handle == HasHandleInvalid.ERROR_INVALID:
                raise ValueError(_NAN_MESSAGE)
            if handle == HasHandleInvalid.SKIP_INVALID:
                bad = _values(out)
                if is_device_column(out):
                    import jax.numpy as jnp

                    (bad,) = packed_device_get(jnp.isnan(bad).any(axis=1), sync_kind="transform")
                else:
                    bad = np.isnan(bad).any(axis=1)
                result = result.take(np.nonzero(~bad)[0])
        return [result]

    def kernel_ran(self, out_cols) -> None:
        """Which form the assembled column took, once a column: a fused
        program's kernel body runs only when it is traced, so the count is
        taken from what the program returned."""
        out = out_cols.get(self.get_output_col())
        if out is not None:
            sparse = isinstance(out, SparseBatch)
            metrics.inc_counter("assembler.sparse_out" if sparse else "assembler.dense_out")


def _values(col):
    return col.values if isinstance(col, SparseBatch) else col
