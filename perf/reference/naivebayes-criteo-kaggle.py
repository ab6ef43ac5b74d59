"""Plain reference: multinomial naive Bayes over indexed categorical fields.

NaiveBayes.java's GenerateModelFunction written down directly in numpy: the
labels and then one field at a time come to the host; the field's (label,
index) pairs are counted in blocks of rows with `np.bincount` into int64; a
field's categories are the indices some label counted, ascending; and

    theta[i][j][v] = log(count(i, j, v) + s) - log(count(i) + s * m_j)
    pi[i] = log(count(i) * F + s) - log(N * F + L * s)

in float64, with m_j the field's number of categories, F the fields, N the
rows, L the labels and s the smoothing. Imports nothing of the program.

`precision="bfloat16"` is the control: theta and pi rounded to bfloat16 (to
nearest, ties to even), the nearest precision below the float32 in which the
configuration states theta.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 23


@jax.jit
def _column(X, j):
    return jax.lax.dynamic_index_in_dim(X, j, axis=1, keepdims=False)


def counts(arrays: dict, data: dict):
    """(labels, each label's rows, [int64 counts [labels, largest index + 1]
    of each field]) of the table."""
    y = np.asarray(arrays["label"], np.float64)
    labels, label_idx = np.unique(y, return_inverse=True)
    num_labels = labels.size
    per_field = []
    for j, card in enumerate(int(c) for c in data["cardinalities"]):
        column = np.asarray(_column(arrays["features"], jnp.int32(j)))
        if column.max() >= card:
            raise ValueError(f"field {j} holds a value that is no index below {card}")
        bins = int(column.max()) + 1  # the field's largest index seen, not its cardinality: what is seen is counted
        field = np.zeros(num_labels * bins, np.int64)
        for start in range(0, column.size, BLOCK_ROWS):
            index = column[start : start + BLOCK_ROWS].astype(np.int64)
            if index.min() < 0 or np.any(index != column[start : start + BLOCK_ROWS]):
                raise ValueError(f"field {j} holds a value that is no index below {card}")
            field += np.bincount(label_idx[start : start + BLOCK_ROWS] * bins + index, minlength=num_labels * bins)
        per_field.append(field.reshape(num_labels, bins))
    return labels, np.bincount(label_idx, minlength=num_labels), per_field


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float64 -> float32 -> bfloat16 (to nearest, ties to even), as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) >> 16 << 16
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def model(labels, label_rows, per_field, smoothing: float, precision: str = "float64") -> dict:
    """{"labels", "categories": [each field's seen indices], "theta":
    [[labels, categories] of each field], "pi"} from exact counts."""
    label_rows = np.asarray(label_rows, np.float64)
    rows = label_rows.sum()
    fields = len(per_field)
    round_ = to_bfloat16 if precision == "bfloat16" else (lambda x: x)
    categories, theta = [], []
    for c in per_field:
        seen = np.flatnonzero(c.sum(axis=0))
        categories.append(seen.astype(np.float64))
        below = np.log(label_rows[:, None] + smoothing * seen.size)
        theta.append(round_(np.log(c[:, seen] + smoothing) - below))
    pi = np.log(label_rows * fields + smoothing) - np.log(rows * fields + labels.size * smoothing)
    return {"labels": labels, "categories": categories, "theta": theta, "pi": round_(pi)}


def pack(model: dict) -> np.ndarray:
    """A model as one float64 vector, [L, F, m_1 .. m_F | labels | every
    field's categories | theta [L, sum m] row by row | pi]: the form the
    harness's stand-ins for the program carry an answer in."""
    sizes = [c.size for c in model["categories"]]
    return np.concatenate([
        [len(model["labels"]), len(sizes)], sizes, model["labels"], *model["categories"],
        np.concatenate(model["theta"], axis=1).ravel(), model["pi"],
    ]).astype(np.float64)


def unpack(packed) -> dict | None:
    """`pack`'s vector as `model`'s dict, or None where the vector is no
    model's (its sizes are not whole, or do not add up to its length)."""
    v = np.asarray(packed, np.float64)
    if v.size < 2 or not all(float(x).is_integer() and x >= 0 for x in v[:2]):
        return None
    labels, fields = int(v[0]), int(v[1])
    sizes = v[2 : 2 + fields]
    if sizes.size != fields or not all(float(x).is_integer() and x >= 0 for x in sizes):
        return None
    total = int(sizes.sum())
    if v.size != 2 + fields + labels + total + labels * total + labels:
        return None
    at = 2 + fields + labels
    bounds = np.concatenate([[0], np.cumsum(sizes.astype(np.int64))])
    categories = v[at : at + total]
    theta = v[at + total : at + total + labels * total].reshape(labels, total)
    return {
        "labels": v[2 + fields : at],
        "categories": [categories[a:b] for a, b in zip(bounds[:-1], bounds[1:])],
        "theta": [theta[:, a:b] for a, b in zip(bounds[:-1], bounds[1:])],
        "pi": v[v.size - labels :],
    }


def fit(arrays: dict, data: dict, params: dict, precision: str = "float64"):
    """(the model packed as `pack` says, None)."""
    labels, label_rows, per_field = counts(arrays, data)
    return pack(model(labels, label_rows, per_field, float(params.get("smoothing", 1.0)), precision)), None
