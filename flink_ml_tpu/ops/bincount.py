"""Counts of bounded ids by label on the device: a bincount with a label axis.

Both forms add the (label, id) pairs of one block of rows into int32 bins
[num_labels, num_bins], exactly, and sort no column (XLA's TPU scatter, in
`label_bincount`, sorts its own keys, block by block: about 1.07 s of a
10.4 s count over the 45.8M rows of 26 Criteo fields on one v5e):

- `label_bincount` scatter-adds them (XLA's scatter). Its cost is one update
  an entry whatever the number of bins, so it serves any width: a field of
  ten million categories, a hashed feature space.
- `label_match_counts` compares each value with a short list of candidates
  and sums the matches over the rows in one product on the matrix unit. Its
  cost grows with the candidates, so it serves narrow ranges, where the
  scatter's many updates to a few hot bins serialise.

The callers bound the ids (every id in [0, num_bins)) and keep the bins below
2**31 entries; nothing here checks either. A row given the label past the
last (label_idx == num_labels) is counted by neither form: that is how a
caller leaves rows out of a block.
"""

from __future__ import annotations


def label_bincount(bins, ids, label_idx):
    """`bins` [L, B] int32 plus one count for each (label_idx[r], ids[r, k]):
    ids [rows, k] int32 in [0, B), label_idx [rows] int32 in [0, L]. A row of
    label L is counted nowhere."""
    import jax.numpy as jnp

    num_labels, num_bins = bins.shape
    keys = (label_idx[:, None] * num_bins + ids).reshape(-1)
    flat = bins.reshape(-1).at[keys].add(jnp.ones_like(keys), mode="drop")
    return flat.reshape(num_labels, num_bins)


def label_match_counts(values, candidates, label_idx, num_labels: int):
    """int32 [L, len(candidates)]: for each label and candidate, the rows of
    that label whose value equals the candidate (label_idx in [0, L]; a row
    of label L is counted nowhere). values [rows] and
    candidates [m] of one dtype. The 0/1 factors are exact in bfloat16 and
    their sums in float32 below 2**24 rows, so a block holds fewer."""
    import jax.numpy as jnp

    match = (values[:, None] == candidates[None, :]).astype(jnp.bfloat16)
    by_label = (label_idx[:, None] == jnp.arange(num_labels)[None, :]).astype(jnp.bfloat16)
    counts = jnp.einsum("rl,rm->lm", by_label, match, preferred_element_type=jnp.float32)
    return counts.astype(jnp.int32)
