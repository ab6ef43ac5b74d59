"""What one NaiveBayes fit over indexed fields needs, counted from the cell's
shapes alone.

One fit over `rows` rows of F indexed fields and L labels, whatever program
implements it: the features (float32) and the labels read once; one int32 bin
for each label and each category of the fields' published cardinalities,
written once; the model written once, a float32 value and L float32 log
probabilities for each category seen. How many are seen follows from the
table maker's draw (Zipf(1) ranks, `expected_seen`), not from a run. FLOPs:
an add for each (row, field), a logarithm and a subtraction for each bin and
label. `perf/work.py` keeps the linear family's counters and may not be edited
by the PR that brought this one; the generator `naivebayes_fit_loop` hands
this function to the harness under the configuration's `work` name.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def expected_seen(categories: int, rows: int) -> float:
    """The categories of one field that `rows` draws are expected to show,
    where rank r is drawn as floor((N + 1)**u) for a uniform u, so with
    probability log((r + 1) / r) / log(N + 1), and N takes what is left."""
    rank = np.arange(1, categories, dtype=np.float64)
    p = np.log1p(1.0 / rank) / np.log(categories + 1.0)
    p = np.append(p, max(0.0, 1.0 - p.sum()))
    return float(np.sum(-np.expm1(rows * np.log1p(-np.minimum(p, 1.0 - 1e-16)))))


def naivebayes_count(data: dict, params: dict) -> Dict[str, float]:
    """`globalBatchSize` is the rows one call of the counter covers: a fit
    reads the whole table once, so it is the table's rows."""
    rows, labels = int(params["globalBatchSize"]), int(data["label_arity"])
    cards = [int(c) for c in data["cardinalities"]]
    seen = sum(expected_seen(c, rows) for c in cards)
    return {
        "bytes": rows * len(cards) * 4 + rows * 4 + labels * sum(cards) * 4 + (1 + labels) * seen * 4,
        "flops": rows * len(cards) + 2 * labels * sum(cards),
    }
