"""What one Lloyd iteration needs, counted from the cell's shapes alone.

One iteration of k-means over n rows of d columns against k centroids,
whatever program implements it: the table read once and the centroids read
and written (float32); the cross term of every row with every centroid,
2 FLOP a multiply-add, and 3 FLOP an element of the table for the norms and
the cluster sums. `perf/work.py` keeps the linear family's counters and may
not be edited by the PR that brought this one; the generator `lloyd_loop`
hands this function to the harness under the configuration's `work` name.
"""

from __future__ import annotations

from typing import Dict


def lloyd_iteration(data: dict, params: dict) -> Dict[str, float]:
    """n is `globalBatchSize`, which the harness reads as the rows one call
    of the counter covers: Lloyd's batch is the whole table."""
    n, d, k = int(params["globalBatchSize"]), int(data["dim"]), int(params["k"])
    return {
        "bytes": n * d * 4 + 2 * k * d * 4,
        "flops": 2 * n * k * d + 3 * n * d,
    }
