"""What one global batch of the online FTRL learner needs, counted from the
cell's shapes alone.

One batch of `globalBatchSize` padded-CSR rows of `nnz` stored entries, whatever
program folds it and whatever the skew: the batch read once (an int32 id and a
float32 value an entry, a float32 label a row), and per entry a multiply-add of
the row-dot, a multiply-add of the gradient sum and the entry's share of the
per-coordinate update (a count, a division, two square roots and some dozen
more: 16 FLOP reckoned). The state's traffic is LEFT OUT: how many distinct
coordinates a batch holds depends on the data (37,000 of 159,744 entries under
this configuration's skew, every entry under none), and a counter that read
the data would move with the seed. So the share of the roofline that reads
this counter is a floor: a program cannot do less, and the share cannot pass
100%. `perf/work.py` keeps the linear family's counters and may not be edited
by the PR that brought this one; the generator `stream_loop` hands this
function to the harness under the configuration's `work` name.
"""

from __future__ import annotations

from typing import Dict


def ftrl_batch(data: dict, params: dict) -> Dict[str, float]:
    batch, nnz = int(params["globalBatchSize"]), int(data["nnz"])
    return {
        "bytes": batch * (nnz * 8 + 4),
        "flops": batch * nnz * (4 + 16),
    }
