"""What one epoch of a fleet of linear models over padded-CSR rows needs,
counted from the cell's shapes alone.

N members train on the SAME batch of B rows of nnz (id, value) pairs,
whatever program runs them: the batch read ONCE (an int32 id and a float32
value a pair, and the label column) whatever N, and every member's
coefficient read and written once, since `reg` > 0 decays every coefficient
every epoch; per member a gather-dot and a scatter-add, 2 FLOP a pair each.
N is the length of the one list-valued hyperparameter, the fleet's grid. At
N = 100 and dim 1e6 an epoch is 0.83 GB, 1.02 ms at a v5e's HBM peak: a floor,
since what a gather or a scatter costs by the entry (PERF.md section 6) is no
byte. `perf/work.py` keeps the solo counters and may not be edited by the PR
that brought this one; the generator hands this function to the harness under
the configuration's `work` name.
"""

from __future__ import annotations

from typing import Dict


def members(params: dict) -> int:
    (grid,) = [value for value in params.values() if isinstance(value, list)]
    return len(grid)


def fleet_sparse_lr_epoch(data: dict, params: dict) -> Dict[str, float]:
    batch, dim, nnz, n = int(params["globalBatchSize"]), int(data["dim"]), int(data["nnz"]), members(params)
    return {
        "bytes": batch * nnz * 8 + batch * 4 + 2 * n * dim * 4,
        "flops": 4 * batch * nnz * n,
    }
