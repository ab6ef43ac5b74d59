"""What one epoch of a fleet of linear models needs, counted from the cell's
shapes alone.

N members train on the SAME batch of B rows of d columns, whatever program
runs them: the batch read ONCE (float32 rows, the label and weight columns)
whatever N, and each member's coefficient read and written; per member a
row-dot and a gradient accumulation, 2 FLOP a feature each. N is the length of
the one list-valued hyperparameter, the fleet's grid. At N = 100 an epoch is
100 FLOP a byte where the solo epoch (`perf/work.py::dense_lr_epoch`) has 1:
the HBM bound still binds against the published peaks (49 us against 20 us of
bf16 matrix-unit time), but a program that keeps the products on the vector
unit is far from either. `perf/work.py` keeps the solo counters and may not be
edited by the PR that brought this one; the generator `fleet_fit_loop` hands
this function to the harness under the configuration's `work` name.
"""

from __future__ import annotations

from typing import Dict


def members(params: dict) -> int:
    (grid,) = [value for value in params.values() if isinstance(value, list)]
    return len(grid)


def fleet_lr_epoch(data: dict, params: dict) -> Dict[str, float]:
    batch, dim, n = int(params["globalBatchSize"]), int(data["dim"]), members(params)
    return {
        "bytes": batch * dim * 4 + 2 * batch * 4 + 2 * n * dim * 4,
        "flops": 4 * batch * dim * n,
    }
