"""What an epoch needs, counted from the cell's shapes and nothing else.

The bytes and FLOPs that the algorithm requires for one epoch over one batch,
whatever program implements it. A configuration names its counter under its
file's `work` key; the rooflines and `fit_mfu` read these numbers and never
the profiler's `bytes_accessed`, which counts what the compiled program
moved (a program that reads X twice would look twice as good).
"""

from __future__ import annotations

from typing import Dict


def dense_lr_epoch(data: dict, params: dict) -> Dict[str, float]:
    """Dense rows: X read once, label and weight columns, coefficient read
    and written; a row-dot and a gradient accumulation, 2 FLOP a feature each."""
    batch, dim = int(params["globalBatchSize"]), int(data["dim"])
    return {
        "bytes": batch * dim * 4 + 2 * batch * 4 + 2 * dim * 4,
        "flops": 4 * batch * dim,
    }


def sparse_lr_epoch(data: dict, params: dict) -> Dict[str, float]:
    """Padded-CSR rows: an (index, value) pair a non-zero, the label column,
    the coefficient read and written once; gather-dot and scatter-add."""
    batch, dim, nnz = int(params["globalBatchSize"]), int(data["dim"]), int(data["nnz"])
    return {
        "bytes": batch * nnz * 8 + batch * 4 + 2 * dim * 4,
        "flops": 4 * batch * nnz,
    }


def least_seconds(work: Dict[str, float], peak: dict, chips: int) -> Dict[str, float]:
    """The least time `chips` chips could take for `work`, and which peak
    binds: the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s."""
    by_flops = work["flops"] / (peak["flops_per_s"] * chips)
    by_bytes = work["bytes"] / (peak["hbm_bytes_per_s"] * chips)
    return {
        "seconds": max(by_flops, by_bytes),
        "bound": "hbm" if by_bytes >= by_flops else "flops",
        "flops_seconds": by_flops,
    }
