"""What the feature stages of a pipeline fit need for one partition, counted
from the cell's shapes alone.

Between the raw columns and the trainer's table lie a scaler, an encoder and
an assembler, whatever programs implement them and however many: the raw
feature columns read once (a float32 a numeric field, a 4-byte index a
categorical field; the label is the trainer's) and the assembled row written
once (an int32 id and a float32 value a stored entry). The passes the two
fits make before anything can be scaled or encoded (a sum and a sum of
squares, a maximum) read the same columns again and are LEFT OUT: a program
that kept a block in fast memory between the two would not pay them. So the
share of the roofline that reads this counter is a floor's, and cannot pass
100%. An operation a value for the scaling, a comparison and a selection an
index: 3 FLOP an entry reckoned, which binds nothing.
"""

from __future__ import annotations

from typing import Dict


def pipeline_prep(data: dict, rows: int) -> Dict[str, float]:
    nnz = int(data["nnz"])
    return {
        "bytes": rows * (nnz * 4 + nnz * 8),
        "flops": rows * nnz * 3,
    }
