"""95th percentile (nearest rank) of the wall of every fit in the window,
from the call to the model's coefficient on the host."""

import math


def read(run):
    walls = sorted(end - start for start, end, _ in run["window"]["ops"])
    if not walls:
        return None
    return walls[math.ceil(0.95 * len(walls)) - 1] * 1000.0
