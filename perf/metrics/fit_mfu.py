"""The whole fit's share of the chips' binding peak: the same least time as
`epoch_roofline`, for every row trained in the traced window, over the
window's wall time. It still bounds a claim once the device program is
replaced. The FLOP-only share (the rows' FLOPs over peak FLOP/s alone) goes to
standard error for the record: it is of the order of 0.01% and bounds nothing."""

import sys


def read(run):
    trace, least = run["trace"], run["least_per_unit"]
    if trace is None or least is None or trace["window_s"] <= 0:
        return None
    units = sum(run["window"]["units"])
    flop_only = units * least["flops_seconds"] / trace["window_s"] * 100.0
    print(f"flop_only_util = {flop_only!r} % (bound: {least['bound']})", file=sys.stderr)
    return units * least["seconds"] / trace["window_s"] * 100.0
