"""Device time of collective operations over device-busy time, mean of the
devices. A trace with no collective operation reports nothing."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["collective_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return trace["collective_s"] / trace["busy_s"] * 100.0
