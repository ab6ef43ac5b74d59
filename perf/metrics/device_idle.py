"""Share of the traced window in which nothing ran on the busiest device."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return (1.0 - trace["busy_s_fullest"] / trace["window_s"]) * 100.0
