"""All rows trained by all fits of the window, over the whole window's wall
time (first fit's call to the last model on the host)."""


def read(run):
    win = run["window"]
    seconds = win["end"] - win["begin"]
    if not win["ops"] or seconds <= 0:
        return None
    return sum(win["units"]) / seconds
