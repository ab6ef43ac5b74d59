"""Process start to the first timed fit: imports, the chip, the tables, the
compile cache (or compilation, on a cell's first run) and the warm-up fit."""


def read(run):
    return run["setup_s"]
