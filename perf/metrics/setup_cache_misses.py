"""Programs that set-up had to compile: jax's persistent-cache miss events
before the window. 0 on every run of a cell after its first in a checkout."""


def read(run):
    return run["setup_cache"]["misses"]
