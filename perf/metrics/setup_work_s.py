"""Set-up less the wait for the chips: `setup_s` minus the time inside
`jax.devices()`, which brings the chips up and which neither the benchmark nor
the program steadies. What is left is theirs: imports, the tables from the
seed, the compile cache's loads (or compilation) and the warm-up fit."""


def read(run):
    return run["setup_s"] - run["devices_s"]
