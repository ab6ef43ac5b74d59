"""XLA compilations inside the measured window, from the program's counter
`jit.compiles` (its listener on jax's backend-compile event). 0 in a steady
state: a shape or a static argument that differs from fit to fit shows here."""


def read(run):
    return run["counters"].get("jit.compiles", 0)
