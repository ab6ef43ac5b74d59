"""Host time a fit spends asleep until a device result it asked for was ready:
the waits of every blocking read inside the fit, whatever its kind (the plan's
counts, the look's boolean, a pipeline's moments, sizes and guards, the packed
result), from the program's funnel `tracing.sync`, which times
`jax.block_until_ready` apart from the copy that follows it. The counter
`fit.sync.wait.ns` over the window's outermost fits (`fit.outer.n`: a
pipeline's fit is one, with its stages' fits inside it). It holds the device's
work and the runtime's notice of its end. Nothing where the program counts no
outermost fit."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.outer.n")
    if not fits:
        return None
    return counters.get("fit.sync.wait.ns", 0) / fits / 1e6
