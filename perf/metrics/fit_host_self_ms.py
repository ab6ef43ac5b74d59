"""A fit's own host time: the span of the outermost fit less its child spans,
the waits and the copies of its blocking reads (`fit.outer.ns` less
`fit.sync.wait.ns` less `fit.sync.copy.ns`), over the window's outermost fits
(`fit.outer.n`). Python and the runtime's enqueues: everything the host did
itself while a fit ran, whether or not the device had work meanwhile. With
`fit_wait_ms` and `fit_d2h_ms` it sums to the fit's wall by the program's
clock. Nothing where the program counts no outermost fit."""

CHILDREN = ("fit.sync.wait.ns", "fit.sync.copy.ns")


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.outer.n")
    if not fits:
        return None
    return (counters["fit.outer.ns"] - sum(counters.get(child, 0) for child in CHILDREN)) / fits / 1e6
