"""Host time a fit spends before the train program is launched: the program's
own phases `fit.extract` and `fit.stage` (counters in ns over the window;
`fit.stage` holds the batch layout, `fit.layout`), a fit. It is the host's
time, and not the chip's idle time: where the device already runs the layout
while the host goes on, the phases overlap device work. Nothing where the
program counts no such phase, or where not every fit of the window was a
linear model's own (`fit.total` also counts a pipeline's and every other
stage's fit, which have no such phases)."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.total.n")
    if not fits or counters.get("fit.extract.n") != fits:
        return None
    return (counters["fit.extract.ns"] + counters.get("fit.stage.ns", 0)) / fits / 1e6
