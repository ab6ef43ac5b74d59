"""Host time a fit spends outside its named phases, nearly all of it after
the readback returned (unpack, flag check, model object, memory watermark):
the program's `fit.total` less `fit.extract`, `fit.stage` (which holds
`fit.layout`), `fit.launch` and `fit.readback` (counters in ns over the
window), a fit. Host time, not the chip's idle time. Nothing where the program
counts no such phase, or where not every fit of the window was a linear
model's own (see `fit_prelaunch_ms`): any other fit has no readback phase, and
its wait for the device would read as finish."""

PHASES = ("fit.extract", "fit.stage", "fit.launch", "fit.readback")


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.total.n")
    if not fits or counters.get("fit.extract.n") != fits:
        return None
    named = sum(counters.get(phase + ".ns", 0) for phase in PHASES)
    return (counters["fit.total.ns"] - named) / fits / 1e6
