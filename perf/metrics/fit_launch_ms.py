"""Host time a fit spends launching device programs through the program's
dispatch funnel: its phase `fit.launch` (counter in ns over the window), a
fit. Host time, not the chip's idle time. Nothing where the program counts no
such phase, or where not every fit of the window was a linear model's own
(see `fit_prelaunch_ms`)."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.total.n")
    if not fits or counters.get("fit.extract.n") != fits:
        return None
    return counters.get("fit.launch.ns", 0) / fits / 1e6
