"""Host time a fit spends taking results that were ready: the transfers and
host copies of every blocking read inside the fit (`np.asarray(jax.device_get)`
after the wait), from the program's funnel `tracing.sync`. The counter
`fit.sync.copy.ns` over the window's outermost fits (`fit.outer.n`). What a
caller does with the host array afterwards (a turn to float64) is the fit's
own time, `fit_host_self_ms`. Nothing where the program counts no outermost
fit."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fit.outer.n")
    if not fits:
        return None
    return counters.get("fit.sync.copy.ns", 0) / fits / 1e6
