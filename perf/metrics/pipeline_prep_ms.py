"""Host time a pipeline fit spends before its last estimator's fit is called:
every feature stage's fit and the training table's way through the fitted
stages, waits for their readbacks included. The program's phase
`pipeline.prep` (counter in ns over the window) over the pipeline fits of the
window (`pipeline.fit.n`). Nothing where the program counts no such phase."""


def read(run):
    counters = run["counters"]
    fits = counters.get("pipeline.fit.n")
    if not fits or "pipeline.prep.ns" not in counters:
        return None
    return counters["pipeline.prep.ns"] / fits / 1e6
