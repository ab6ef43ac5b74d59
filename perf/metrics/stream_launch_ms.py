"""Host time a global batch the online loop spends in the step, dispatching the batch's device program (it does not wait for it): the program's phase
`online.launch` (counter in ns over the window) over the batches of the window
(`online.batch.n`). Host time, not the chip's idle time. Nothing where the
program counts no such phase."""


def read(run):
    counters = run["counters"]
    batches = counters.get("online.batch.n")
    if not batches or "online.launch.ns" not in counters:
        return None
    return counters["online.launch.ns"] / batches / 1e6
