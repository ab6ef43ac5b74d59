"""Host time a global batch the online loop spends publishing the batch's version (a record swap; a readback here would show): the program's phase
`online.publish` (counter in ns over the window) over the batches of the window
(`online.batch.n`). Host time, not the chip's idle time. Nothing where the
program counts no such phase."""


def read(run):
    counters = run["counters"]
    batches = counters.get("online.batch.n")
    if not batches or "online.publish.ns" not in counters:
        return None
    return counters["online.publish.ns"] / batches / 1e6
