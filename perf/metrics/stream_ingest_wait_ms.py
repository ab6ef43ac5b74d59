"""Host time a global batch the online loop spends waiting for the stager to hand it the batch: the program's phase
`online.ingest` (counter in ns over the window) over the batches of the window
(`online.batch.n`). Host time, not the chip's idle time. Nothing where the
program counts no such phase."""


def read(run):
    counters = run["counters"]
    batches = counters.get("online.batch.n")
    if not batches or "online.ingest.ns" not in counters:
        return None
    return counters["online.ingest.ns"] / batches / 1e6
