"""Host time a global batch the online loop spends at its fence, waiting until
the state of two batches ago is whole on the device (no readback): the
program's phase `online.fence` (counter in ns over the window) over the
batches of the window (`online.batch.n`). Where the device binds, it is where
the host spends a batch. Nothing where the program counts no such phase."""


def read(run):
    counters = run["counters"]
    batches = counters.get("online.batch.n")
    if not batches or "online.fence.ns" not in counters:
        return None
    return counters["online.fence.ns"] / batches / 1e6
