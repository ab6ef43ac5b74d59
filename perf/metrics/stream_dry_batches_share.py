"""Share of the window's global batches whose fence found its state ready
before it asked: the device had run out of queued work, so a host pause
outlasted the batches in flight. The program's counter `online.fence.dry` over
`online.batch.n`, in percent; 0 in a steady window. Nothing where the program
does not pass its fence through the funnel that counts it (`sync.fence.n`: an
older program never says dry)."""


def read(run):
    counters = run["counters"]
    batches = counters.get("online.batch.n")
    if not batches or not counters.get("sync.fence.n"):
        return None
    return 100.0 * counters.get("online.fence.dry", 0) / batches
