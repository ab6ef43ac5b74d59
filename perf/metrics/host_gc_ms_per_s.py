"""Time the process spent in Python's cycle collector, in ms a second of the
window: the program's counter `host.gc.ns` (its `gc.callbacks` entry, every
generation) over the window's length on the host's clock. A full collection
(generation 2) stops every thread for tens of milliseconds over a large heap;
`host.gc.full.n` counts those. A window without a collection reads 0. Nothing
where the program times no collection: told by the outermost fit or the
funnelled fence that the same program counts, since a counter that did not
move is not in the window's counters."""

SAME_PROGRAM = ("host.gc.ns", "fit.outer.n", "sync.fence.n")


def read(run):
    counters = run["counters"]
    seconds = run["window"]["end"] - run["window"]["begin"]
    if seconds <= 0 or not any(counters.get(name) for name in SAME_PROGRAM):
        return None
    return counters.get("host.gc.ns", 0) / 1e6 / seconds
