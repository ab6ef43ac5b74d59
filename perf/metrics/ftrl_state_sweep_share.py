"""State slots the FTRL step's update is built to write, a batch, as a share
of the model's coordinates: the program's counter `ftrl.slots_updated` (from
shapes: `dim` for a sweep of all coordinates, batch x nnz for the update over
the coordinates the batch holds) over `ftrl.batches` x dim. 100 says every
batch swept the whole state; 0.078 at this cell's shapes says none did.
Nothing where the program counts no FTRL batch. Repeats exactly."""


def read(run):
    counters = run["counters"]
    batches = counters.get("ftrl.batches")
    if not batches:
        return None
    return 100.0 * counters.get("ftrl.slots_updated", 0) / (batches * int(run["config"]["data"]["dim"]))
