"""Share of the window's FTRL batches whose state was written at the batch's
coordinates alone, in per cent: the program's counter `ftrl.write.slots`
(one tick a batch whose state `ops/slot_write`'s kernels wrote: the
coefficient's fresh buffer in one pass, and z and n in place where the
batch's distinct coordinates fill no more than a quarter of its entries;
`_slot_writer` takes them for float32 state of a tile or more on one TPU)
over `ftrl.batches`. 100 in the stream
cell; a program that scatters with XLA (the CPU's, and an older program's,
which has no such counter) reads 0. Nothing where the program counts no
FTRL batch. Repeats exactly."""


def read(run):
    counters = run["counters"]
    batches = counters.get("ftrl.batches")
    if not batches:
        return None
    return 100.0 * counters.get("ftrl.write.slots", 0) / batches
