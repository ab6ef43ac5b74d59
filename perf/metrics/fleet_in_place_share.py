"""Share of the window's fleet fits that copied no table, in per cent: the
program's counter `fleet.in_place` (one tick a fleet fit whose programs read
the caller's table where it lies, `optimizer._can_train_in_place`: one device,
a dense device table of the engine's dtype in whole batches) over
`fleet.fits`. 100 in the path's cell; a fit that laid its table out first
ticks `layout.general` or `layout.exchange` instead, and at the cell's size
has no room to. Nothing where the program counts no fleet fit. An older
program, which lays every fleet's table out, reads 0. Repeats exactly."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fleet.fits")
    if not fits:
        return None
    return 100.0 * counters.get("fleet.in_place", 0) / fits
