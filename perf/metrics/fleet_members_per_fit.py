"""Models a fleet fit trains, over the window: the program's counters
`fleet.modelsTrained` over `fleet.fits` (one tick a `FitFleet.fit`, the first
by its members). 100 in the path's cell says the job ran as ONE fleet of the
grid's 100 members; 1 a fit, or nothing, says it was broken into solo fits.
Nothing where the program counts no fleet fit: a window of solo fits, and a
stand-in for the fleet. Repeats exactly."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fleet.fits")
    if not fits:
        return None
    return counters.get("fleet.modelsTrained", 0) / fits
