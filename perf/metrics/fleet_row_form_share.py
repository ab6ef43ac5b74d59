"""Share of the window's fleet fits whose epochs took the member-row form, in
per cent: the program's counter `fleet.product.rows` (one tick a fleet fit
whose members' coefficients are held [dim, N] and whose entries are gathered
and segment-summed as N-wide rows, `optimizer._fleet_rows`: a padded-CSR
float32 table on a TPU, on the whole-fit route of one fleet) over
`fleet.fits`. 100 in the sparse path's cell; a fleet fit that took the reduce
form ticks `fleet.product.reduce` instead (the CPU's, and an older program's,
which has no such counter and reads 0). Nothing where the program counts no
fleet fit. Repeats exactly."""


def read(run):
    counters = run["counters"]
    fits = counters.get("fleet.fits")
    if not fits:
        return None
    return 100.0 * counters.get("fleet.product.rows", 0) / fits
