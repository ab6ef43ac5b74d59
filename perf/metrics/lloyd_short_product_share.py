"""Share of the window's k-means fits whose cross term multiplies only the
pieces its points have, in per cent: the program's counters
`lloyd.product.short` and `lloyd.product.full` tick once a fit beside
`lloyd.iterations`, the first where the fit's look at ALL rows of its staged
table found every value exact in bfloat16 (pixel bytes: one piece, three
bfloat16 passes against the centroids' three pieces), the second where it
found one that is not, or nothing was looked at (a table on no TPU, the
overlap schedule): the six passes of `lax.Precision.HIGHEST`. The float32
product is the same either way. Nothing where neither ticked: a window of
other stages' fits, and an older program, which counts neither."""


def read(run):
    counters = run["counters"]
    short = counters.get("lloyd.product.short", 0)
    fits = short + counters.get("lloyd.product.full", 0)
    if not fits:
        return None
    return 100.0 * short / fits
