"""Share of the window's batch layouts that took the explicit exchange, in
per cent: the program's counters `layout.exchange` and `layout.general` tick
once a laid-out array (X, y, a weight column, each sparse leaf), whichever
form `SGD._lay_out` picked for it. The exchange is for tables: a 1-D column
keeps the general form, so a dense fit whose table took the exchange reads 50
(X of X and y), a sparse one 66.7 (both leaves of three arrays), and 0 says
that the table did not. Nothing where neither ticked: a fit on one data shard
lays nothing out, and an older program counts neither."""


def read(run):
    counters = run["counters"]
    exchanged = counters.get("layout.exchange", 0)
    laid_out = exchanged + counters.get("layout.general", 0)
    if not laid_out:
        return None
    return 100.0 * exchanged / laid_out
