"""Share of the sparse entries an epoch trains (columns x rows of its batch)
that go through the gather and the scatter-add, in per cent, over the
window's fits: the program's counters `sparse_epoch.entries_gathered` over
`sparse_epoch.entries`, which a sparse one-shard fit counts where its table
is staged. A fit whose table `ops/sparse_epoch.py` planned gathers the
columns the plan does not hold (12 of the 39 Criteo fields: 30.77; more where
a field's rare categories escape the plan's sample); a fit that was given no
plan gathers every entry: 100. Nothing where no entry was counted: a dense
fit, a Lloyd fit, a fit over laid-out batches, and an older program, which has
no such counters."""


def read(run):
    counters = run["counters"]
    entries = counters.get("sparse_epoch.entries", 0)
    if not entries:
        return None
    return 100.0 * counters.get("sparse_epoch.entries_gathered", 0) / entries
