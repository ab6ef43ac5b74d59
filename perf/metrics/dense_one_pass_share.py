"""Share of the window's dense fits whose epochs read their batch once, in per
cent: the program's counters `dense_epoch.one_pass` and `dense_epoch.reduce`
tick once a dense fit where its data is staged, the first where
`optimizer._can_one_pass` admits the table to the one-read kernel
(`ops/dense_epoch.py`: one data shard, a narrow float32 table the TPU keeps
rows-minor), the second where the fit keeps the two reductions of
`dense_dot` / `dense_grad` (every fit over laid-out batches, so every fit on
four chips: 0 there). Nothing where neither ticked: a sparse fit, a Lloyd
fit, and an older program count neither."""


def read(run):
    counters = run["counters"]
    one_pass = counters.get("dense_epoch.one_pass", 0)
    fits = one_pass + counters.get("dense_epoch.reduce", 0)
    if not fits:
        return None
    return 100.0 * one_pass / fits
