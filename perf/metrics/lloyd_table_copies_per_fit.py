"""Table-sized device copies a k-means fit makes before its launch (a pad to
the shards, a cast to float32, a re-sharding), a fit: the program's counter
`lloyd.table_copy` over `lloyd.iterations`' fits. 0 says the resident table
was trained in place. Nothing where the program counts no Lloyd iteration: an
older program, or a window of other stages' fits. Repeats exactly."""


def read(run):
    counters = run["counters"]
    attempted = run["window"]["attempted"]
    if not attempted or not counters.get("lloyd.iterations"):
        return None
    return counters.get("lloyd.table_copy", 0) / attempted
