"""Share of the window's NaiveBayes fields counted on the device, in per cent:
the program's counters `naivebayes.fields.indexed` (a field counted by the
scatter-add) and `naivebayes.fields.compare` (by compares) over those and
`naivebayes.fields.host` (a field of a fit that took the host route), one tick
a field a fit. 100 in the week-refit cell, where every field is an index.
Nothing where the program counts no NaiveBayes field: an older program, which
has no such counter, reads nothing. Repeats exactly."""


def read(run):
    counters = run["counters"]
    device = counters.get("naivebayes.fields.indexed", 0) + counters.get("naivebayes.fields.compare", 0)
    fields = device + counters.get("naivebayes.fields.host", 0)
    if not fields:
        return None
    return 100.0 * device / fields
