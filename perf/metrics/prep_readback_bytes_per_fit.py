"""Bytes the host reads back from the device a pipeline fit before its last
estimator's fit: the scaler's mean and deviation, the encoder's two numbers a
column, the guards of the training table's transforms. The program's counter
`pipeline.prep.readback_bytes` over the pipeline fits of the window
(`pipeline.fit.n`). A few hundred bytes; a table-sized number says a column
went to the host. Nothing where the program counts no pipeline fit."""


def read(run):
    counters = run["counters"]
    fits = counters.get("pipeline.fit.n")
    if not fits:
        return None
    return counters.get("pipeline.prep.readback_bytes", 0) / fits
