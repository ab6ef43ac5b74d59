"""The least time the chip could take for the feature stages of the traced
window's pipeline fits (perf/counters/pipeline_prep.py: the raw feature
columns read once and the assembled rows written once, over the HBM peak of
perf/peaks.json; the generator reckons it a fit where it knows the peak) as a
share of the device time of the configuration's `prep_programs` in the trace:
the scaler's and the encoder's fits and the programs that take the training
table through the fitted stages, hand-written kernels among them where there
are any. Nothing off the chip, for a configuration without such programs, or
where the trace holds none of them (an older program)."""


def read(run):
    trace, win = run["trace"], run["window"]
    names = run["config"].get("prep_programs")
    least = win.get("prep_least_s_a_fit")
    if trace is None or not names or least is None:
        return None
    device_s = sum(trace["modules_s"].get(name, 0.0) for name in names)
    if device_s <= 0:
        return None
    return trace["spans"] * least / device_s * 100.0
