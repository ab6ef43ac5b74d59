"""The least time the chips could take for the rows trained in the traced
window (perf/work.py's bytes and FLOPs from the cell's shapes, over
perf/peaks.json) as a share of the device time of the training program's
executions. The configuration names that program under `train_programs`. A
chip trace that holds none of them is an error, not a silence: the program was
renamed, and the configuration has to follow it."""


def read(run):
    trace, least = run["trace"], run["least_per_unit"]
    if trace is None or least is None:
        return None
    names = run["config"]["train_programs"]
    device_s = sum(trace["modules_s"].get(name, 0.0) for name in names)
    if device_s <= 0:
        raise RuntimeError(
            f"epoch_roofline: the trace holds none of the configuration's train_programs {names}; "
            f"it holds {sorted(trace['modules_s'])}"
        )
    return sum(run["window"]["units"]) * least["seconds"] / device_s * 100.0
