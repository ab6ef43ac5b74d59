"""Readings for the limits of a sparse fleet cell (`lr-regpath-criteo-1m.*`):
many seeds in one process, on the chip. `perf/probe_fleet.py` for a table of
hashed rows, with `perf/faults_fleet_sparse.py`'s faults (that probe loads
the dense path's faults and may not be edited by the PR that brought this
one).

    python perf/probe_fleet_sparse.py --workload <cell> --seeds 1,2,3 \
        [--faults members_reversed,final_update_left_out,reg_left_out,dictionary_ids_shifted]

For each seed it makes the cell's table, drives a short window of the cell's
own traffic, and prints one JSON line with the numbers `correct` compares for
(a) the program, (b) the control, the plain reference in `FitFleet`'s place
with its products in bfloat16, and (c) each fault asked for, and the seconds
each reference took. Each is put through the cell's limits, and standard
error says for every number whether it is ok or FAILED: the program has to
pass, the control and each fault to fail.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

PERF = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, PERF)

import run as harness  # noqa: E402

CONTROL = "bfloat16"  # the nearest precision below the float32 the configuration states


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--faults", default="")
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    args = parser.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.named(bench["workloads"], args.workload, "workload")
    sys.path.insert(0, harness.ROOT)
    ctx = harness.Context(bench, cell, 0, args.rehearse_on_cpu)
    harness.devices_or_exit(ctx)
    harness.enable_cache()
    import numpy as np

    import flink_ml_tpu.fleet as program

    faults = ctx.load("", "faults_fleet_sparse")
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    maker = ctx.load("tables", ctx.config["data"]["table"])
    data, limits = ctx.config["data"], ctx.traffic["limits"]
    the_programs = program.FitFleet
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        line = {"workload": cell["name"], "seed": seed, "fits": len(win["ops"]), "failed": win["failed"]}
        line["fit_s"] = [end - start for start, end, _ in win["ops"]]
        began = time.perf_counter()
        want = np.asarray(reference.fit(state["arrays"], data, state["params"])[0])
        line["reference_s"] = time.perf_counter() - began

        def judged(who, fits):
            """The numbers, and on standard error each against its limit."""
            numbers = generator.compared(ctx, want, fits)
            correct, compared = ctx.compare.verdict(numbers, limits)
            print(f"seed {seed} {who}:", file=sys.stderr)
            ctx.compare.report(compared, correct)
            return dict(numbers, correct=correct)

        def stand_in(fault, precision):
            program.FitFleet = faults.planted(reference, maker, data, fault, precision)
            try:
                return judged(fault or precision, [generator.fit_path(ctx, state)])
            finally:
                program.FitFleet = the_programs

        line["program"] = judged("program", [np.stack(members) for _, members in win["answers"]])
        line["control_" + CONTROL] = stand_in(None, CONTROL)
        for fault in filter(None, args.faults.split(",")):
            line["fault_" + fault] = stand_in(fault, "float32")
        print(json.dumps(line), flush=True)
        del state, win, want
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
