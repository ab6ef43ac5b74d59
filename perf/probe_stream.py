"""Readings for the limits of a cell of the generator `stream_loop`: many seeds
in one process, on the chip. `perf/probe.py` and `perf/probe_lloyd.py` for an
online learner (those files read a whole fit's model and may not be edited by
the PR that brought this one).

    python perf/probe_stream.py --workload <cell> --seeds 1,2,3 [--faults batch_skipped,...] [--skew uniform]

For each seed it makes the cell's log, drives a short window of the cell's own
traffic, and prints one JSON line with the numbers `correct` compares for (a)
the program, (b) the control, the plain reference in the program's place with
its products' operands in bfloat16, and (c) each fault asked for
(`perf/faults_stream.py`'s, or `perf/faults.py`'s by their names there). Each
is put through the cell's limits, and standard error says for every number
whether it is ok or FAILED: the program has to pass, the control and each fault
to fail.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

PERF = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, PERF)

import run as harness  # noqa: E402

CONTROL = "bfloat16"  # the nearest precision below the float32 the configuration states


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=2.0)
    parser.add_argument("--faults", default="")
    parser.add_argument("--skew", default=None, help="the table maker's skew in place of the configuration's")
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    args = parser.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.named(bench["workloads"], args.workload, "workload")
    sys.path.insert(0, harness.ROOT)
    ctx = harness.Context(bench, cell, 0, args.rehearse_on_cpu)
    harness.devices_or_exit(ctx)
    harness.enable_cache()
    own, shared = ctx.load("", "faults_stream"), ctx.load("", "faults")
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    limits = ctx.traffic["limits"]
    if args.skew:
        ctx.config["data"]["skew"] = args.skew
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        data, maker = ctx.config["data"], ctx.load("tables", ctx.config["data"]["table"])
        line = {"workload": cell["name"], "seed": seed, "batches": len(win["ops"]), "failed": win["failed"]}
        line["skew"] = maker.skew_of(data)
        first = state["log"].arrays["indices"][: int(state["params"]["globalBatchSize"])]
        line["distinct_in_first_batch"], line["entries_a_batch"] = len(np.unique(np.asarray(first))), int(first.size)
        line["window_s"] = win["end"] - win["begin"]
        line["rows_per_s"] = len(win["ops"]) * int(state["params"]["globalBatchSize"]) / line["window_s"]

        def judged(who, numbers):
            """The numbers, and on standard error each against its limit."""
            correct, compared = ctx.compare.verdict(numbers, limits)
            print(f"seed {seed} {who}:", file=sys.stderr)
            ctx.compare.report(compared, correct)
            return dict(numbers, correct=correct)

        def stand_in(fault, precision):
            def make_stage(params):
                if fault in shared.FAULTS:
                    return shared.ReferenceStage(reference, maker, data, params, 1, fault, precision)
                return own.ReferenceStage(reference, maker, data, params, fault, precision)

            learner = generator.StandIn(make_stage, state["log"], reference)
            learner.fold(state["check_version"])
            numbers = generator.compared(ctx, dict(state, learner=learner, held=True))
            return judged(fault or precision, dict(numbers, version_gap=0.0, shed=0.0))

        line["program"] = judged("program", generator.check(ctx, state, win))
        state["learner"] = None  # the program's state leaves the chip before the stand-ins' arrives
        gc.collect()
        line["control_" + CONTROL] = stand_in(None, CONTROL)
        for fault in filter(None, args.faults.split(",")):
            line["fault_" + fault] = stand_in(fault, "float32")
        print(json.dumps(line), flush=True)
        del state, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
