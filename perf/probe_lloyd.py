"""Readings for the limits of a cell of the generator `lloyd_loop`: many seeds
in one process, on the chip. `perf/probe.py` for a model of centroids and
counts (that file reads a linear model's coefficient and may not be edited by
the PR that brought this one).

    python perf/probe_lloyd.py --workload <cell> --seeds 1,2,3 [--faults half_rows,...]

For each seed it makes the cell's table, drives a short window of the cell's
own traffic, and prints one JSON line with the numbers `correct` compares for
(a) the program, (b) the control, the plain reference in the program's place
with its products in bfloat16, and (c) each fault asked for. Each is put
through the cell's limits, and standard error says for every number whether it
is ok or FAILED: the program has to pass, the control and each fault to fail.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

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
    faults = ctx.load("", "faults_lloyd")
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    maker = ctx.load("tables", ctx.config["data"]["table"])
    data, limits = ctx.config["data"], ctx.traffic["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        params = state["params"]
        line = {"workload": cell["name"], "seed": seed, "fits": len(win["ops"]), "failed": win["failed"]}
        line["fit_s"] = [end - start for start, end, _ in win["ops"]]
        want = reference.fit(state["arrays"], data, params)[0]

        def judged(who, models, make_stage):
            """The numbers, and on standard error each against its limit."""
            numbers = generator.compared(ctx, state, reference, want, models, make_stage)
            correct, compared = ctx.compare.verdict(numbers, limits)
            print(f"seed {seed} {who}:", file=sys.stderr)
            ctx.compare.report(compared, correct)
            return dict(numbers, correct=correct)

        def stand_in(fault, precision):
            def make_stage(params):
                return faults.ReferenceStage(reference, maker, data, params, fault, precision)

            return judged(fault or precision, [generator.packed(make_stage(params).fit(state["table"]))], make_stage)

        line["program"] = judged("program", [model for _, model in win["answers"]], ctx.make_stage)
        line["control_" + CONTROL] = stand_in(None, CONTROL)
        for fault in filter(None, args.faults.split(",")):
            line["fault_" + fault] = stand_in(fault, "float32")
        print(json.dumps(line), flush=True)
        del state, win, want
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
