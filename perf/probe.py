"""Readings for a cell's limits: many seeds in one process, on the chip.

    python perf/probe.py --workload <cell> --seeds 1,2,3 --seconds 1 [--faults half_batch,...]

For each seed it makes the cell's tables, drives a short window of the cell's
own traffic, and prints one JSON line with (a) the numbers `correct` compares
for the program, (b) the same numbers for the control, the plain reference in
the program's place at the next lower precision, and (c) for each fault asked
for, the reference with that fault planted. Each of the three is also put
through the comparison against the cell's limits, and standard error says for
every number whether it is ok or FAILED: the program has to pass, the control
and each fault to fail. PERF.md's limits are set from these lines; the
benchmark's own runs never load this file.
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

CONTROL = "bfloat16"  # the nearest precision below the float32 both configurations state
TABLES = 2  # the control and the faults are read on a cell's first two tables


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
    faults = ctx.load("", "faults")
    compare = ctx.compare
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    maker = ctx.load("tables", ctx.config["data"]["table"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        line = {"workload": cell["name"], "seed": seed, "fits": len(win["ops"]), "failed": win["failed"]}
        limits = ctx.traffic["limits"]

        def judged(who, numbers):
            """The numbers, and on standard error each against its limit."""
            correct, compared = compare.verdict(numbers, limits)
            print(f"seed {seed} {who}:", file=sys.stderr)
            compare.report(compared, correct)
            return dict(numbers, correct=correct)

        line["program"] = judged("program", generator.check(ctx, state, win))
        tables = list(range(min(TABLES, len(state["arrays"]))))
        refs = {i: reference.fit(state["arrays"][i], ctx.config["data"], state["params"])[0] for i in tables}

        def read(fault, precision):
            stage = faults.ReferenceStage(
                reference, maker, ctx.config["data"], state["params"], ctx.chips, fault, precision
            )
            answers = [(i, stage.fit(state["tables"][i]).coefficient) for i in tables]
            return judged(
                fault or precision,
                {
                    "coef_gap": min(compare.coefficient_gap(a, refs[i]) for i, a in answers),
                    "coef_max_gap": min(compare.largest_miss(a, refs[i]) for i, a in answers),
                },
            )

        line["control_" + CONTROL] = read(None, CONTROL)
        for fault in filter(None, args.faults.split(",")):
            line["fault_" + fault] = read(fault, "float32")
        print(json.dumps(line), flush=True)
        del state, win, refs
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
