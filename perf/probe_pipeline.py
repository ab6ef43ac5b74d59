"""Readings for the pipeline cell's limits: many seeds in one process, on the chip.

    python perf/probe_pipeline.py --workload <cell> --seeds 1,2,3 [--partitions 2] [--seconds 1]

For each seed it makes the cell's partitions (`--partitions` of them: the
readings need no more than a fit or two), drives a short window of the cell's
own traffic, and prints one JSON line with (a) the numbers `correct` compares
for the program and (b) the same numbers for the control: the plain reference
in the program's place at the next lower precision, everything it fits (the
scaler's moments, the sizes, the coefficient) held against the float32
reference of the same partition. Each is put through the cell's limits, and
standard error says for every number whether it is ok or FAILED: the program
has to pass, the control to fail. PERF.md's limits are set from these lines;
the benchmark's own runs never load this file. `perf/probe.py` reads a
coefficient alone and cannot take this cell.
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
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--partitions", type=int, default=2)
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    args = parser.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.named(bench["workloads"], args.workload, "workload")
    sys.path.insert(0, harness.ROOT)
    ctx = harness.Context(bench, cell, 0, args.rehearse_on_cpu)
    ctx.traffic["partitions"] = min(args.partitions, int(ctx.traffic["partitions"]))
    harness.devices_or_exit(ctx)
    harness.enable_cache()
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    limits = ctx.traffic["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        line = {"workload": cell["name"], "seed": seed, "fits": len(win["ops"]), "failed": win["failed"]}

        def judged(who, numbers):
            """The numbers, and on standard error each against its limit."""
            correct, compared = ctx.compare.verdict(numbers, limits)
            print(f"seed {seed} {who}:", file=sys.stderr)
            ctx.compare.report(compared, correct)
            return dict(numbers, correct=correct)

        line["program"] = judged("program", generator.check(ctx, state, win))
        arrays, data, params = state["arrays"][0], state["data"], state["params"]
        coeff, _, _, stats = reference.fit(arrays, data, params)
        low, _, _, low_stats = reference.fit(arrays, data, params, precision=CONTROL)
        control = {name: np.asarray(low_stats[name]) for name in ("mean", "std")}
        control.update(sizes=low_stats["sizes"], coefficient=low)
        line["control_" + CONTROL] = judged(CONTROL, generator.gaps(control, coeff, stats))
        print(json.dumps(line), flush=True)
        del state, win, coeff, low
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
