"""Readings for the limits of a cell of the generator `naivebayes_fit_loop`:
many seeds in one process, on the chip.

    python perf/probe_naivebayes.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's table, drives a short window of the cell's
own traffic, and prints one JSON line with the numbers `correct` compares for
(a) the program, (b) the control, the plain reference with its theta and pi
rounded to bfloat16, and (c) two faults of the reference's counts: one row
lost from, and one row added to, the category with the most rows below
65,536 (the least a lost or added row moves a theta the limit has to see).
Each is put through the cell's limits, and standard error says for every
number whether it is ok or FAILED: the program has to pass, the control and
each fault to fail.
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

CONTROL = "bfloat16"  # the nearest precision below the float32 the configuration states theta in
FEWER_THAN = 65536


def moved(per_field, step: int):
    """The counts with `step` rows moved on the category (of any field and
    label) that holds the most rows below FEWER_THAN."""
    best = (-1, None)
    for j, counts in enumerate(per_field):
        below = np.where(counts < FEWER_THAN, counts, -1)
        at = np.unravel_index(int(np.argmax(below)), counts.shape)
        if below[at] > best[0]:
            best = (int(below[at]), (j, at))
    j, at = best[1]
    out = [c.copy() for c in per_field]
    out[j][at] += step
    return out, {"field": j, "label": int(at[0]), "index": int(at[1]), "rows": best[0]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--rehearse-on-cpu", action="store_true")
    args = parser.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.named(bench["workloads"], args.workload, "workload")
    sys.path.insert(0, harness.ROOT)
    ctx = harness.Context(bench, cell, 0, args.rehearse_on_cpu)
    harness.devices_or_exit(ctx)
    harness.enable_cache()
    generator = ctx.load("generators", ctx.traffic["generator"])
    reference = ctx.load("reference", cell["config"])
    limits = ctx.traffic["limits"]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        state = generator.setup(ctx)
        win = generator.window(ctx, state, args.seconds)
        program = generator.check(ctx, state, win)
        labels, label_rows, per_field = reference.counts(state["arrays"], state["data"])
        smoothing = float(state["params"].get("smoothing", 1.0))
        want = reference.model(labels, label_rows, per_field, smoothing)
        readings = {"program": program}
        readings["control"] = generator.compared(
            want, reference.model(labels, label_rows, per_field, smoothing, CONTROL)
        )
        where = {}
        for name, step in (("row_lost", -1), ("row_added", 1)):
            counts, where[name] = moved(per_field, step)
            readings[name] = generator.compared(want, reference.model(labels, label_rows, counts, smoothing))
        for name, numbers in readings.items():
            passed, compared = ctx.compare.verdict(numbers, limits)
            wanted = name == "program"
            ok = ok and passed == wanted
            for number, entry in compared.items():
                within = "within" if entry["ok"] else "over"
                print(f"seed {seed} {name} {number} = {entry['value']!r} limit {entry['limit']} {within}", file=sys.stderr)
            verdict = "ok" if passed == wanted else "FAILED"
            print(f"seed {seed} {name}: {'passes' if passed else 'fails'} ({verdict})", file=sys.stderr)
        print(json.dumps({"seed": seed, "fits": len(win["ops"]), "faults_at": where, "readings": readings}), flush=True)
        del state, win
        gc.collect()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
