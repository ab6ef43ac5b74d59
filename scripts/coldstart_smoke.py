#!/usr/bin/env python
"""Cold-start smoke for the AOT program bank (docs/performance.md §12).

One deterministic serving workload (a StandardScaler → Normalizer fused
pipeline, seed-pinned model constants and example batch) run in three
modes by a FRESH process each time:

- ``populate`` — warm the bank: ``MicroBatchServer.warmup`` drives every
  (bucket) program through the lazyjit/compilebank funnels, AOT-compiling
  and back-filling ``<bankdir>``.
- ``serve`` — the no-compile SLA probe: warm-load the bank at process
  start, serve the FIRST request, and assert in-process that the
  dispatch performed ZERO kernel traces and ZERO XLA backend compiles
  (`jit.traces` / `jit.compiles` deltas both zero). Exit 1 otherwise —
  this is the CI gate.
- ``baseline`` — the same fresh-process first serve with the bank off
  (every program traces + compiles), for the bank-on vs bank-off
  cold-start walls the ``bench`` mode prints.

- ``bench`` — the three above side by side: this process stays OFF jax (a
  chip belongs to one process at a time, so a parent that had touched
  jax would starve its children) and runs ``populate``, ``serve`` and
  ``baseline`` as three children, one at a time, against a temporary
  bank directory. It asserts the banked serve's zero traces / zero
  compiles / bank hits and that its output sha256 equals the freshly
  compiled baseline's, then prints the cold-start walls side by side.

Prints one JSON object on stdout (the bench mode and the CI step both
parse it): coldStartMs (process start → first result), firstServeMs,
serveTraceCount, serveCompileCount, bankHits/bankMisses/bankLoads,
bankLoadMs, and a sha256 of the output column for cross-process
bit-identity checks.
"""

import hashlib
import json
import os
import sys
import time

_T0 = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D = 16
BUCKETS = (8, 32)
ROWS = 8  # == smallest bucket: the padded batch IS the request batch


def build_workload():
    """The deterministic (seed-pinned) serving pipeline + example batch:
    populate and serve children MUST build identical programs or the
    bank signatures would never match across processes."""
    import numpy as np

    from flink_ml_tpu.models.feature.normalizer import Normalizer
    from flink_ml_tpu.models.feature.standardscaler import StandardScalerModel
    from flink_ml_tpu.pipeline import PipelineModel
    from flink_ml_tpu.table import Table

    rng = np.random.default_rng(7)
    scaler = StandardScalerModel()
    scaler.mean = rng.standard_normal(D)
    scaler.std = np.abs(rng.standard_normal(D)) + 0.1
    scaler.set_input_col("features").set_output_col("scaled")
    norm = Normalizer().set_p(2.0).set_input_col("scaled").set_output_col("norm")
    model = PipelineModel([scaler, norm])
    example = Table(
        {"features": rng.standard_normal((ROWS, D)).astype(np.float32)}
    )
    return model, example


def run_bench():
    """Parent of the three cold-start children; imports no jax."""
    import shutil
    import subprocess
    import tempfile

    # the bank is keyed by program signature, not by path, so a
    # temporary directory is found again by the serve child all the same
    bank_dir = tempfile.mkdtemp(prefix="aot-bank.")

    def child(mode):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), bank_dir, mode],
            capture_output=True,
            text=True,
            timeout=600,
        )
        wall_ms = (time.perf_counter() - t0) * 1000.0
        if proc.returncode != 0:
            tail = "; ".join(proc.stderr.strip().splitlines()[-3:])
            raise RuntimeError(f"{mode} child: exit {proc.returncode}: {tail}")
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        out["processWallMs"] = wall_ms
        return out

    try:
        populate = child("populate")
        serve = child("serve")  # exits 1 itself on a trace, a compile or no bank hit
        baseline = child("baseline")
    finally:
        shutil.rmtree(bank_dir, ignore_errors=True)
    if serve["outSha"] != baseline["outSha"]:
        raise RuntimeError(
            "bank-loaded executable output diverged from the freshly-compiled "
            f"baseline: {serve['outSha']} != {baseline['outSha']}"
        )
    print(
        json.dumps(
            {
                "mode": "bench",
                "device": serve["device"],
                "coldStartMs": serve["coldStartMs"],
                "baselineColdStartMs": baseline["coldStartMs"],
                "firstServeMs": serve["firstServeMs"],
                "baselineFirstServeMs": baseline["firstServeMs"],
                "populateMs": populate["warmupMs"],
                "bankLoadMs": serve["bankLoadMs"],
                "bankLoads": serve["bankLoads"],
                "bankHits": serve["bankHits"],
                "bankMisses": serve["bankMisses"],
                "serveTraceCount": serve["serveTraceCount"],
                "serveCompileCount": serve["serveCompileCount"],
                "baselineServeTraceCount": baseline["serveTraceCount"],
                "baselineServeCompileCount": baseline["serveCompileCount"],
                "processWallMs": {
                    "populate": populate["processWallMs"],
                    "serve": serve["processWallMs"],
                    "baseline": baseline["processWallMs"],
                },
                "bitIdentical": True,
            }
        )
    )
    return 0


def main(argv):
    if len(argv) == 2 and argv[1] == "bench":
        return run_bench()
    if len(argv) != 3 or argv[2] not in ("populate", "serve", "baseline"):
        print(
            f"usage: {argv[0]} <bankdir> populate|serve|baseline\n"
            f"       {argv[0]} bench",
            file=sys.stderr,
        )
        return 2
    bank_dir, mode = argv[1], argv[2]

    from flink_ml_tpu import config
    from flink_ml_tpu.obs import tracing
    from flink_ml_tpu.serving import MicroBatchServer
    from flink_ml_tpu.utils import metrics

    # install the backend-compile monitoring hooks BEFORE anything can
    # compile: a bank hit must register zero compile events, and without
    # the hooks the serveCompileCount==0 assert would be vacuous
    tracing.install_jax_hooks()

    if mode != "baseline":
        # the bank alone, so bank-on vs bank-off differ in one thing. (The
        # persistent XLA cache stays off here for a second reason: on the
        # CPU backend of jaxlib 0.9.0 an executable loaded from a WARM
        # persistent cache cannot be re-serialized into the bank. The two
        # tiers together are pinned by tests/test_compilebank.py from a
        # cold cache, and on the TPU by chip_smoke.py's serve phase.)
        config.program_bank_dir = bank_dir

    import numpy as np

    model, example = build_workload()
    server = MicroBatchServer(model, buckets=BUCKETS)

    if mode == "populate":
        info = server.warmup(example)
        print(json.dumps({"mode": mode, **info}))
        return 0

    before = metrics.snapshot()
    t0 = time.perf_counter()
    out = list(server.serve(iter([example])))[0]
    first_serve_ms = (time.perf_counter() - t0) * 1000.0
    cold_start_ms = (time.perf_counter() - _T0) * 1000.0
    delta = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    snap = metrics.snapshot()["counters"]
    digest = hashlib.sha256(
        np.ascontiguousarray(
            np.asarray(out.column("norm"), dtype=np.float32)
        ).tobytes()
    ).hexdigest()
    import jax

    devices = jax.devices()
    payload = {
        "mode": mode,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "coldStartMs": cold_start_ms,
        "firstServeMs": first_serve_ms,
        "serveTraceCount": float(delta.get("jit.traces", 0)),
        "serveCompileCount": float(delta.get("jit.compiles", 0)),
        "bankHits": float(snap.get("bank.hits", 0)),
        "bankMisses": float(snap.get("bank.misses", 0)),
        "bankLoads": float(snap.get("jit.bankLoads", 0)),
        "bankLoadMs": metrics.snapshot()["timers"]
        .get("bank.load", {})
        .get("totalMs", 0.0),
        "outSha": digest,
    }
    print(json.dumps(payload))
    if mode == "serve":
        if payload["serveTraceCount"] != 0 or payload["serveCompileCount"] != 0:
            print(
                "cold-start SLA violated: first serve traced or compiled "
                f"(traces={payload['serveTraceCount']}, "
                f"compiles={payload['serveCompileCount']})",
                file=sys.stderr,
            )
            return 1
        if payload["bankHits"] == 0 or payload["bankLoads"] == 0:
            print("bank never hit — warmup did not populate?", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
