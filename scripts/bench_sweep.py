"""All-config benchmark sweep: run every conf/*.json entry at reference
size, collect per-stage totals/throughputs/phase breakdowns into one JSON.

Each config runs in its own SUBPROCESS with a wall-clock timeout, so one
hung or host-bound stage cannot stall the sweep (the round-3 sweep died
after 3 of 37 configs for exactly that reason). Results are keyed by
(config, entry) — multi-entry configs like benchmark-demo.json keep every
entry. The reference analogue is Benchmark.main over its 36 resource
configs (flink-ml-benchmark/src/main/java/org/apache/flink/ml/benchmark/
Benchmark.java:45-60, BenchmarkUtils.java:74-144).

Usage:
  python scripts/bench_sweep.py [--timeout S] [--out FILE] [--runs N]
  python scripts/bench_sweep.py --one conf/foo.json   (child mode)

Output: benchmarks/SWEEP.json (committed — the per-stage perf evidence);
each entry reports the best of N runs (default 2: run 1 pays XLA compile,
run 2 is steady state; the persistent compile cache usually makes even
run 1 warm).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO, "benchmarks", "SWEEP.json")


def child(config_path: str, runs: int) -> None:
    sys.path.insert(0, REPO)
    from flink_ml_tpu import config as lib_config
    from flink_ml_tpu.benchmark import runner

    lib_config.enable_compilation_cache()
    config = runner.load_config(config_path)
    for name, entry in config.items():
        if name == "version":
            continue
        attempts = []
        error = None
        for _ in range(runs):
            t0 = time.perf_counter()
            try:
                r = runner.run_benchmark(name, entry)
                r["wallS"] = time.perf_counter() - t0
                attempts.append(r)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                error = repr(e)
                break
        if attempts:
            best = min(attempts, key=lambda r: r["totalTimeMs"])
            best["coldWallS"] = attempts[0]["wallS"]
            print("RESULT " + json.dumps({"entry": name, "result": best}), flush=True)
        else:
            print("RESULT " + json.dumps({"entry": name, "error": error}), flush=True)


def main(argv) -> None:
    if "--one" in argv:
        runs = int(argv[argv.index("--runs") + 1]) if "--runs" in argv else 2
        child(argv[argv.index("--one") + 1], runs)
        return
    timeout = float(argv[argv.index("--timeout") + 1]) if "--timeout" in argv else 600.0
    out_path = argv[argv.index("--out") + 1] if "--out" in argv else DEFAULT_OUT
    runs = int(argv[argv.index("--runs") + 1]) if "--runs" in argv else 2
    flag_values = set()
    for flag in ("--out", "--timeout", "--runs", "--one"):
        if flag in argv:
            flag_values.add(argv.index(flag) + 1)
    only = [
        a
        for i, a in enumerate(argv)
        if i not in flag_values and a.endswith(".json") and os.path.exists(a)
    ]
    paths = only or sorted(glob.glob(os.path.join(REPO, "conf", "*.json")))
    results = {}
    for path in paths:
        base = os.path.basename(path)
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", path, "--runs", str(runs)],
                capture_output=True,
                text=True,
                timeout=timeout,
                cwd=REPO,
            )
            wall = time.perf_counter() - t0
            got = False
            for line in proc.stdout.splitlines():
                if not line.startswith("RESULT "):
                    continue
                got = True
                rec = json.loads(line[len("RESULT "):])
                key = f"{base}:{rec['entry']}"
                results[key] = rec
                if "result" in rec:
                    r = rec["result"]
                    print(
                        f"{key:60s} total {r['totalTimeMs']:10.1f}ms"
                        f"  thr {r['inputThroughput']:14.1f} rec/s",
                        flush=True,
                    )
                else:
                    print(f"{key:60s} ERROR {rec['error']}", flush=True)
            if not got:
                tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
                results[f"{base}:?"] = {"error": f"no output (rc={proc.returncode}): {tail}"}
                print(f"{base:60s} NO OUTPUT rc={proc.returncode} {tail}", flush=True)
        except subprocess.TimeoutExpired:
            wall = time.perf_counter() - t0
            results[f"{base}:?"] = {"error": f"timeout after {wall:.0f}s"}
            print(f"{base:60s} TIMEOUT after {wall:.0f}s", flush=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    if only and os.path.exists(out_path):
        # partial (named-config) runs MERGE into the existing sweep file
        # instead of clobbering the other 30+ entries
        try:
            with open(out_path) as f:
                previous = json.load(f).get("entries", {})
            stale_prefixes = {os.path.basename(p) + ":" for p in paths}
            for key, rec in previous.items():
                if not any(key.startswith(pre) for pre in stale_prefixes):
                    results.setdefault(key, rec)
        except (OSError, ValueError):
            pass
    meta = {
        "timeoutS": timeout,
        "runsPerEntry": runs,
        "numEntries": len(results),
        "numErrors": sum(1 for v in results.values() if "error" in v),
    }
    with open(out_path, "w") as f:
        json.dump({"meta": meta, "entries": results}, f, indent=2, sort_keys=True)
    print(f"wrote {out_path}: {meta}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
