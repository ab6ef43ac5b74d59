"""One run of one cell of BENCHMARK.json.

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (tables on the device from the seed, the compile cache, one warm-up of
the cell's shapes), then the measured window, then the comparison with the
plain reference that decides `correct`. The last line of standard output is
the result. Everything that belongs to one configuration, traffic mix or
metric is a file found by its name (perf/README.md); this file names none.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
CACHE_DIR = os.path.join(PERF, ".cache", "jax")
TRACE_DIR = os.path.join(PERF, ".cache", "trace")
MISS_EVENT = "/jax/compilation_cache/cache_misses"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_module(kind: str, name: str):
    """The module perf/<kind>/<name>.py, found by path: names carry dots and
    dashes, which an import statement cannot."""
    path = os.path.join(PERF, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"perf_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named(entries, name: str, what: str):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


class Context:
    """What a generator and a metric reader may ask of the harness."""

    def __init__(self, bench, cell, seed, rehearse):
        self.bench, self.cell, self.seed, self.rehearse = bench, cell, seed, rehearse
        self.config = load_json(ROOT, named(bench["configs"], cell["config"], "configuration")["file"])
        self.traffic = load_json(PERF, "traffic", cell["traffic"] + ".json")
        if rehearse:
            self.traffic.update(self.traffic.get("rehearsal", {}))
        self.chips = int(cell["chips"])
        self.compare = load_module("", "compare")
        self.work = load_module("", "work")
        self.mesh = None
        self.peak = None

    load = staticmethod(load_module)

    def seed_key(self):
        """A key from any whole number: the low 31 bits, then the rest."""
        import jax

        return jax.random.fold_in(jax.random.PRNGKey(self.seed & 0x7FFFFFFF), self.seed >> 31)

    def make_stage(self, params: dict):
        """The configuration's estimator with `params` set through the
        program's own setters (maxIter -> set_max_iter)."""
        module, _, cls = self.config["stage"]["class"].rpartition(".")
        stage = getattr(importlib.import_module(module), cls)()
        for key, value in params.items():
            setter = "set_" + "".join("_" + c.lower() if c.isupper() else c for c in key)
            getattr(stage, setter)(value)
        return stage

    def least_per_unit(self):
        """The least time the cell's chips could take for one unit of the
        window's work (a trained row): the configuration's counter gives an
        epoch's bytes and FLOPs, an epoch trains globalBatchSize rows. None
        off the chip, where there is no peak to hold it against."""
        if self.peak is None:
            return None
        params = self.config["stage"]["params"]
        epoch = getattr(self.work, self.config["work"])(self.config["data"], params)
        rows = int(params["globalBatchSize"])
        unit = {name: amount / rows for name, amount in epoch.items()}
        return self.work.least_seconds(unit, self.peak, self.chips)


def devices_or_exit(ctx):
    """The chips the cell asks for, or no result: another platform or count
    is fatal unless the run is an explicit rehearsal."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    peaks = load_json(PERF, "peaks.json")
    if ctx.rehearse:
        devices = devices[: ctx.chips]
        ctx.peak = None
        # off the chip jax may offer more devices than the cell has chips:
        # hold the program's default mesh to the cell's (on the chip the
        # counts are equal, and the default is left alone)
        from flink_ml_tpu.parallel import mesh as program_mesh

        program_mesh.set_default_mesh(program_mesh.create_mesh(devices=devices))
    else:
        if devices[0].platform != "tpu" or len(devices) != ctx.chips:
            print(
                f"perf/run.py: the cell needs {ctx.chips} TPU chip(s); jax reports "
                f"{len(devices)} x {devices[0].platform!r}",
                file=sys.stderr,
            )
            sys.exit(2)
        kind = devices[0].device_kind
        if kind not in peaks:
            print(f"perf/run.py: no peaks for device_kind {kind!r} in perf/peaks.json", file=sys.stderr)
            sys.exit(2)
        ctx.peak = peaks[kind]
    ctx.mesh = Mesh(np.array(devices), ("data",))
    return devices


def enable_cache():
    """JAX's persistent compile cache: where the environment says, else at a
    fixed path inside the checkout (the path is part of the cache's key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def wanted_metrics(bench, cell_name: str, traced: bool):
    """The cell's metrics for this kind of run: end to end without the
    trace, per layer with it; one with a `workloads` key only in those."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="run the traffic file's `rehearsal` sizes on whatever jax finds; "
        "the result names that platform and is no measurement",
    )
    args = parser.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = named(bench["workloads"], args.workload, "workload")
    ctx = Context(bench, cell, args.seed, args.rehearse_on_cpu)
    sys.path.insert(0, ROOT)  # the system under test is imported from this checkout

    import jax

    asked = time.perf_counter()
    devices = devices_or_exit(ctx)
    devices_s = time.perf_counter() - asked
    enable_cache()
    cache = {"misses": 0, "hits": 0}

    def on_event(event: str, **_):
        if event == MISS_EVENT:
            cache["misses"] += 1
        elif event == HIT_EVENT:
            cache["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    from flink_ml_tpu.utils import metrics as program_counters

    generator = ctx.load("generators", ctx.traffic["generator"])
    state = generator.setup(ctx)
    setup_cache = dict(cache)
    traced = bool(args.trace)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    counters_before = program_counters.snapshot()
    setup_s = time.perf_counter() - PROCESS_START

    seconds = args.seconds
    if traced:  # a trace of a few seconds holds hundreds of thousands of events
        seconds = min(seconds, float(ctx.traffic.get("trace_seconds", seconds)))
    win = generator.window(ctx, state, seconds)

    counters = program_counters.snapshot_delta(counters_before, program_counters.snapshot())["counters"]
    trace = None
    if traced:
        jax.profiler.stop_trace()
    peak_bytes = memory_peak(devices)
    if traced:
        tracereduce = ctx.load("", "tracereduce")
        trace = tracereduce.reduce(tracereduce.load_newest(TRACE_DIR), win["span"], not ctx.rehearse)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    numbers = generator.check(ctx, state, win)
    correct, compared = ctx.compare.verdict(numbers, ctx.traffic["limits"])
    correct = correct and win["failed"] == 0

    run = {
        "window": win,
        "setup_s": setup_s,
        "devices_s": devices_s,
        "counters": counters,
        "setup_cache": setup_cache,
        "trace": trace,
        "least_per_unit": ctx.least_per_unit(),
        "config": ctx.config,
    }
    reported = {}
    for metric in wanted_metrics(bench, cell["name"], traced):
        value = ctx.load("metrics", metric["name"]).read(run)
        if value is not None:
            reported[metric["name"]] = {"value": value, "unit": metric["unit"]}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result = {
        "correct": bool(correct),
        "attempted": win["attempted"],
        "failed": win["failed"],
        "metrics": reported,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    result["run"] = {
        "workload": cell["name"], "seed": args.seed, "seconds": seconds,
        "rehearsal": ctx.rehearse,
    }
    compared["failed"] = {"value": win["failed"], "limit": 0, "ok": win["failed"] == 0}
    result["compared"] = compared
    ctx.compare.report(compared, result["correct"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
