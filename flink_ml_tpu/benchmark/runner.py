"""Benchmark runner + CLI — JSON-config-driven stage benchmarking.

TPU-native re-design of flink-ml-benchmark (Benchmark.java:45-60,
BenchmarkUtils.java:74-144, BenchmarkResult.java). Config format is the
reference's: a JSON object of named entries, each with a `stage`
{className, paramMap} and an `inputData` generator spec (and optional
`modelData`). Java class names resolve to this framework's classes through
the persistence alias map, so the reference's 36 shipped configs run
unchanged. Results use the reference's schema (totalTimeMs,
inputRecordNum, inputThroughput, outputRecordNum, outputThroughput) plus
one TPU-port extension: phaseTimesMs, the per-phase wall-clock breakdown
(datagen/fit/transform/collect).

CLI: python -m flink_ml_tpu.benchmark <config.json> [--output-file r.json]
     [--profile-dir traces/]   (jax.profiler device trace for TensorBoard)
"""

from __future__ import annotations

import json
import re
import sys
import time
from typing import Dict, List, Optional

from ..api import AlgoOperator, Estimator, Model
from ..obs import memledger, tracing
from ..table import Table
from ..utils import metrics, read_write

_BENCH_JAVA_PREFIX = "org.apache.flink.ml.benchmark.datagenerator."
_BENCH_PY_MODULE = "flink_ml_tpu.benchmark.datagenerator"


def _resolve_generator(class_name: str):
    import importlib

    if class_name.startswith(_BENCH_JAVA_PREFIX):
        simple = class_name.rsplit(".", 1)[1]
        module = importlib.import_module(_BENCH_PY_MODULE)
        return getattr(module, simple)
    module_name, _, cls_name = class_name.rpartition(".")
    return getattr(importlib.import_module(module_name), cls_name)


def instantiate_generator(spec: Dict):
    cls = _resolve_generator(spec["className"])
    gen = cls()
    for name, json_value in spec.get("paramMap", {}).items():
        param = gen.get_param(name)
        if param is not None:
            gen.set(param, param.json_decode(json_value))
    return gen


def load_config(path: str) -> Dict:
    """Reads a benchmark config; tolerates the reference's // license
    header comments."""
    with open(path) as f:
        text = f.read()
    text = re.sub(r"^\s*//.*$", "", text, flags=re.M)
    return json.loads(text)


def run_benchmark(name: str, entry: Dict) -> Dict:
    """BenchmarkUtils.runBenchmark: generate input, fit/transform the stage,
    time end to end, report throughput — plus a per-phase wall-clock
    breakdown (datagen/fit/transform/collect) the reference's netRuntime
    can't show (the tool that catches host-bound ingestion regressions).

    The result also embeds `metrics` — the registry delta this entry
    produced (per-phase span timers, readback bytes/count, jit compile
    count, collective/datacache counters), so an emitted BENCH json
    carries its own evidence for perf claims."""
    from contextlib import contextmanager

    from ..obs import timeline

    tracing.install_jax_hooks()
    metrics_before = metrics.snapshot()
    timeline_start_us = timeline.now_us()
    hbm_mark = memledger.mark_peak()
    phases: Dict[str, float] = {}

    @contextmanager
    def timed_phase(phase: str):
        start = time.perf_counter()
        with tracing.span("benchmark.phase", benchmark=name, phase=phase):
            try:
                yield
            finally:
                elapsed = time.perf_counter() - start
                phases[phase] = phases.get(phase, 0.0) + elapsed
                metrics.record_time(f"benchmark.{name}.{phase}", elapsed)

    with timed_phase("datagen"):
        stage = read_write.instantiate_with_params(entry["stage"])
        from . import datagenerator as dg

        # stages that declare host-resident compute (Stage.prefers_host_input)
        # get host-born inputs — see set_prefer_host
        dg.set_prefer_host(bool(getattr(stage, "prefers_host_input", False)))
        try:
            input_tables = instantiate_generator(entry["inputData"]).get_data()
        finally:
            dg.set_prefer_host(False)
        _adapt_input_columns(stage, input_tables)
        model_tables: Optional[List[Table]] = None
        if "modelData" in entry:
            model_tables = instantiate_generator(entry["modelData"]).get_data()
            _block_until_ready(model_tables)
        _block_until_ready(input_tables)

    num_input = sum(t.num_rows for t in input_tables)
    start = time.perf_counter()
    # each phase blocks on its own device work so async dispatch can't leak
    # a phase's compute into the next one's timing
    if isinstance(stage, Estimator):
        with timed_phase("fit"):
            model = stage.fit(*input_tables)
        with timed_phase("transform"):
            outputs = model.transform(*input_tables)
            _block_until_ready(outputs)
    elif isinstance(stage, Model) and model_tables is not None:
        with timed_phase("fit"):
            stage.set_model_data(*model_tables)
        with timed_phase("transform"):
            outputs = stage.transform(*input_tables)
            _block_until_ready(outputs)
    elif isinstance(stage, AlgoOperator):
        with timed_phase("transform"):
            outputs = stage.transform(*input_tables)
            _block_until_ready(outputs)
    else:
        raise TypeError(f"Unsupported stage type {type(stage).__name__}")
    with timed_phase("collect"):
        num_output = sum(t.num_rows for t in outputs)
    elapsed_ms = (time.perf_counter() - start) * 1000.0

    delta = metrics.snapshot_delta(metrics_before, metrics.snapshot())
    # dispatch-wall attribution (obs/timeline.py): the work phases' wall
    # split into host-dispatch time (the `fit.launch` phase of the dispatch
    # funnel — every chunk/fused-program launch rides it) and the GAP the
    # host was not dispatching: device execution + readback + idle latency.
    # `dispatchGapMs ~ wallMs - hostDispatchMs` is THE item-2 progress
    # metric: the resident-program work must grow hostDispatch's share of
    # a shrinking wall. gapCount = dispatch->drain cycles (one per chunk).
    work_ms = (phases.get("fit", 0.0) + phases.get("transform", 0.0)) * 1000.0
    host_dispatch_ms = delta["counters"].get("fit.launch.ns", 0) / 1e6
    gap_count = int(delta["counters"].get("fit.launch.n", 0))
    return {
        "name": name,
        "totalTimeMs": elapsed_ms,
        "inputRecordNum": num_input,
        "inputThroughput": num_input * 1000.0 / elapsed_ms if elapsed_ms else 0.0,
        "outputRecordNum": num_output,
        "outputThroughput": num_output * 1000.0 / elapsed_ms if elapsed_ms else 0.0,
        "phaseTimesMs": {k: v * 1000.0 for k, v in phases.items()},
        # first-class dispatch-pipeline fields (also inside metrics):
        # blocking host↔device syncs this entry paid, and the in-flight
        # chunk depth its pipelined loops ran at — a sync-count jump
        # between BENCH files is a dispatch regression
        "hostSyncCount": int(delta["counters"].get("iteration.host_sync", 0)),
        "dispatchDepth": int(delta["gauges"].get("iteration.dispatch_depth", 0)),
        # whole-fit resident-program evidence (parallel/dispatch.py): fits
        # that ran as ONE dispatch + ONE packed readback, and fits that
        # asked to but fell back to the chunked path (per-reason counters
        # inside metrics) — a fallback jump between BENCH files means a
        # config change quietly knocked fits off the resident path
        "wholeFitCount": int(delta["counters"].get("dispatch.whole_fit", 0)),
        "wholeFitFallbacks": int(
            delta["counters"].get("dispatch.whole_fit_fallback", 0)
        ),
        # fleet-training evidence (fleet.py): members this entry trained
        # through the vmapped resident program, and the many-model
        # throughput those fits amortized into the work phases —
        # modelsPerSecond at fleetSize=1 IS the solo fit rate, so a drop
        # at constant fleetSize between BENCH files is a fleet regression
        "fleetSize": (
            int(delta["gauges"].get("fleet.size", 0))
            if delta["counters"].get("fleet.modelsTrained", 0)
            else 0
        ),
        "modelsPerSecond": (
            delta["counters"].get("fleet.modelsTrained", 0)
            / (work_ms / 1000.0)
            if work_ms and delta["counters"].get("fleet.modelsTrained", 0)
            else 0.0
        ),
        "hostDispatchMs": host_dispatch_ms,
        "dispatchGapMs": (
            max(0.0, work_ms - host_dispatch_ms) if gap_count else 0.0
        ),
        "gapCount": gap_count,
        # segments the transform phase fused (0 = eager per-stage path); a
        # drop between BENCH files means stages fell off the fused path
        "fusedSegments": int(delta["gauges"].get("pipeline.fused_segments", 0)),
        # input-pipeline evidence: bytes/transfers this entry pushed
        # host→device through the accounted stager, and the device epoch
        # cache's hit/miss split — an h2dBytes jump between BENCH files is
        # an upload regression (a loop quietly going back to re-uploading
        # its epochs), exactly as hostSyncCount is for readbacks
        "h2dBytes": int(delta["counters"].get("h2d.bytes", 0)),
        "h2dCount": int(delta["counters"].get("h2d.count", 0)),
        "deviceCacheHits": int(delta["counters"].get("devicecache.hit", 0)),
        "deviceCacheMisses": int(delta["counters"].get("devicecache.miss", 0)),
        # checkpoint-subsystem evidence (ckpt/snapshot.py): snapshots this
        # entry wrote and the bytes they gathered — a jump between BENCH
        # files means a loop's snapshot cadence (or payload) changed
        "checkpointCount": int(delta["counters"].get("checkpoint.count", 0)),
        "checkpointBytes": int(delta["counters"].get("checkpoint.bytes", 0)),
        # flow-control evidence (flow.py): transient-fault retries this
        # entry paid, items shed/rejected by overloaded channels, and the
        # deepest any bounded queue got — a retryCount jump between BENCH
        # files means a dependency got flaky, a shed/reject jump means a
        # consumer stopped keeping up, and peakQueueDepth is the memory
        # high-water evidence behind the bounded-overload claim
        "retryCount": int(delta["counters"].get("flow.retry", 0)),
        "shedCount": int(delta["counters"].get("flow.shed", 0)),
        "rejectCount": int(delta["counters"].get("flow.reject", 0)),
        "peakQueueDepth": int(delta["gauges"].get("flow.peakQueueDepth", 0)),
        # device-memory evidence (obs/memledger.py): the peak ledgered
        # HBM bytes this entry touched (watermark over the whole entry,
        # datagen included) and the model constants still resident at
        # entry end — a peakHbmBytes jump between BENCH files means a
        # loop started holding more live at once (the regression the
        # ROADMAP's 2D-mesh and HBM-paging work must not cause), a
        # residentModelBytes jump means published models grew
        "peakHbmBytes": int(memledger.peak_since(hbm_mark)),
        "residentModelBytes": int(memledger.live_bytes("model")),
        # model-lifecycle evidence (lifecycle.py): live model versions this
        # entry published into a serving plan, promotions the gate refused,
        # and health-triggered rollbacks — a promoteRejected jump between
        # BENCH files means the trainer started producing bad candidates,
        # a rollbackCount jump means bad ones started slipping the gate
        "swapCount": int(delta["counters"].get("lifecycle.swap", 0)),
        "rollbackCount": int(delta["counters"].get("lifecycle.rollback", 0)),
        "promoteRejected": int(delta["counters"].get("lifecycle.promoteRejected", 0)),
        # serving evidence (data/modelstore.py): the model-store page-ins
        # this entry paid — a jump between two results of the same job is
        # a serving regression
        "pageInCount": int(delta["counters"].get("modelstore.pageIn", 0)),
        # per-op collective traffic this entry traced (calls/bytes/chunks
        # from the accounted wrappers in parallel/collectives.py, plus the
        # sparse-vs-dense byte ratio when a sparse reduce ran) — the
        # traffic-proportionality evidence next to the timing numbers
        "collectiveBreakdown": collective_breakdown(delta),
        # per-chunk timeline attribution when the flight recorder is on
        # (wall = dispatch + device + readback + idle-gap, obs/timeline.py)
        "dispatchAttribution": _entry_attribution(timeline, timeline_start_us),
        "metrics": delta,
    }


def _entry_attribution(timeline, start_us: float) -> Optional[Dict]:
    """This entry's dispatch-wall attribution from the flight recorder
    (events recorded since `start_us`); None when the timeline is off or
    no chunk dispatch ran. The per-chunk rows are dropped from the BENCH
    payload (unbounded size) — totals + per-epoch means stay."""
    if not timeline.enabled():
        return None
    events, _ = timeline.snapshot_events()
    attr = timeline.dispatch_attribution(
        [e for e in events if e["tsUs"] >= start_us]
    )
    if not attr:
        return None
    attr.pop("chunks", None)
    return attr


def collective_breakdown(delta: Dict) -> Dict[str, Dict]:
    """Reduce a metrics delta's `collective.<op>.{calls,bytes,chunks}`
    counters into {op: {calls, bytes[, chunks]}} (+ `sparseRatio` from the
    gauge). Empty dict when the entry dispatched no accounted collective."""
    out: Dict[str, Dict] = {}
    for name, value in delta.get("counters", {}).items():
        parts = name.split(".")
        if len(parts) != 3 or parts[0] != "collective":
            continue
        op, field = parts[1], parts[2]
        if field in ("calls", "bytes", "chunks", "dense_equiv_bytes"):
            out.setdefault(op, {})[field] = int(value)
    ratio = delta.get("gauges", {}).get("collective.sparse_ratio")
    if out and ratio is not None:
        out["sparseRatio"] = ratio
    return out


def _adapt_input_columns(stage, input_tables: List[Table]) -> None:
    """Compensate for broken upstream benchmark configs: several reference
    configs (normalizer, maxabsscaler, vectorslicer, elementwiseproduct,
    polynoimalexpansion) generate a single column named 'featuresCol' while
    the stage's input/features param keeps its default ('input'/'features')
    — the stage would fail on the reference too. When the stage's input
    column is missing and the generated table has exactly one column, point
    the stage at that column and log the adaptation."""
    if len(input_tables) != 1 or len(input_tables[0].column_names) != 1:
        return
    only_col = input_tables[0].column_names[0]
    for getter, setter in (
        ("get_input_col", "set_input_col"),
        ("get_features_col", "set_features_col"),
    ):
        if hasattr(stage, getter):
            current = getattr(stage, getter)()
            if current not in input_tables[0] and only_col != current:
                getattr(stage, setter)(only_col)
                print(
                    f"  [config-adapt] {type(stage).__name__}.{getter[4:]}: "
                    f"{current!r} -> {only_col!r} (column absent from generated table)",
                    file=sys.stderr,
                )
            return


def _block_until_ready(tables: List[Table]) -> None:
    """Force device-resident columns to completion so phase timings measure
    real work, not async dispatch. The barrier is a scalar READBACK of a
    probe value that depends on every device column (one host round trip
    total) — including device arrays nested inside SparseBatch and
    DictTokenMatrix columns. It was chosen over `block_until_ready` on an
    installation that is gone; whether the plain call suffices on today's
    machine is to be re-measured (ROADMAP.md S2)."""
    import jax
    import jax.numpy as jnp

    from ..table import DictTokenMatrix, SparseBatch

    probes = []
    for t in tables:
        for name in t.column_names:
            col = t.column(name)
            if isinstance(col, SparseBatch):
                arrs = (col.indices, col.values)
            elif isinstance(col, DictTokenMatrix):
                arrs = (col.ids,)
            else:
                arrs = (col,)
            for arr in arrs:
                if isinstance(arr, jax.Array):
                    probes.append(arr[(0,) * arr.ndim].astype(jnp.float32))
    if probes:
        # the barrier is itself a blocking read: through the funnel, like any other
        tracing.sync("barrier", jnp.stack(probes), arrays=len(probes))


def execute_benchmarks(config: Dict) -> Dict[str, Dict]:
    results = {}
    names = [k for k in config if k != "version"]
    print(f"Found {len(names)} benchmarks.")
    for name in names:
        print(f"Running benchmark {name}.")
        results[name] = run_benchmark(name, config[name])
        r = results[name]
        phase_str = "  ".join(
            f"{k}: {v:.1f}" for k, v in r["phaseTimesMs"].items()
        )
        print(
            f"  totalTimeMs: {r['totalTimeMs']:.1f}  "
            f"inputThroughput: {r['inputThroughput']:.1f} rec/s  [{phase_str}]"
        )
    print("Benchmarks execution completed.")
    return results


def main(argv: List[str]) -> None:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return
    config_path = argv[0]
    output_file = None
    if "--output-file" in argv:
        output_file = argv[argv.index("--output-file") + 1]
    profile_dir = None
    if "--profile-dir" in argv:
        profile_dir = argv[argv.index("--profile-dir") + 1]
    config = load_config(config_path)
    if profile_dir:  # jax.profiler device trace, TensorBoard-loadable
        with metrics.profile_trace(profile_dir):
            results = execute_benchmarks(config)
        print(f"Profiler trace written to {profile_dir}.")
    else:
        results = execute_benchmarks(config)
    if output_file:
        payload = {
            name: {"stage": config[name]["stage"], "results": r}
            for name, r in results.items()
        }
        with open(output_file, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"Benchmark results saved as json in {output_file}.")
    else:
        print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main(sys.argv[1:])
