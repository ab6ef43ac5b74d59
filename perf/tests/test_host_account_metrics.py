"""The readers of the host's account of a fit (`fit.outer.*`, `fit.sync.*`:
wait, copy and the fit's own time), of the collector's pauses (`host.gc.ns`)
and of the online loop's fence (`online.fence.*`): on a `run` made by hand,
on the counters of a program that has none of them (the parent commit), and
in traced rehearsals of perf/run.py against the program as it is."""

import json

import pytest

import run as harness

NS = 1_000_000  # a millisecond
FIT_METRICS = ("fit_wait_ms", "fit_d2h_ms", "fit_host_self_ms")
STREAM_METRICS = ("stream_fence_wait_ms", "stream_dry_batches_share")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


def reader(name):
    return harness.load_module("metrics", name).read


def hand_made(counters, seconds=2.0):
    return {"counters": counters, "window": {"attempted": 4, "begin": 10.0, "end": 10.0 + seconds}, "trace": None}


# a pipeline's window: four outermost fits of four estimators each
FITS = {
    "fit.total.n": 16, "fit.total.ns": 70 * NS,
    "fit.outer.n": 4, "fit.outer.ns": 40 * NS,
    "fit.sync.wait.ns": 24 * NS, "fit.sync.copy.ns": 6 * NS, "fit.sync.bytes": 4096,
    "host.gc.ns": 5 * NS, "host.gc.n": 9,
}
STREAM = {
    "online.batch.n": 8, "online.batch.ns": 130 * NS,
    "online.fence.n": 6, "online.fence.ns": 120 * NS,
    "online.fence.dry": 2, "sync.fence.n": 6, "sync.fence.wait.ns": 119 * NS,
}
PARENT_FITS = {"fit.total.n": 4, "fit.total.ns": 40 * NS, "fit.readback.ns": 30 * NS, "iteration.host_sync": 4}
PARENT_STREAM = {k: v for k, v in STREAM.items() if k not in ("online.fence.dry", "sync.fence.n", "sync.fence.wait.ns")}


@pytest.mark.parametrize(
    "name, counters, value",
    [
        ("fit_wait_ms", FITS, 6.0),
        ("fit_d2h_ms", FITS, 1.5),
        ("fit_host_self_ms", FITS, 2.5),  # (40 - 24 - 6) ms over 4 outermost fits
        ("host_gc_ms_per_s", FITS, 2.5),  # 5 ms over a window of 2 s
        ("host_gc_ms_per_s", STREAM, 0.0),  # a window without a collection
        ("stream_fence_wait_ms", STREAM, 15.0),
        ("stream_dry_batches_share", STREAM, 25.0),
    ],
)
def test_readers_on_a_hand_made_run(name, counters, value):
    assert reader(name)(hand_made(counters)) == pytest.approx(value)


def test_the_three_parts_sum_to_the_fits_wall():
    parts = [reader(name)(hand_made(FITS)) for name in FIT_METRICS]
    assert sum(parts) == pytest.approx(FITS["fit.outer.ns"] / FITS["fit.outer.n"] / NS)


def test_a_fit_that_read_nothing_is_all_its_own():
    counters = {"fit.outer.n": 2, "fit.outer.ns": 8 * NS}
    assert reader("fit_wait_ms")(hand_made(counters)) == 0
    assert reader("fit_d2h_ms")(hand_made(counters)) == 0
    assert reader("fit_host_self_ms")(hand_made(counters)) == pytest.approx(4.0)


def test_a_steady_stream_was_never_dry():
    counters = {k: v for k, v in STREAM.items() if k != "online.fence.dry"}
    assert reader("stream_dry_batches_share")(hand_made(counters)) == 0


@pytest.mark.parametrize(
    "name, counters",
    [(name, PARENT_FITS) for name in FIT_METRICS + ("host_gc_ms_per_s",)]
    + [("stream_dry_batches_share", PARENT_STREAM), ("host_gc_ms_per_s", PARENT_STREAM)]
    + [(name, {}) for name in FIT_METRICS + STREAM_METRICS + ("host_gc_ms_per_s",)],
)
def test_a_program_without_the_counters_reports_nothing(name, counters):
    # the parent commit, under this benchmark's files: counters, but none of this PR's
    assert reader(name)(hand_made(counters)) is None


def test_the_fence_was_a_phase_before_it_had_a_reader():
    # `online.fence` is the parent's: its wait reads there too, and only there
    assert reader("stream_fence_wait_ms")(hand_made(PARENT_STREAM)) == pytest.approx(15.0)


def test_every_new_entry_lists_its_cells():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: harness.load_json(harness.ROOT, c["file"]) for c in BENCH["configs"]}
    online = {
        name for name, cell in cells.items()
        if configs[cell["config"]]["stage"]["class"].rpartition(".")[2].startswith("Online")
    }
    listed = {m["name"]: set(m["workloads"]) for m in BENCH["per_layer"] if "workloads" in m}
    for name in FIT_METRICS:
        assert listed[name] == set(cells) - online
    for name in STREAM_METRICS:
        assert listed[name] == online
    assert listed["host_gc_ms_per_s"] == set(cells)


def rehearsal(capsys, cell):
    code = harness.main(
        ["--workload", cell, "--rehearse-on-cpu", "--seed", "2147484003", "--seconds", "0.5", "--trace", "1"]
    )
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])["metrics"]


@pytest.mark.parametrize("cell", ["lr-sparse-1m.partitions", "criteo-onehot-pipeline.day-partitions"])
def test_traced_rehearsal_of_a_fit_cell_reports_the_three_way_split(capsys, cell):
    metrics = rehearsal(capsys, cell)
    for name in FIT_METRICS:
        assert metrics[name]["unit"] == "ms" and metrics[name]["value"] > 0, name
    assert metrics["host_gc_ms_per_s"]["unit"] == "ms/s" and metrics["host_gc_ms_per_s"]["value"] >= 0
    assert not set(STREAM_METRICS) & set(metrics)


def test_traced_rehearsal_of_the_stream_cell_reports_its_fence(capsys):
    metrics = rehearsal(capsys, "ftrl-criteo-1tb.stream")
    assert metrics["stream_fence_wait_ms"]["value"] > 0
    assert 0 <= metrics["stream_dry_batches_share"]["value"] <= 100
    assert metrics["host_gc_ms_per_s"]["value"] >= 0
    assert not set(FIT_METRICS) & set(metrics)
