"""What the online FTRL configuration brought: the table maker without hashing
and with skew inside a field, the work counter, the plain reference, the
generator `stream_loop` through perf/run.py at the rehearsal size with its own
faults planted, and the readers of the program's `online.*` and `ftrl.*`
counters."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import run as harness

CELL = "ftrl-criteo-1tb.stream"
CONFIG = harness.load_json(harness.PERF, "configs", "ftrl-criteo-1tb.json")
TRAFFIC = harness.load_json(harness.PERF, "traffic", "stream.json")
SMALL = dict(CONFIG["data"], **TRAFFIC["rehearsal"]["data"])
ARGS = ["--seed", "2147484011", "--seconds", "0.3", "--trace", "0"]
faults = harness.load_module("", "faults_stream")
maker = harness.load_module("tables", CONFIG["data"]["table"])
reference = harness.load_module("reference", "ftrl-criteo-1tb")
NS = 1_000_000


def made(seed, rows=20_000, data=SMALL):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    arrays = maker.make(jax.random.PRNGKey(seed), rows, data, mesh)
    return {name: np.asarray(a) for name, a in arrays.items()}


def test_the_configuration_is_the_sources_shapes_and_the_log_fills_a_third_of_the_chip():
    data, params = CONFIG["data"], CONFIG["stage"]["params"]
    assert len(data["cardinalities"]) == 26 and data["integer_fields"] == 13 and data["nnz"] == 39
    assert sum(data["cardinalities"]) == 204_184_588 and data["dim"] == 204_184_601
    assert max(data["cardinalities"]) == 40_000_000  # the reference's cap
    assert params == {"alpha": 0.1, "beta": 0.1, "reg": 0.0, "elasticNet": 0.0, "globalBatchSize": 4096}
    rows = TRAFFIC["batches"] * params["globalBatchSize"]
    assert rows == 16_384_000 and rows * (39 * 8 + 4) == 5_177_344_000
    assert len(CONFIG["source"]) <= 200 and CONFIG["reduced"] == ["numValues"]
    maker.checked(data)  # the spread is a bijection of every field in 32 bits


def test_fields_keep_their_offsets_and_nothing_is_hashed():
    arrays = made(7)
    ids, values = arrays["indices"], arrays["values"]
    counts, cards = SMALL["integer_fields"], SMALL["cardinalities"]
    first = maker.offsets(SMALL)
    assert first[0] == counts and first[-1] + cards[-1] == SMALL["dim"]
    assert (ids[:, :counts] == np.arange(counts)).all()
    assert (values[:, :counts] >= 0).all() and (values[:, :counts] < 1).all()
    assert (values[:, counts:] == 1.0).all()
    for field, (start, card) in enumerate(zip(first, cards)):
        column = ids[:, counts + field]
        assert column.min() >= start and column.max() < start + card
        if card <= 64:  # a small field's every category shows in 20,000 rows
            assert len(np.unique(column)) == card
    assert set(np.unique(arrays["label"])) == {0.0, 1.0}


@pytest.mark.parametrize("card", [3, 10, 1100, 12_973, 40_000_000])
def test_the_spread_is_a_bijection_of_a_fields_range(card):
    ranks = np.arange(min(card, 200_000), dtype=np.uint32)
    out = np.asarray(maker.spread(jnp.asarray(ranks), jnp.uint32(card)))
    assert out.max() < card and len(np.unique(out)) == len(ranks)
    want = (ranks.astype(np.uint64) * 101 % card) * 103 % card
    assert (out == want).all()
    if card > 10_403:  # hot categories are not neighbours in memory
        assert abs(int(out[1]) - int(out[0])) == 10_403


def test_a_field_no_spread_fits_is_refused():
    with pytest.raises(ValueError, match="no bijection"):
        maker.checked(dict(SMALL, cardinalities=[101 * 7] + SMALL["cardinalities"][1:], dim=SMALL["dim"] - 1100 + 707))
    with pytest.raises(ValueError, match="not dim"):
        maker.checked(dict(SMALL, dim=SMALL["dim"] + 1))


def test_the_skew_gives_rank_one_its_share_and_a_batch_its_repeats():
    arrays = made(11, rows=40_000)
    counts, cards = SMALL["integer_fields"], SMALL["cardinalities"]
    first = maker.offsets(SMALL)
    field = cards.index(1100)
    column = arrays["indices"][:, counts + field]
    head = (column == first[field]).mean()  # rank 1 lies at the field's first id
    assert head == pytest.approx(math.log(2) / math.log(1101), rel=0.05)
    second = (column == first[field] + 10_403 % 1100).mean()
    assert second == pytest.approx(math.log(1.5) / math.log(1101), rel=0.08)
    batch = arrays["indices"][:4096]
    assert len(np.unique(batch)) < 0.25 * batch.size  # skewed fields repeat


def test_the_uniform_skew_is_the_other_side_and_no_third_is_taken():
    """`probe_stream.py --skew uniform`: every category as likely as another,
    so a batch holds more distinct coordinates than under the configuration's."""
    flat = made(11, rows=40_000, data=dict(SMALL, skew="uniform"))
    counts, cards = SMALL["integer_fields"], SMALL["cardinalities"]
    first = maker.offsets(SMALL)
    field = cards.index(1100)
    column = flat["indices"][:, counts + field]
    assert column.min() == first[field] and column.max() == first[field] + 1099
    shares = np.bincount(column - first[field], minlength=1100) / len(column)
    assert shares.max() < 3 / 1100 and shares.min() > 0
    skewed = made(11, rows=40_000)
    assert len(np.unique(flat["indices"][:4096])) > 1.3 * len(np.unique(skewed["indices"][:4096]))
    assert maker.skew_of(SMALL) == "zipf1"
    with pytest.raises(ValueError, match="zipf1 or uniform"):
        maker.skew_of(dict(SMALL, skew="zipf2"))


def test_the_same_seed_gives_the_same_log_and_another_seed_another():
    first, again, other = made(13, 2_000), made(13, 2_000), made(14, 2_000)
    assert all((first[name] == again[name]).all() for name in first)
    assert (first["indices"] != other["indices"]).any()


def test_ftrl_batch_counts_the_batch_once_and_no_state():
    counter = harness.load_module("counters", CONFIG["work"])
    counted = getattr(counter, CONFIG["work"])(CONFIG["data"], CONFIG["stage"]["params"])
    assert counted == {"bytes": 4096 * (39 * 8 + 4), "flops": 4096 * 39 * 20}
    assert counted["bytes"] == 1_294_336
    work = harness.load_module("", "work")
    least = work.least_seconds(counted, harness.load_json(harness.PERF, "peaks.json")["TPU v5 lite"], 1)
    assert least["bound"] == "hbm" and least["seconds"] == pytest.approx(1.5804e-6, rel=1e-4)


def test_the_reference_keeps_what_no_row_holds_and_takes_the_l1_branch():
    dim = 12
    state = (jnp.linspace(-1, 1, dim), jnp.full(dim, 0.2), jnp.full(dim, 0.3))
    idx = jnp.asarray([[0, 3, -1], [0, 5, 3]], jnp.int32)
    val = jnp.asarray([[1.0, 0.5, 9.0], [1.0, 2.0, 0.25]], jnp.float32)
    y = jnp.asarray([1.0, 0.0])
    params = {"alpha": 0.1, "beta": 0.1, "reg": 1.0, "elasticNet": 0.5}
    w, z, n = reference.batch_step(state, (idx, val, y), reference.hyperparameters(params))
    untouched = np.setdiff1d(np.arange(dim), [0, 3, 5])
    for new, old in zip((w, z, n), state):
        assert (np.asarray(new)[untouched] == np.asarray(old)[untouched]).all()
        assert (np.asarray(new)[[0, 3, 5]] != np.asarray(old)[[0, 3, 5]]).all()
    # by hand at coordinate 5 (one row holds it): g = (p - y) * 2, p from the row's score
    score = 1.0 * state[0][0] + 2.0 * state[0][5] + 0.25 * state[0][3]
    g = float((1 / (1 + np.exp(-score)) - 0.0) * 2.0)
    n5 = 0.3 + g * g
    z5 = 0.2 + g - (math.sqrt(n5) - math.sqrt(0.3)) / 0.1 * float(state[0][5])
    w5 = 0.0 if abs(z5) <= 0.5 else (math.copysign(0.5, z5) - z5) / ((0.1 + math.sqrt(n5)) / 0.1 + 0.5)
    assert float(n[5]) == pytest.approx(n5, rel=1e-5) and float(z[5]) == pytest.approx(z5, rel=1e-5)
    assert float(w[5]) == pytest.approx(w5, rel=1e-5, abs=1e-7)
    packed = reference.pack((w, z, n))
    assert all((a == b).all() for a, b in zip(reference.unpack(packed, dim), (w, z, n)))


def result_of(capsys, extra=()):
    code = harness.main(["--workload", CELL, "--rehearse-on-cpu", *ARGS, *extra])
    out, err = capsys.readouterr()
    assert code == 0
    return json.loads(out.strip().splitlines()[-1]), err


def plant(monkeypatch, fault=None, precision="float32"):
    def make_stage(ctx, params):
        return faults.ReferenceStage(
            ctx.load("reference", ctx.cell["config"]),
            ctx.load("tables", ctx.config["data"]["table"]),
            ctx.config["data"], params, fault, precision,
        )

    monkeypatch.setattr(harness.Context, "make_stage", make_stage)


class Densifying:
    """The program's estimator as it stood before the sparse path: every
    sparse batch of the stream is folded as a dense matrix."""

    def __init__(self, stage):
        self.stage = stage

    def set_initial_model_data(self, table):
        self.stage.set_initial_model_data(table)
        return self

    def fit(self, stream):
        from flink_ml_tpu.table import StreamTable, Table

        dense = [
            Table({"features": batch.column("features").to_dense(), "label": batch.column("label")})
            for batch in stream
        ]
        return self.stage.fit(StreamTable.from_batches(dense))


def test_the_guard_passes_the_program_and_refuses_one_that_sweeps_every_coordinate():
    generator = harness.load_module("generators", "stream_loop")
    ctx = harness.Context(harness.load_json(harness.ROOT, "BENCHMARK.json"), {"name": CELL, "config": "ftrl-criteo-1tb", "traffic": "stream", "chips": 1}, 0, True)
    params = CONFIG["stage"]["params"]
    generator.refuse_a_program_that_densifies(ctx.make_stage, params)
    with pytest.raises(RuntimeError, match="updated 64 state slots"):
        generator.refuse_a_program_that_densifies(lambda p: Densifying(ctx.make_stage(p)), params)
    generator.refuse_a_program_that_densifies(lambda p: object(), params)  # a stand-in folds no stream


def test_the_rehearsal_folds_a_version_a_batch_and_reads_nothing_back(capsys):
    result, err = result_of(capsys, ["--trace", "1"])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 12
    assert set(result["compared"]) == {
        "coef_gap", "coef_max_gap", "step_coef_gap", "step_coef_max_gap",
        "step_state_gap", "step_state_max_gap", "version_gap", "shed", "failed",
    }
    assert result["compared"]["version_gap"]["value"] == 0 and result["compared"]["shed"]["value"] == 0
    metrics = result["metrics"]
    assert metrics["host_syncs_per_fit"]["value"] == 0.0 and metrics["window_compiles"]["value"] == 0
    dim, batch, nnz = SMALL["dim"], TRAFFIC["rehearsal"]["batch"], 39
    assert metrics["ftrl_state_sweep_share"]["value"] == pytest.approx(100.0 * batch * nnz / dim)
    assert {"stream_ingest_wait_ms", "stream_launch_ms", "stream_publish_ms"} <= set(metrics)
    assert not {"fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms", "fit_p95_ms"} & set(metrics)
    assert err.strip().splitlines()[-1] == "correct = True"


def test_the_end_to_end_metrics_are_the_rate_and_the_setup(capsys):
    result, _ = result_of(capsys)
    assert set(result["metrics"]) == {"trained_rows_per_s", "setup_s"}
    assert result["metrics"]["trained_rows_per_s"]["value"] > 0


def test_the_sound_reference_as_a_stand_in_is_correct(capsys, monkeypatch):
    plant(monkeypatch)
    result, _ = result_of(capsys)
    assert result["correct"] is True
    # a stand-in folds nothing: its window is the versions up to the checked one
    assert result["attempted"] == TRAFFIC["rehearsal"]["check_version"] - TRAFFIC["rehearsal"]["warmup"]


def test_the_control_in_bfloat16_is_not_correct(capsys, monkeypatch):
    plant(monkeypatch, precision="bfloat16")
    result, err = result_of(capsys)
    assert result["correct"] is False and "FAILED" in err
    assert not result["compared"]["coef_gap"]["ok"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_online_learner_is_not_correct(capsys, monkeypatch, fault):
    plant(monkeypatch, fault=fault)
    result, _ = result_of(capsys)
    assert result["correct"] is False
    assert not result["compared"]["coef_gap"]["ok"]


def test_a_skipped_batch_is_consistent_step_by_step_and_wrong_as_a_whole(capsys, monkeypatch):
    plant(monkeypatch, fault="batch_skipped")
    compared = result_of(capsys)[0]["compared"]
    assert compared["step_coef_gap"]["value"] == 0.0 and compared["step_state_gap"]["ok"]
    assert compared["coef_gap"]["value"] > 0.01


def test_a_shed_batch_and_a_lost_version_are_not_correct(capsys, monkeypatch):
    generator = harness.load_module("generators", TRAFFIC["generator"])
    fold = generator.Program.fold

    def fold_and_lose_one(self, batches=1):
        fold(self, batches)
        if self.version == 20:  # one batch folded that the window never asked for
            fold(self, 1)

    monkeypatch.setattr(generator.Program, "fold", fold_and_lose_one)
    monkeypatch.setattr(harness.Context, "load", staticmethod(lambda kind, name: generator if kind == "generators" else harness.load_module(kind, name)))
    result, _ = result_of(capsys)
    assert result["correct"] is False and result["compared"]["version_gap"]["value"] == 1.0


read = lambda name: harness.load_module("metrics", name).read  # noqa: E731
WINDOW = {
    "online.batch.n": 4, "online.batch.ns": 40 * NS, "online.ingest.ns": 2 * NS, "online.ingest.n": 4,
    "online.launch.ns": 24 * NS, "online.launch.n": 4, "online.publish.ns": 1 * NS, "online.publish.n": 4,
    "ftrl.batches": 4, "ftrl.rows": 4 * 4096, "ftrl.slots_updated": 4 * 4096 * 39,
}


def hand_made(counters, dim=204_184_601):
    return {"counters": counters, "window": {"attempted": 4}, "trace": None, "config": {"data": {"dim": dim}}}


@pytest.mark.parametrize(
    "name, value",
    [
        ("stream_ingest_wait_ms", 0.5),
        ("stream_launch_ms", 6.0),
        ("stream_publish_ms", 0.25),
        ("ftrl_state_sweep_share", 100.0 * 4096 * 39 / 204_184_601),
    ],
)
def test_the_new_readers_on_a_hand_made_run(name, value):
    assert read(name)(hand_made(WINDOW)) == pytest.approx(value)


def test_a_sweep_reads_a_hundred():
    counters = dict(WINDOW, **{"ftrl.slots_updated": 4 * 1000})
    assert read("ftrl_state_sweep_share")(hand_made(counters, dim=1000)) == 100.0
    assert read("ftrl_state_sweep_share")(hand_made(WINDOW)) == pytest.approx(0.0782, rel=2e-3)


@pytest.mark.parametrize("name", ["stream_ingest_wait_ms", "stream_launch_ms", "stream_publish_ms", "ftrl_state_sweep_share"])
def test_a_program_without_the_online_phases_reports_nothing(name):
    # the parent commit, or a window of whole fits: counters, but none of these
    assert read(name)(hand_made({"fit.total.n": 4, "iteration.host_sync": 4})) is None
    assert read(name)(hand_made({})) is None


def test_the_metrics_list_the_cell_and_no_fit_metric_gained_it():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("ftrl_state_sweep_share", "stream_ingest_wait_ms", "stream_launch_ms", "stream_publish_ms"):
        entry = by_name[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "trained_rows_per_s" and entry["better"] == "lower"
    for name in ("fit_prelaunch_ms", "fit_launch_ms", "fit_finish_ms"):
        assert CELL not in by_name[name]["workloads"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "stream" and len(cell["why"]) <= 200
    names = [m["name"] for m in harness.wanted_metrics(bench, CELL, False)]
    assert names == ["trained_rows_per_s", "setup_s"]  # no fit_p95_ms: a batch's tail is no user's number
