"""The writes at a batch's slots (`ops/slot_write.py`), run here by their own
code, interpreted, against XLA's scatter, bit for bit:

1. `write_in_place` (two vectors sharing the slots, given up) and
   `copy_writing` (a fresh vector, the input left as it was) at coordinates
   0, 127, 128 and d - 1, several in one 128-lane row and in one tile, tiles
   on either side of a tile's edge and of a block's edge, a length that is a
   multiple of neither 128 nor 1024 (the partial last tile), no slot at all,
   every coordinate, and slots past `count` that point anywhere;
2. `_ftrl_touched` with the kernels against itself with XLA's scatters, on
   either side of its quarter of the batch's entries and at the boundary;
3. the online loop where `mesh.on_tpu` says yes, over a model of a tile
   and more: the same versions as XLA's scatters to the bit,
   `ftrl.write.slots` once a batch, a held record unchanged, an untouched
   coordinate kept, a checkpoint resumed; a shorter model keeps XLA's
   scatters.

A CPU array is on no TPU, so `_slot_writer` never takes the kernels here:
the tests that need them tell `mesh.on_tpu` what the chip's arrays say, and
the kernels run interpreted because the arrays' true platform says so.
(Compiled for a described v5e at the stream cell's size: the last test of
tests/test_layout_exchange.py, beside the other compiles for the chip.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import config
from flink_ml_tpu.models.classification import onlinelogisticregression as olr
from flink_ml_tpu.ops import slot_write
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.utils import metrics

import test_online_ftrl_sparse as online

SORTED = dict(indices_are_sorted=True, unique_indices=True)
TILE = slot_write.TILE
BLOCK = 2 * TILE  # the pass's block in these tests: several blocks over a few thousand elements


def scattered(array, coord, values):
    return np.asarray(jnp.asarray(array).at[jnp.asarray(coord)].set(jnp.asarray(values), mode="drop", **SORTED))


@jax.jit
def in_place(a, b, coord, u, v, count):
    return slot_write.write_in_place((a, b), coord, (u, v), count, interpret=True)


@jax.jit
def copied(a, coord, u, count):
    return slot_write.copy_writing(a, coord, u, count, interpret=True, block=BLOCK)


def check_both_writers(d, coords, slots=None, past=None, seed=0):
    """Both writers at `coords` (the first `count` slots) against XLA's
    scatter of the same slots; the slots behind them point past the end, or
    at `past` (coordinates XLA's scatter is not given)."""
    rng = np.random.default_rng(seed)
    coords = np.asarray(coords, np.int32)
    count = len(coords)
    slots = slots or max(count, 1)
    behind = d + np.arange(slots - count) if past is None else np.resize(np.asarray(past, np.int32), slots - count)
    coord = np.concatenate([coords, behind]).astype(np.int32)
    a, b = (rng.standard_normal(d).astype(np.float32) for _ in range(2))
    u, v = (rng.standard_normal(slots).astype(np.float32) for _ in range(2))
    want = [scattered(x, coords, w[:count]) for x, w in ((a, u), (b, v))]
    got = in_place(jnp.asarray(a), jnp.asarray(b), jnp.asarray(coord), jnp.asarray(u), jnp.asarray(v), jnp.int32(count))
    for g, w in zip(got, want):
        assert np.asarray(g).tobytes() == w.tobytes()
    given = jnp.asarray(a)
    fresh = copied(given, jnp.asarray(coord), jnp.asarray(u), jnp.int32(count))
    assert np.asarray(fresh).tobytes() == want[0].tobytes()
    assert np.asarray(given).tobytes() == a.tobytes()  # the input is left as it was


D = 5 * TILE + 601  # a multiple of neither 128 nor 1024: a partial last tile


@pytest.mark.parametrize(
    "coords",
    [
        [0, 127, 128, D - 1],
        [3, 4, 5, 64, 127],  # one 128-lane row
        [130, 200, 900, 1023, 1024, 1025],  # one tile, then the next across its edge
        [BLOCK - 2, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK - 1, 2 * BLOCK],  # across the pass's block edges
        [5 * TILE - 1, 5 * TILE, 5 * TILE + 1, D - 2, D - 1],  # into the partial tile
        [],  # no slot: both vectors as they were
    ],
    ids=["ends-and-lanes", "one-row", "tile-edge", "block-edges", "partial-tile", "none"],
)
def test_both_writers_are_xlas_scatter_to_the_bit(coords):
    check_both_writers(D, coords, slots=12)


@pytest.mark.parametrize("d", [TILE, TILE + 37, 200], ids=["one-tile", "37-past", "shorter-than-a-tile"])
def test_every_coordinate_of_a_vector(d):
    check_both_writers(d, np.arange(d), slots=d)


def test_slots_past_count_are_not_written_wherever_they_point():
    check_both_writers(D, [5, 2048, D - 1], slots=40, past=[0, 6, 2048, 4000, D - 1])


def test_more_slots_than_a_chunk_of_scalar_memory_holds():
    """Slots in two chunks of CHUNK, a tile shared across the chunks' edge."""
    rng = np.random.default_rng(3)
    d = (slot_write.CHUNK + 200) * 8
    edge = sorted(rng.choice(np.arange(8, d - 8, 8), slot_write.CHUNK + 100, replace=False))
    edge[slot_write.CHUNK - 1] = edge[slot_write.CHUNK] - 1  # the last of one chunk and the first of the next share a tile
    check_both_writers(d, sorted(set(edge)), slots=slot_write.CHUNK + 150, seed=3)


def batch(distinct, seed=19):
    rng = np.random.RandomState(seed)
    dim, rows, nnz = TILE + 37, 16, 6
    idx = np.resize(rng.permutation(dim)[:distinct], rows * nnz)
    idx = rng.permutation(idx).reshape(rows, nnz).astype(np.int32)
    val = (rng.rand(rows, nnz) + 0.1).astype(np.float32)
    y = rng.randint(0, 2, rows).astype(np.float32)
    state = tuple(jnp.asarray(a, jnp.float32) for a in (rng.randn(dim) * 0.1, rng.randn(dim) * 0.1, rng.rand(dim)))
    return state, (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y))


@pytest.mark.parametrize("distinct", [4, 24, 25, 96], ids=["few", "a-quarter", "one-over", "all"])
def test_the_step_writes_what_xlas_scatters_write(distinct):
    (w, z, n), rows = batch(distinct)
    hyper = jnp.asarray([0.1, 0.1, 0.05, 0.05], jnp.float32)
    step = jax.jit(olr._ftrl_touched, static_argnames=("writer",))
    want = step(z, n, w, *rows, hyper)
    got = step(z, n, w, *rows, hyper, writer="interpret")
    for g, r in zip(got, want):
        assert np.asarray(g).tobytes() == np.asarray(r).tobytes()


@pytest.fixture
def wide(monkeypatch):
    """The online tests' model, a whole tile and a partial one wide: the
    slot writer's least."""
    monkeypatch.setattr(online, "DIM", TILE + 37)
    monkeypatch.setattr(online, "NEVER", TILE + 36)


@pytest.fixture
def on_a_tpu(monkeypatch, wide):
    """`mesh.on_tpu` says what the chip's arrays say: the loop takes the
    kernels, interpreted."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)


def test_the_writer_is_read_off_the_state():
    state = (jnp.zeros(TILE),) * 3
    assert olr._slot_writer(*state) is None  # the CPU's arrays: XLA's scatter


def test_the_writer_is_taken_for_float32_state_on_one_tpu(on_a_tpu):
    state = (jnp.zeros(TILE),) * 3
    assert olr._slot_writer(*state) == "interpret"
    assert olr._slot_writer(jnp.zeros(TILE, jnp.bfloat16), *state[1:]) is None
    # shorter than a tile: laid out in tiles of 128, which the kernels' copies do not take
    assert olr._slot_writer(*(jnp.zeros(TILE - 1),) * 3) is None


def test_a_stream_on_a_tpu_gives_xlas_versions_and_counts_each_batch(monkeypatch, wide):
    rng = np.random.RandomState(3)
    chunks = [online.rows_of(rng, 16, few=k % 2 == 1) for k in range(5)]
    w0 = np.linspace(-0.5, 0.5, online.DIM)
    runs = []
    for kernels in (False, True):
        if kernels:
            monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
        model = online.estimator(16, w0, 0.4, 0.5).fit(online.stream_of(chunks))
        counted = online.counters_over(model.process_updates)
        assert counted["ftrl.batches"] == 5 and counted.get("ftrl.write.slots", 0) == (5 if kernels else 0)
        runs.append([np.asarray(a) for a in model.training_state()[1]])
    for kernel, xla in zip(*reversed(runs)):
        assert kernel.tobytes() == xla.tobytes()


def test_on_a_tpu_a_model_shorter_than_a_tile_keeps_xlas_scatter(monkeypatch):
    """The stream cell's guard folds a batch into a 64-coordinate model on
    the chip, where the kernels' tile copies do not compile."""
    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    rng = np.random.RandomState(3)
    model = online.estimator(16).fit(online.stream_of([online.rows_of(rng, 16) for _ in range(2)]))
    counted = online.counters_over(model.process_updates)
    assert counted["ftrl.batches"] == 2 and "ftrl.write.slots" not in counted


def test_on_a_tpu_a_held_record_does_not_change(on_a_tpu):
    rng = np.random.RandomState(11)
    model = online.estimator(16).fit(online.stream_of([online.rows_of(rng, 16) for _ in range(4)]))
    model.process_updates(max_batches=1)
    record, then = model._published, np.array(model._published.coefficient)
    model.process_updates()
    assert model.model_version == 4
    assert np.asarray(record.coefficient).tobytes() == then.tobytes()
    assert not np.array_equal(then, np.asarray(model._published.coefficient))


def test_on_a_tpu_a_coordinate_no_row_holds_keeps_w_z_n_to_the_bit(on_a_tpu):
    rng = np.random.RandomState(5)
    chunks = [online.rows_of(rng, 16) for _ in range(3)]
    w0 = np.linspace(-0.5, 0.5, online.DIM)
    model = online.estimator(16, w0).fit(online.stream_of(chunks, device=True))
    model.process_updates()
    w, z, n = (np.asarray(a) for a in model.training_state()[1])
    assert w[online.NEVER].tobytes() == np.float32(w0[online.NEVER]).tobytes()
    assert z[online.NEVER] == 0.0 and n[online.NEVER] == 0.0
    assert n[online.EVERY] > 0.0


def test_on_a_tpu_a_stream_resumes_from_its_checkpoint_to_the_bit(on_a_tpu, tmp_path):
    rng = np.random.RandomState(37)
    chunks = [online.rows_of(rng, 16) for _ in range(4)]
    full = online.estimator(16, reg=0.2, elastic_net=0.5).fit(online.stream_of(chunks))
    full.process_updates()
    with config.iteration_checkpointing(str(tmp_path / "ckpt")):
        online.estimator(16, reg=0.2, elastic_net=0.5).fit(online.stream_of(chunks)).process_updates(max_batches=2)
        resumed = online.estimator(16, reg=0.2, elastic_net=0.5).fit(online.stream_of(chunks))
        assert resumed.process_updates(max_batches=1) == 2
        before = metrics.snapshot()
        resumed.process_updates()
        assert metrics.snapshot_delta(before, metrics.snapshot())["counters"]["ftrl.write.slots"] == 2
    assert resumed.model_version == 4
    assert resumed.coefficient.tobytes() == full.coefficient.tobytes()
