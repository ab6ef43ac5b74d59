"""The batch layout of a row-sharded table as an explicit exchange
(ops/optimizer.py: `_exchange_batches_impl`, picked by `SGD._lay_out`).

Pinned here, on the suite's eight virtual devices:

1. the exchange gives `_layout_batches_impl`'s array to the letter — values
   and sharding — for a dense X and both sparse leaves, over 2, 4 and 8 data
   shards, in one slab and in two, borrowed and donated;
2. `SGD._lay_out` takes the exchange where `_can_exchange` admits the input
   and the general form everywhere else (a 1-D column, a piece or a width
   that the pad to whole tiles would blow up, rows kept major, ...), counts
   which (`layout.exchange`, `layout.general`), and the result is the same
   batches either way;
3. a four-shard LogisticRegression fit gives the same coefficient, bit for
   bit, by either form;
4. compiled for four v5e chips at the benchmark's size (no chip needed), the
   exchange holds one all-to-all under XLA's own name, in a loop of slabs;
5. the exchange hands its batches over as `BatchStrips`, an array the TPU
   keeps with the batch axis outermost in memory by its own default: compiled
   for the chip, the train program reads batch k where it lies and each slab
   arrives in its final place; on the CPU a fit is the general form's to the
   bit, pad rows and columns and all.

The CPU keeps every table rows-major, so `_can_exchange` never admits one
here: the tests that need the exchange taken tell `mesh_lib.rows_minor` to
say what the TPU says of a narrow table.
"""

import math
import re
from functools import cache

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.ops import losses, optimizer
from flink_ml_tpu.ops.optimizer import SGD
from flink_ml_tpu.parallel import mesh as mesh_lib
from flink_ml_tpu.table import SparseBatch
from flink_ml_tpu.utils import metrics

BATCH = 16
DIM = 5
NNZ = 3
SLAB = mesh_lib.SUBLANES  # batches a shard exchanges at a time

# the smallest table `_can_exchange` admits over four shards: a piece of a
# batch is one tile's 128 lanes, a width is whole sublanes, a shard holds
# one slab of batches
WIDE_BATCH = 4 * mesh_lib.LANES
WIDE_DIM = mesh_lib.SUBLANES
WIDE_ROWS = 4 * WIDE_BATCH * SLAB

# kind -> (trailing shape, dtype): what SGD lays out for a fit
KINDS = {
    "X": ((DIM,), np.float32),
    "y": ((), np.float32),
    "weight": ((), np.float32),
    "indices": ((NNZ,), np.int32),
    "values": ((NNZ,), np.float32),
}


def data_mesh(shards):
    return mesh_lib.create_mesh((mesh_lib.DATA_AXIS,), devices=jax.devices()[:shards])


def host_column(kind, rows, seed=0, width=None):
    tail, dtype = KINDS[kind]
    if width is not None and tail:
        tail = (width,)
    rng = np.random.default_rng(seed)
    if dtype == np.int32:
        return rng.integers(0, 40, (rows,) + tail).astype(dtype)
    return rng.random((rows,) + tail).astype(dtype)


def by_rows(mesh, arr):
    return jax.device_put(arr, mesh_lib.data_sharding(mesh, arr.ndim))


def batched_sharding(mesh, ndim):
    return NamedSharding(mesh, P(None, mesh_lib.DATA_AXIS, *([None] * (ndim - 1))))


def as_batches(laid_out):
    """A laid-out array as [batch, row, ...] on the host, by either form."""
    if not isinstance(laid_out, optimizer.BatchStrips):
        return np.asarray(laid_out)
    strips = np.asarray(laid_out.strips)  # [batch, shard, column (padded), row]
    assert not strips[:, :, laid_out.width:].any()  # the pad columns are zeros
    return np.swapaxes(strips, 2, 3)[..., : laid_out.width].reshape(laid_out.shape)


def batches_of(host, batch, b_pad=None, d_pad=None):
    """The layout in numpy: rows padded to whole batches, [batch, row, ...],
    the row axis padded to b_pad, the feature axis to d_pad."""
    rows = host.shape[0]
    num = -(-rows // batch)
    out = np.zeros((num * batch,) + host.shape[1:], host.dtype)
    out[:rows] = host
    out = out.reshape((num, batch) + host.shape[1:])
    if b_pad and b_pad != batch:
        out = np.pad(out, [(0, 0), (0, b_pad - batch)] + [(0, 0)] * (out.ndim - 2))
    if d_pad and d_pad != out.shape[-1]:
        out = np.pad(out, [(0, 0)] * (out.ndim - 1) + [(0, d_pad - out.shape[-1])])
    return out


@pytest.fixture
def rows_minor(monkeypatch):
    """The device says of every table what the TPU says of a narrow one."""
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)


@pytest.mark.parametrize("slabs", [1, 2], ids=["one_slab", "two_slabs"])
@pytest.mark.parametrize("owned", [False, True], ids=["borrowed", "donated"])
@pytest.mark.parametrize("kind", ["X", "indices", "values"])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_exchange_gives_the_general_layout(shards, kind, owned, slabs):
    mesh = data_mesh(shards)
    rows = shards * BATCH * SLAB * slabs
    host = host_column(kind, rows, seed=shards)
    sharding = batched_sharding(mesh, host.ndim)
    general = optimizer._layout_batches(
        by_rows(mesh, host), rows, rows // BATCH, BATCH, BATCH, None, sharding
    )
    exchange = optimizer._exchange_batches_donating if owned else optimizer._exchange_batches
    given = by_rows(mesh, host)
    got = exchange(given, BATCH, mesh)
    assert got.shape == general.shape and got.dtype == general.dtype and got.width == host.shape[1]
    np.testing.assert_array_equal(as_batches(got), np.asarray(general))
    np.testing.assert_array_equal(as_batches(got), batches_of(host, BATCH))
    # every shard holds its own rows of every batch, [column, row] as they arrived
    piece = BATCH // shards
    assert got.strips.sharding.is_equivalent_to(batched_sharding(mesh, 3), 4)
    for shard in got.strips.addressable_shards:
        assert shard.data.shape == (rows // BATCH, 1, mesh_lib.SUBLANES, piece)  # 5 or 3 columns kept as 8
    if not owned:
        np.testing.assert_array_equal(np.asarray(given), host)  # a borrowed input is left alone


def test_the_exchange_is_one_accounted_all_to_all_in_a_loop_of_slabs():
    mesh = data_mesh(2)
    host = host_column("X", 2 * BATCH * SLAB * 2)
    sharding = batched_sharding(mesh, 2)
    before = metrics.snapshot()
    fn = jax.jit(  # a jit of its own: the module's may have this trace cached
        lambda arr: optimizer._exchange_batches_impl(arr, BATCH, mesh)
    )
    text = fn.lower(by_rows(mesh, host)).compile().as_text()
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    assert counters["collective.all_to_all.calls"] == 1  # one traced, in a loop of two
    # what one shard sends a slab: 2 shards x 8 batches x [8, 128] padded strips of float32
    assert counters["collective.all_to_all.bytes"] == 2 * SLAB * 8 * 128 * 4
    assert "all-to-all" in text and "while" in text


def lay_out(mesh, X, y, weights=None, batch=BATCH, d_pad=None, replicate_data=False):
    """`SGD._lay_out` and how many arrays took each form."""
    before = metrics.snapshot()
    out = SGD(global_batch_size=batch)._batchify(mesh, X, y, weights, d_pad, replicate_data)
    counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
    return out, counters.get("layout.exchange", 0), counters.get("layout.general", 0)


def test_device_table_of_whole_batches_takes_the_exchange(rows_minor):
    mesh = data_mesh(4)
    X = host_column("X", WIDE_ROWS, width=WIDE_DIM)
    y, w = (host_column(k, WIDE_ROWS) for k in ("y", "weight"))
    (X_b, y_b, w_b), exchanged, general = lay_out(
        mesh, by_rows(mesh, X), by_rows(mesh, y), by_rows(mesh, w), batch=WIDE_BATCH
    )
    assert (exchanged, general) == (1, 2)  # the table; a 1-D column keeps the general form
    for got, host in ((X_b, X), (y_b, y), (w_b, w)):
        np.testing.assert_array_equal(as_batches(got), batches_of(host, WIDE_BATCH))
    assert X_b.strips.sharding.is_equivalent_to(batched_sharding(mesh, 3), 4)
    assert y_b.sharding.is_equivalent_to(batched_sharding(mesh, 1), 2)


def test_sparse_leaves_take_the_exchange(rows_minor):
    mesh = data_mesh(4)
    indices, values = (host_column(k, WIDE_ROWS, width=WIDE_DIM) for k in ("indices", "values"))
    y = host_column("y", WIDE_ROWS)
    (X_b, y_b, _), exchanged, general = lay_out(
        mesh, (by_rows(mesh, indices), by_rows(mesh, values)), by_rows(mesh, y), batch=WIDE_BATCH
    )
    assert (exchanged, general) == (2, 1)  # both leaves; y; the default weights are made in place
    np.testing.assert_array_equal(as_batches(X_b[0]), batches_of(indices, WIDE_BATCH))
    np.testing.assert_array_equal(as_batches(X_b[1]), batches_of(values, WIDE_BATCH))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, WIDE_BATCH))


def test_host_table_that_divides_is_staged_by_rows_and_exchanged(rows_minor):
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    (X_b, y_b, _), exchanged, general = lay_out(mesh, X, y, batch=WIDE_BATCH)  # numpy in: staged, owned, donated
    assert (exchanged, general) == (1, 1)
    np.testing.assert_array_equal(as_batches(X_b), batches_of(X, WIDE_BATCH))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, WIDE_BATCH))


FALLBACKS = {
    # name: (shards, rows, batch, width, on the device?); each is the
    # admitted table above but for the one thing its name says
    "ragged_rows": (4, WIDE_ROWS - 4, WIDE_BATCH, WIDE_DIM, True),
    "batch_straddles_two_shares": (4, WIDE_ROWS, 3 * WIDE_BATCH, WIDE_DIM, True),
    "b_pad": (4, 4 * 514 * SLAB, 514, WIDE_DIM, True),  # 514 rows do not divide over 4 shards
    "host_rows_do_not_divide": (4, WIDE_ROWS - 3, WIDE_BATCH, WIDE_DIM, False),
    "one_shard": (1, WIDE_BATCH * SLAB, WIDE_BATCH, WIDE_DIM, True),
    # the library's default globalBatchSize: a piece of 8 rows would be sent as 128
    "piece_far_under_a_tile": (4, 4 * 32 * SLAB, 32, WIDE_DIM, True),
    "piece_just_over_a_tile": (4, 4 * 4 * 140 * SLAB, 4 * 140, WIDE_DIM, True),  # 140 rows sent as 256
    "width_far_under_a_tile": (4, WIDE_ROWS, WIDE_BATCH, 5, True),  # 5 columns sent as 8
    "batches_not_whole_slabs": (4, 4 * WIDE_BATCH * (SLAB + 1), WIDE_BATCH, WIDE_DIM, True),
}


@pytest.mark.parametrize("name", list(FALLBACKS))
def test_every_other_input_keeps_the_general_form(name, rows_minor):
    shards, rows, batch, width, on_device = FALLBACKS[name]
    mesh = data_mesh(shards)
    X, y = host_column("X", rows, width=width), host_column("y", rows)
    given = (by_rows(mesh, X), by_rows(mesh, y)) if on_device else (X, y)
    (X_b, y_b, w_b), exchanged, general = lay_out(mesh, *given, batch=batch)
    assert (exchanged, general) == (0, 2)
    b_pad = -(-batch // shards) * shards
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, batch, b_pad))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, batch, b_pad))
    np.testing.assert_array_equal(  # the rows that are padding weigh nothing
        np.asarray(w_b), batches_of(np.ones(rows, np.float32), batch, b_pad)
    )


def test_rows_kept_major_keep_the_general_form():
    """What the CPU says of every table, and the TPU of one 128 wide or more."""
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    given = by_rows(mesh, X)
    assert not mesh_lib.rows_minor(given)
    (X_b, _, _), exchanged, general = lay_out(mesh, given, by_rows(mesh, y), batch=WIDE_BATCH)
    assert (exchanged, general) == (0, 2)
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH))


@pytest.mark.parametrize(
    "dtype, admitted",
    [
        (np.float32, True),
        (np.int32, True),
        (np.uint32, True),
        (jnp.bfloat16, False),
        (np.int8, False),
        (np.float64, False),
    ],
    ids=["float32", "int32", "uint32", "bfloat16", "int8", "float64"],
)
def test_only_32_bit_tables_are_admitted(dtype, admitted, rows_minor):
    mesh = data_mesh(4)
    with jax.enable_x64(True):
        table = by_rows(mesh, np.zeros((WIDE_ROWS, WIDE_DIM), dtype))
    assert table.dtype == dtype
    assert optimizer._can_exchange(table, WIDE_ROWS, WIDE_BATCH, 4, None, mesh) is admitted


@pytest.mark.parametrize(
    "width, piece, small",
    [
        (100, 25000, True),  # the benchmark's four-chip cell: sent as 104 x 25088, 4.4% more
        (40, 25000, True),  # a padded-CSR leaf of 39 fields
        (100, 8, False),  # globalBatchSize 32 on four shards: 16 times the bytes
        (100, 1000, False),  # 1000 rows sent as 1024, 100 columns as 104: 6.5% more
        (100, 1024, True),
        (5, 25088, False),  # 5 columns sent as 8
        (8, 128, True),
        (8, 129, False),  # 129 rows sent as 256
        (104, 25088, True),  # whole tiles: nothing added
    ],
)
def test_the_pad_to_whole_tiles_is_bounded(width, piece, small):
    assert optimizer._pad_is_small(width, piece) is small


def test_replicated_data_keeps_the_general_form(rows_minor):
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    (X_b, y_b, _), exchanged, general = lay_out(
        mesh, by_rows(mesh, X), by_rows(mesh, y), batch=WIDE_BATCH, replicate_data=True
    )
    assert (exchanged, general) == (0, 2)
    assert X_b.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, WIDE_BATCH))


def test_a_feature_pad_keeps_the_general_form(mesh_2d, rows_minor):
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    rows_sharded = lambda a: jax.device_put(a, mesh_lib.data_sharding(mesh_2d, a.ndim))
    (X_b, y_b, _), exchanged, general = lay_out(
        mesh_2d, rows_sharded(X), rows_sharded(y), batch=WIDE_BATCH, d_pad=WIDE_DIM + 2
    )
    assert (exchanged, general) == (0, 2)  # X is padded over the model axis
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH, d_pad=WIDE_DIM + 2))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, WIDE_BATCH))


def test_sparse_leaves_on_a_2d_mesh_take_the_exchange(mesh_2d, rows_minor):
    """The 2D sparse route: the leaves have no feature axis to shard."""
    rows_sharded = lambda a: jax.device_put(a, mesh_lib.data_sharding(mesh_2d, a.ndim))
    shards = mesh_lib.num_data_shards(mesh_2d)
    batch, rows = shards * mesh_lib.LANES, shards * shards * mesh_lib.LANES * SLAB
    indices, values = (host_column(k, rows, width=WIDE_DIM) for k in ("indices", "values"))
    y = host_column("y", rows)
    (X_b, _, _), exchanged, general = lay_out(
        mesh_2d, (rows_sharded(indices), rows_sharded(values)), rows_sharded(y), batch=batch
    )
    assert (exchanged, general) == (2, 1)
    np.testing.assert_array_equal(as_batches(X_b[0]), batches_of(indices, batch))
    np.testing.assert_array_equal(as_batches(X_b[1]), batches_of(values, batch))


def test_a_table_sharded_another_way_keeps_the_general_form(rows_minor):
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    replicated = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
    (X_b, y_b, _), exchanged, general = lay_out(mesh, replicated(X), replicated(y), batch=WIDE_BATCH)
    assert (exchanged, general) == (0, 2)
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH))
    assert X_b.sharding.is_equivalent_to(batched_sharding(mesh, 2), 3)


def dense_table(mesh, rows, width=WIDE_DIM):
    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, width)).astype(np.float32)
    y = (X @ rng.normal(size=width) > 0).astype(np.float32)
    return Table({"features": by_rows(mesh, X), "label": by_rows(mesh, y)})


def sparse_table(mesh, rows, width=WIDE_DIM):
    rng = np.random.default_rng(11)
    indices = np.sort(rng.integers(0, 40, (rows, width)).astype(np.int32), axis=1)
    values = rng.random((rows, width)).astype(np.float32)
    y = (values.sum(axis=1) > width / 2).astype(np.float32)
    features = SparseBatch(40, by_rows(mesh, indices), by_rows(mesh, values))
    return Table({"features": features, "label": by_rows(mesh, y)})


def coefficients_by_either_form(table, batch, max_iter, monkeypatch):
    """LogisticRegression fitted with the exchange taken, then with it turned
    away. `max_iter` is more than a pass: a dense fit of at most one walks
    the table's shares and lays nothing out (tests/test_layout_walk.py)."""

    def fit():
        before = metrics.snapshot()
        model = LogisticRegression().set_global_batch_size(batch).set_max_iter(max_iter).fit(table)
        counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
        return np.asarray(model.coefficient), counters

    exchanged, counters = fit()
    assert counters.get("layout.exchange", 0) >= 1 and counters.get("layout.general", 0) == 1  # y
    monkeypatch.setattr(optimizer, "_can_exchange", lambda *args: False)
    general, counters = fit()
    assert counters.get("layout.general", 0) >= 2 and "layout.exchange" not in counters
    assert np.all(np.isfinite(exchanged)) and np.any(exchanged != 0)
    return exchanged, general


@pytest.mark.parametrize("make_table", [dense_table, sparse_table], ids=["dense", "sparse"])
def test_four_shard_fit_is_bit_identical_by_either_form(make_table, rows_minor, monkeypatch):
    mesh = data_mesh(4)
    with mesh_lib.use_mesh(mesh):
        more_than_a_pass = WIDE_ROWS // WIDE_BATCH + 4
        exchanged, general = coefficients_by_either_form(make_table(mesh, WIDE_ROWS), WIDE_BATCH, more_than_a_pass, monkeypatch)
    np.testing.assert_array_equal(exchanged, general)


# a strip neither of whose sides is whole tiles: 31 columns are sent as 32,
# 127 rows as 128, so the staged copy holds pad rows and the strips pad columns
RAGGED_DIM, RAGGED_PIECE = 31, 127


@pytest.mark.parametrize("make_table", [dense_table, sparse_table], ids=["dense", "sparse"])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_ragged_strips_fit_bit_identical_by_either_form(shards, make_table, rows_minor, monkeypatch):
    """The pad to whole tiles adds no row and no column to any batch: the
    same weight sum, the same loss, so the same coefficient to the bit."""
    assert optimizer._padded_strip(RAGGED_DIM, RAGGED_PIECE) == (32, 128)
    mesh = data_mesh(shards)
    batch = shards * RAGGED_PIECE
    with mesh_lib.use_mesh(mesh):
        table = make_table(mesh, shards * batch * SLAB, RAGGED_DIM)
        exchanged, general = coefficients_by_either_form(table, batch, shards * SLAB + 3, monkeypatch)  # a pass and three
    np.testing.assert_array_equal(exchanged, general)


def test_the_exchanged_table_is_strips_and_every_other_array_is_not(rows_minor):
    """The training loop finds its batch by the array's own form."""
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=5), host_column("y", WIDE_ROWS)
    wide = host_column("X", WIDE_ROWS, width=WIDE_DIM)
    (X_b, y_b, w_b), exchanged, _ = lay_out(mesh, by_rows(mesh, wide), by_rows(mesh, y), batch=WIDE_BATCH)
    assert exchanged == 1 and isinstance(X_b, optimizer.BatchStrips)
    assert X_b.strips.shape == (WIDE_ROWS // WIDE_BATCH, 4, WIDE_DIM, WIDE_BATCH // 4)
    assert isinstance(y_b, jax.Array) and isinstance(w_b, jax.Array)
    (X_b, _, _), exchanged, _ = lay_out(mesh, by_rows(mesh, X), by_rows(mesh, y), batch=WIDE_BATCH)
    assert exchanged == 0 and isinstance(X_b, jax.Array)
    # batch k by either form, under jit: the same rows
    strips = optimizer._exchange_batches(by_rows(mesh, wide), WIDE_BATCH, mesh)
    for k in (0, 5, 4 * SLAB - 1):
        got = jax.jit(optimizer._index_batch)(strips, k)
        np.testing.assert_array_equal(np.asarray(got), batches_of(wide, WIDE_BATCH)[k])


# --- compiled for the chip, at the benchmark's size; nothing runs ---------


@pytest.fixture(scope="module")
def four_v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices), (mesh_lib.DATA_AXIS,))


def compiled_for(devices, fn, shape, dtype, **statics):
    table = jax.ShapeDtypeStruct(shape, dtype, sharding=mesh_lib.data_sharding(devices, 2))
    return jax.jit(fn, static_argnames=tuple(statics)).lower(table, **statics).compile()


CELL_BATCH = 100_000
COMPILED_TABLES = pytest.mark.parametrize(
    "rows, width, dtype",
    [
        (32_000_000, 100, np.float32),
        (48_000_000, 100, np.float32),
        (32_000_000, 40, np.int32),
        (32_000_000, 40, np.float32),
    ],
    ids=["benchmark_cell", "48m_rows", "csr_indices", "csr_values"],
)


@cache
def compiled_exchange(mesh, rows, width, dtype):
    return compiled_for(
        mesh, optimizer._exchange_batches_impl, (rows, width), dtype, batch=CELL_BATCH, mesh=mesh
    )


def instructions(compiled):
    """[(name, opcode)] of a compiled program's HLO, fusions' insides left out."""
    found, inside_fusion = [], False
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace():
            inside_fusion = "fused_computation" in line
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(", line)
        if m and not inside_fusion:
            found.append((m.group(1), m.group(2)))
    return found


@COMPILED_TABLES
def test_compiled_for_four_v5e_the_exchange_is_one_all_to_all_by_xlas_name(four_v5e, rows, width, dtype):
    """A device trace's readers find collective time by XLA's own name of
    the op (`all-to-all`, perf/tracereduce.py); the table is never split
    along its rows by a loop over its columns; the temporaries stay under
    the general form's."""
    sharding = batched_sharding(four_v5e, 2)
    exchange = compiled_exchange(four_v5e, rows, width, dtype)
    ops = instructions(exchange)
    exchanges = [name for name, opcode in ops if opcode == "all-to-all"]
    assert len(exchanges) == 1 and exchanges[0].startswith("all-to-all")
    assert sum(opcode == "while" for _, opcode in ops) == 2  # the slabs, and a slab's strips
    general = compiled_for(
        four_v5e, optimizer._layout_batches_impl, (rows, width), dtype,
        n=rows, num_batches=rows // CELL_BATCH, batch=CELL_BATCH, b_pad=CELL_BATCH, d_pad=None, sharding=sharding,
    )
    assert exchange.memory_analysis().temp_size_in_bytes < 0.7 * general.memory_analysis().temp_size_in_bytes


@COMPILED_TABLES
def test_compiled_for_four_v5e_every_slab_arrives_in_its_final_place(four_v5e, rows, width, dtype):
    """The strips are kept [batch][shard][column][row] in whole (8, 128) tiles
    by the device's own default (no layout is asked for: a custom one does not
    survive this installation's persistent compile cache), and they are the
    buffer the loop of slabs writes into: no copy of the share puts the
    batches in order or cuts the pad afterwards."""
    exchange = compiled_exchange(four_v5e, rows, width, dtype)
    layout = exchange.output_formats.strips.layout
    assert tuple(layout.major_to_minor) == (0, 1, 2, 3) and tuple(layout.tiling) == ((8, 128),)
    memory = exchange.memory_analysis()
    width_pad, piece_pad = optimizer._padded_strip(width, CELL_BATCH // 4)
    assert memory.output_size_in_bytes == rows // CELL_BATCH * width_pad * piece_pad * 4  # the pad is kept
    assert memory.temp_size_in_bytes < 0.5 * memory.output_size_in_bytes  # two slabs staged, no second copy
    after_the_loops = instructions(exchange)
    after_the_loops = after_the_loops[max(i for i, (_, opcode) in enumerate(after_the_loops) if opcode == "while") + 1:]
    assert {opcode for _, opcode in after_the_loops} <= {"get-tuple-element", "bitcast"}


TABLE_TYPE = re.compile(r"\w+\[([\d,]*)\]\{([\d,]*)(?::T\((\d+),(\d+)\))?")
HANDS_ON = {"parameter", "tuple", "get-tuple-element", "while", "fusion", "bitcast", "conditional", "call"}


def bytes_touched(compiled, dims):
    """{instruction: bytes of memory it touches} for every instruction of a
    compiled program that reads a 32-bit array of `dims`, fusions' insides
    included, and does more than hand it on. A `dynamic-slice` touches whole
    tiles of the array as the device keeps it: one batch of a table kept
    [column][batch][row] shares each (8, 128) tile with seven others."""
    found = {}
    for computation in re.split(r"\n(?=\S)", compiled.as_text()):
        types = {}
        for line in computation.splitlines():
            m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (\S+) ([a-z][\w\-]*)\((.*)", line)
            if not m:
                continue
            name, kept, opcode, operands = m.groups()
            types[name] = kept
            if opcode in HANDS_ON:
                continue
            for operand in re.findall(r"%([\w.\-]+)", operands.split("), ")[0]):
                t = TABLE_TYPE.match(types.get(operand, ""))
                if not t or [int(d) for d in t.group(1).split(",") if d] != list(dims):
                    continue
                read = list(dims)
                if opcode == "dynamic-slice":
                    read = [int(d) for d in re.search(r"dynamic_slice_sizes=\{([\d,]+)\}", line).group(1).split(",")]
                if t.group(3):  # tiles lie over the two minor-most axes in memory
                    for axis, tile in zip((int(d) for d in t.group(2).split(",")), (int(t.group(4)), int(t.group(3)))):
                        read[axis] = -(-read[axis] // tile) * tile
                found[name] = 4 * math.prod(read)
    return found


def compiled_train(mesh, loss_func, dim, X_b):
    """`_sgd_train` for laid-out features described by `X_b` (a pytree of
    ShapeDtypeStructs), y and weights in the general form's array."""
    nb, batch, _ = (X_b[0] if isinstance(X_b, tuple) else X_b).shape
    y_b = jax.ShapeDtypeStruct((nb, batch), np.float32, sharding=batched_sharding(mesh, 1))
    whole = NamedSharding(mesh, P())
    coefficient = jax.ShapeDtypeStruct((dim,), np.float32, sharding=whole)
    hyper = jax.ShapeDtypeStruct((5,), np.float32, sharding=whole)
    train = lambda X, y, w, c, h: optimizer._sgd_train(X, y, w, c, loss_func, h, True, None)
    return jax.jit(train).lower(X_b, y_b, y_b, coefficient, hyper).compile()


def laid_out(exchange):
    """What the exchange's executable hands on, as the train program's input."""
    return jax.tree_util.tree_map(
        lambda out, kept: jax.ShapeDtypeStruct(out.shape, out.dtype, sharding=kept),
        exchange.out_info, exchange.output_formats,
    )


@pytest.mark.parametrize("rows", [32_000_000, 48_000_000], ids=["benchmark_cell", "48m_rows"])
def test_compiled_for_four_v5e_the_dense_epoch_reads_its_batch_where_it_lies(four_v5e, rows):
    """Batch k is read once an epoch, whole tiles of one batch and no other's,
    and the row-dot and the gradient share what was read. From the general
    form's array, which the v5e keeps [column][batch][row], the one slice
    touches eight batches (the 34 ms a fit this PR removed)."""
    X_b = laid_out(compiled_exchange(four_v5e, rows, 100, np.float32))
    assert X_b.width == 100 and X_b.shape == (rows // CELL_BATCH, CELL_BATCH, 100)
    share = (rows // CELL_BATCH, 1, 104, CELL_BATCH // 4)
    batch_bytes = CELL_BATCH // 4 * 100 * 4
    touched = bytes_touched(compiled_train(four_v5e, losses.BINARY_LOGISTIC_LOSS, 100, X_b), share)
    assert touched and all(name.startswith("dynamic-slice") for name in touched)
    assert sum(touched.values()) <= 2.5 * batch_bytes
    if rows == 32_000_000:
        general = jax.ShapeDtypeStruct(X_b.shape, X_b.dtype, sharding=batched_sharding(four_v5e, 2))
        share = (rows // CELL_BATCH, CELL_BATCH // 4, 100)
        touched = bytes_touched(compiled_train(four_v5e, losses.BINARY_LOGISTIC_LOSS, 100, general), share)
        assert max(touched.values()) > 7 * batch_bytes


def test_compiled_for_four_v5e_the_sparse_epoch_reads_its_batch_where_it_lies(four_v5e):
    """Both padded-CSR leaves, 39 hashed fields sent as 40, a million coefficients."""
    rows = 32_000_000
    X_b = tuple(laid_out(compiled_exchange(four_v5e, rows, 40, dtype)) for dtype in (np.int32, np.float32))
    share = (rows // CELL_BATCH, 1, 40, CELL_BATCH // 4)
    batch_bytes = CELL_BATCH // 4 * 40 * 4
    touched = bytes_touched(compiled_train(four_v5e, losses.SPARSE_BINARY_LOGISTIC_LOSS, 1_000_000, X_b), share)
    assert len(touched) == 2 and all(name.startswith("dynamic-slice") for name in touched)  # one a leaf
    assert max(touched.values()) <= 1.1 * batch_bytes


# --- one chip: the flat loop's dense epoch as one read (ops/dense_epoch.py) ------


def compiled_flat(four_v5e, rows, loss_func, one_pass, has_weights=False, chip=0, carry=False):
    """`_sgd_train_flat` for a 100-wide float32 table on ONE described v5e;
    with `carry`, as a leg of a walked fit on chip `chip`."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(four_v5e.devices.flat[chip])

    def on_chip(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    train = lambda X, y, w, c, n, h, carry: optimizer._sgd_train_flat(
        X, y, w, c, loss_func, CELL_BATCH, has_weights, n, h, True, one_pass, False, carry=carry
    )
    weights = on_chip((rows if has_weights else 0,))
    return jax.jit(train).lower(
        on_chip((rows, 100)), on_chip((rows,)), weights, None if carry else on_chip((100,)), on_chip((), np.int32),
        on_chip((5,)), on_chip((204,)) if carry else None,
    ).compile()


@pytest.mark.parametrize(
    "rows, loss, has_weights",
    [
        (20_000_000, "BINARY_LOGISTIC_LOSS", False),  # the benchmark's cell
        (1_000_000, "HINGE_LOSS", True),  # chip_smoke's; 1e6 rows are no whole tiles of anything
        (1_000_000, "LEAST_SQUARE_LOSS", False),
    ],
    ids=["benchmark_cell", "hinge_weighted_1m", "least_square_1m"],
)
def test_compiled_for_a_v5e_the_one_read_epoch_copies_no_table(four_v5e, rows, loss, has_weights):
    """The kernel is in the flat program (one `tpu_custom_call`, under the
    kernel's name), it is handed the table the other way round as the same
    bytes (a bitcast of the parameter, which the v5e keeps rows-minor), and
    the program holds no temporary the size of the table or of a column; the
    reduce form's program holds no kernel."""
    one_read = compiled_flat(four_v5e, rows, getattr(losses, loss), True, has_weights)
    text = one_read.as_text()
    assert f"f32[{rows},100]{{0,1:T(8,128)}} parameter(0)" in text  # rows-minor, by the device's own default
    calls = [(name, opcode) for name, opcode in instructions(one_read) if opcode == "custom-call"]
    assert len(calls) == 1 and calls[0][0].startswith("dense_epoch_one_pass") and "tpu_custom_call" in text
    assert re.search(rf"f32\[100,{rows}\]{{1,0:T\(8,128\)}} bitcast\(", text)
    assert not re.search(rf"= \w+\[[\d,]*{rows}[\d,]*\]\S* (copy|transpose)\(", text)
    memory = one_read.memory_analysis()
    assert memory.argument_size_in_bytes >= rows * 104 * 4
    assert memory.temp_size_in_bytes < 2 << 20  # a column of 1e6 rows is 4 MB, the table 416
    if rows == 20_000_000:
        reduce_form = compiled_flat(four_v5e, rows, getattr(losses, loss), False)
        assert not any(opcode == "custom-call" for _, opcode in instructions(reduce_form))


@pytest.mark.parametrize("chip, has_weights", [(0, False), (3, False), (2, True)], ids=["first_chip", "last_chip", "weighted"])
def test_compiled_for_a_v5e_a_leg_of_a_walked_fit_is_the_one_read_epoch_over_the_share_where_it_lies(
    four_v5e, chip, has_weights
):
    """The four-chip cell's walked fit (`SGD._stage_walk`): a leg is the flat
    program over one chip's share of 8M rows, whichever chip holds it; it
    holds the one-read kernel, copies neither the share nor a column of it,
    takes the state of the leg before as one vector of 2 * 100 + 4 numbers
    and hands its own on as one; the finish that follows the last leg is a
    program of a few hundred numbers."""
    rows = 32_000_000 // 4
    leg = compiled_flat(four_v5e, rows, losses.BINARY_LOGISTIC_LOSS, True, has_weights, chip, carry=True)
    text = leg.as_text()
    assert f"f32[{rows},100]{{0,1:T(8,128)}} parameter(0)" in text
    calls = [(name, opcode) for name, opcode in instructions(leg) if opcode == "custom-call"]
    assert len(calls) == 1 and calls[0][0].startswith("dense_epoch_one_pass")
    assert not re.search(rf"= \w+\[[\d,]*{rows}[\d,]*\]\S* (copy|transpose)\(", text)
    memory = leg.memory_analysis()
    assert memory.temp_size_in_bytes < 2 << 20
    assert memory.output_size_in_bytes <= 1024  # 204 floats handed on
    parameters = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(\d\)", text.split("ENTRY", 1)[1])
    assert "f32[204]" in parameters and "f32[100]" not in parameters
    from jax.sharding import SingleDeviceSharding

    on_chip = lambda shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=SingleDeviceSharding(four_v5e.devices.flat[chip]))  # noqa: E731
    finish = jax.jit(lambda carry, hyper: optimizer._finish_walk(carry, hyper, np.dtype(np.float32), True))
    finish = finish.lower(on_chip((204,)), on_chip((5,))).compile()
    assert finish.memory_analysis().output_size_in_bytes <= 512  # flag, 100 coefficients, criteria, epochs
    assert not any(opcode == "custom-call" for _, opcode in instructions(finish))


def test_compiled_for_a_v5e_the_one_shard_fit_is_the_same_program_with_its_small_inputs_from_the_host(four_v5e):
    """The one-chip cell's fit hands `_sgd_train_flat` its row count, weight
    placeholder and start coefficient as host values (`SGD._stage_flat`),
    which jit places with the launch: the program compiled so holds the
    instructions of the one handed them on the chip, line for line, but for
    the annotations that say where a parameter lies."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(four_v5e.devices.flat[0])
    rows = 20_000_000

    def compiled(placed):
        small = dict(sharding=chip) if placed else {}
        train = lambda X, y, w, c, n, h: optimizer._sgd_train_flat(  # noqa: E731
            X, y, w, c, losses.BINARY_LOGISTIC_LOSS, CELL_BATCH, False, n, h, True, True, False
        )
        return jax.jit(train).lower(
            jax.ShapeDtypeStruct((rows, 100), np.float32, sharding=chip),
            jax.ShapeDtypeStruct((rows,), np.float32, sharding=chip),
            jax.ShapeDtypeStruct((0,), np.float32, **small),
            jax.ShapeDtypeStruct((100,), np.float32, **small),
            jax.ShapeDtypeStruct((), np.int32, **small),
            jax.ShapeDtypeStruct((5,), np.float32, sharding=chip),
        ).compile().as_text()

    def lines(text):
        """The instructions, where each parameter lies and the source lines left out."""
        placement = r", sharding=\{[^}]*\}, frontend_attributes=\{xla\.sdy\.sharding=\"[^\"]*\"\}"
        return [
            re.sub(r", metadata=\{[^}]*\}", "", re.sub(placement, "", line))
            for line in text.splitlines()
            if " = " in line and not line.startswith("HloModule")
        ]

    from_host, placed = compiled(False), compiled(True)
    assert from_host != placed  # the parameters' annotations differ ...
    assert lines(from_host) == lines(placed)  # ... and nothing else
    assert len([line for line in lines(from_host) if "tpu_custom_call" in line]) == 1


# --- one chip: the Lloyd loop's cross term by the pieces its points have (ops/distance.py) ------


def compiled_lloyd(four_v5e, point_pieces):
    """`_lloyd_fit_impl` at the k-means cell's size, 2.7M x 784 rows against
    4,096 centroids, on ONE described v5e."""
    from jax.sharding import SingleDeviceSharding

    from flink_ml_tpu.models.clustering import kmeans as km

    chip = SingleDeviceSharding(four_v5e.devices.flat[0])
    fit = lambda X, init, max_iter: km._lloyd_fit_impl(X, None, init, max_iter, "euclidean", None, point_pieces)
    return jax.jit(fit).lower(
        jax.ShapeDtypeStruct((2_700_000, 784), np.float32, sharding=chip),
        jax.ShapeDtypeStruct((4096, 784), np.float32, sharding=chip),
        jax.ShapeDtypeStruct((), np.int32, sharding=chip),
    ).compile()


def products(compiled):
    """[(operand element types, the instruction's line)] of every matrix
    product of a compiled program, which the TPU's compiler writes as a
    convolution inside a fusion."""
    text = compiled.as_text()
    found = []
    for line in text.splitlines():
        m = re.search(r" convolution\(%([\w.\-]+), %([\w.\-]+)\)", line)
        if m:
            types = [re.search(rf"%{re.escape(name)} = (\w+)\[", text).group(1) for name in m.groups()]
            found.append((types, line))
    return found


def test_compiled_for_a_v5e_the_short_cross_term_is_bfloat16_products_and_no_float32_one(four_v5e):
    """The points known to be one piece: the block's bfloat16 rows against
    the centroids' three bfloat16 pieces in ONE product (the pieces a second
    contracted axis of three, added in the unit's accumulators), no float32
    operand and no six-pass product anywhere; the pieces are rounded by an op
    the compiler keeps; no temporary near the table's size. Not known to be:
    the one float32 product at `highest`, as it was."""
    from flink_ml_tpu.ops.distance import ALL_PIECES, ONE_PIECE

    short = compiled_lloyd(four_v5e, ONE_PIECE)
    ((types, line),) = products(short)
    assert types == ["bf16", "bf16"] and "window={size=3}" in line and "operand_precision" not in line
    assert "= f32[6656,4096," in line and "highest" not in short.as_text()
    assert len(re.findall(r" reduce-precision\(", short.as_text())) == 2  # hi, and mid of what hi left
    memory = short.memory_analysis()
    assert memory.argument_size_in_bytes >= 2_700_000 * 784 * 4
    assert memory.temp_size_in_bytes < 256 << 20
    ((types, line),) = products(compiled_lloyd(four_v5e, ALL_PIECES))
    assert types == ["f32", "f32"] and "operand_precision={highest,highest}" in line


def test_compiled_for_a_v5e_the_look_is_one_pass_with_no_temporary(four_v5e):
    from jax.sharding import SingleDeviceSharding

    from flink_ml_tpu.models.clustering import kmeans as km

    table = jax.ShapeDtypeStruct((2_700_000, 784), np.float32, sharding=SingleDeviceSharding(four_v5e.devices.flat[0]))
    look = jax.jit(km._exact_in_bfloat16_impl).lower(table).compile()
    assert " reduce-precision(" in look.as_text() and " convert(" not in look.as_text()
    assert look.memory_analysis().temp_size_in_bytes < 1 << 20


def compiled_fleet(mesh, members, in_place, loss=losses.BINARY_LOGISTIC_LOSS):
    """`_sgd_fleet_whole_fit_impl` for one v5e chip over the path cell's
    table, 20M x 100 in batches of 100,000: the caller's table viewed where it
    lies (`FlatBatches`), or laid out first."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(mesh.devices.flat[0])
    rows, width, batches = 20_000_000, 100, 200

    def on_chip(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fit(X, y_b, w_b, carry, criteria, hyper):
        X_b = optimizer.FlatBatches(X, CELL_BATCH) if in_place else X
        return optimizer._sgd_fleet_whole_fit_impl(
            X_b, y_b, w_b, carry, criteria, loss, hyper, True, None
        )

    carry = (on_chip((members, width)), on_chip((members, width)), on_chip((members,)), on_chip((members,), np.int32))
    table = on_chip((rows, width) if in_place else (batches, CELL_BATCH, width))
    return jax.jit(fit).lower(
        table, on_chip((batches, CELL_BATCH)), on_chip((batches, CELL_BATCH)), carry, on_chip((members,)), on_chip((members, 5))
    ).compile()


@pytest.mark.parametrize("in_place", [True, False], ids=["in_place", "laid_out"])
def test_compiled_for_a_v5e_a_fleet_of_a_hundred_reads_one_batch_an_epoch_and_copies_no_table(four_v5e, in_place):
    """The path cell's program (`lr-regpath-100.path`): the table is the
    parameter as the device keeps it (rows-minor for the flat view), no copy
    and no gather of it, and the temporaries are a batch and the members'
    products of it. Indexed by each member's own epoch the same program asked
    for 22 GB of a 15.75 GB chip: the table copied rows-major for a gather
    that made a hundred copies of the batch."""
    fleet = compiled_fleet(four_v5e, 100, in_place)
    text = fleet.as_text()
    if in_place:
        assert "f32[20000000,100]{0,1:T(8,128)} parameter(0)" in text
    assert " gather(" not in text
    assert not re.search(r"= f32\[(20000000,100|200,100000,100)\]\S* (copy|transpose)\(", text)
    memory = fleet.memory_analysis()
    assert memory.argument_size_in_bytes >= 20_000_000 * 100 * 4
    assert memory.temp_size_in_bytes < 256 << 20  # a batch is 42 MB as the device keeps it, the products 41


@pytest.mark.parametrize("in_place", [True, False], ids=["in_place", "laid_out"])
def test_compiled_for_a_v5e_the_fleets_matrix_form_is_two_products_on_the_batch_where_it_lies(four_v5e, in_place):
    """What a fleet on the chip is handed (`losses.product_variant`,
    PR 40): the members' row-dots and gradients are two `convolution`s at
    HIGHEST, the batch's slice fused into their operands, with no copy or
    transpose of the table or of a batch for a transposed operand, and no
    product of the members with the batch ([100, 100000, 100]) formed."""
    fleet = compiled_fleet(four_v5e, 100, in_place, losses.product_variant(losses.BINARY_LOGISTIC_LOSS))
    text = fleet.as_text()
    products = re.findall(r"= f32\[(\d+,\d+)\]\S* convolution\(.*operand_precision=\{highest,highest\}", text)
    assert sorted(products) == ["100,100", "100,100000"]
    assert " gather(" not in text and "f32[100,100000,100]" not in text
    assert not re.search(r"= f32\[(20000000,100|200,100000,100|100000,100)\]\S* (copy|transpose)\(", text)
    memory = fleet.memory_analysis()
    assert memory.argument_size_in_bytes >= 20_000_000 * 100 * 4
    assert memory.temp_size_in_bytes < 256 << 20


def test_compiled_for_a_v5e_the_plan_reads_the_rows_a_fit_reaches_where_they_lie(four_v5e):
    """The sparse path cell's plan (`sparse_epoch._column_dictionaries`): a
    fleet of 20 epochs of 100,000 rows reads 2M of the 28M resident rows of
    39 fields. The table is the parameter as the device keeps it, rows-minor,
    its columns a view; neither it nor the read rows are copied or
    transposed, and the temporaries are a column's and the sample's (the
    plan over all 28M rows held 225 MB)."""
    from jax.sharding import SingleDeviceSharding

    from flink_ml_tpu.ops import sparse_epoch

    table = jax.ShapeDtypeStruct((28_000_000, 39), np.int32, sharding=SingleDeviceSharding(four_v5e.devices.flat[0]))
    plan = jax.jit(sparse_epoch._column_dictionaries, static_argnames=("rows",)).lower(table, rows=2_000_000).compile()
    text = plan.as_text()
    assert "s32[28000000,39]{0,1:T(8,128)} parameter(0)" in text
    assert not re.search(r"= s32\[(28000000,39|39,28000000|2000000,39|39,2000000)\]\S* (copy|transpose|fusion)\(", text)
    memory = plan.memory_analysis()
    assert memory.argument_size_in_bytes >= 28_000_000 * 39 * 4
    assert memory.temp_size_in_bytes < 64 << 20


# --- one chip: the online FTRL step writes the slots it changes (ops/slot_write.py) ------


STREAM_DIM = 204_184_601  # the stream cell's coordinates


def compiled_ftrl_touched(four_v5e, writer):
    """`_ftrl_touched` for the stream cell's batch (4,096 rows of 39 entries)
    on ONE described v5e, z and n donated as the online loop donates them."""
    from jax.sharding import SingleDeviceSharding

    from flink_ml_tpu.models.classification import onlinelogisticregression as olr

    chip = SingleDeviceSharding(four_v5e.devices.flat[0])

    def on_chip(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    step = jax.jit(olr._ftrl_touched, static_argnames=("writer",), donate_argnames=("z", "n"))
    state = on_chip((STREAM_DIM,))
    return step.lower(
        state, state, state, on_chip((4096, 39), np.int32), on_chip((4096, 39)), on_chip((4096,)), on_chip((4,)), writer=writer
    ).compile()


def test_compiled_for_a_v5e_the_ftrl_step_writes_its_slots_and_passes_over_the_coefficient_once(four_v5e):
    """With the slot writer the step copies no model-sized vector and scatters
    into none on the quarter's side, the stream cell's: there z and n are
    written in place by one kernel (their partial last tile by an in-place
    update of its 25 elements). The fresh coefficient the record needs is made
    by one kernel that passes over the old one once, on either side (XLA's
    form copies it, then scatters into the copy). z and n keep XLA's in-place
    scatters on the side of all the entries alone."""
    full = rf"f32\[{STREAM_DIM}\]"
    kernel = compiled_ftrl_touched(four_v5e, "kernel")
    text = kernel.as_text()
    assert not re.search(rf"{full}\S* copy\(", text)
    calls = [name.rsplit(".", 1)[0] for name, opcode in instructions(kernel) if opcode == "custom-call"]
    assert sorted(name for name in calls if name.startswith("slot_write")) == ["slot_write_copy", "slot_write_in_place"]
    assert re.search(rf"= {full}\S* custom-call\(", text)  # the one pass that makes the record's buffer
    updates = re.findall(rf"= {full}\S* fusion\(.*op_name=\"jit\(_ftrl_touched\)/([^\"]*)\"", text)
    assert sorted(updates) == ["cond/branch_0_fun/scatter"] * 2 + ["cond/branch_1_fun/dynamic_update_slice"] * 2
    assert 'op_name="jit(_ftrl_touched)/cond/branch_1_fun/slot_write_in_place/pallas_call"' in text  # the quarter's side
    assert "input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }" in text
    memory = kernel.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * STREAM_DIM * 4  # z and n where they lie
    assert memory.temp_size_in_bytes < 64 << 20
