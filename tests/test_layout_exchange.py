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
   exchange holds one all-to-all under XLA's own name, in a loop of slabs.

The CPU keeps every table rows-major, so `_can_exchange` never admits one
here: the tests that need the exchange taken tell `mesh_lib.rows_minor` to
say what the TPU says of a narrow table.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification.logisticregression import LogisticRegression
from flink_ml_tpu.ops import optimizer
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
    got = exchange(given, BATCH, sharding)
    assert got.shape == general.shape and got.dtype == general.dtype
    assert got.sharding == general.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(general))
    np.testing.assert_array_equal(np.asarray(got), batches_of(host, BATCH))
    # every shard holds its own rows of every batch, as the training programs expect
    piece = BATCH // shards
    for shard in got.addressable_shards:
        assert shard.data.shape[:2] == (rows // BATCH, piece)
    if not owned:
        np.testing.assert_array_equal(np.asarray(given), host)  # a borrowed input is left alone


def test_the_exchange_is_one_accounted_all_to_all_in_a_loop_of_slabs():
    mesh = data_mesh(2)
    host = host_column("X", 2 * BATCH * SLAB * 2)
    sharding = batched_sharding(mesh, 2)
    before = metrics.snapshot()
    fn = jax.jit(  # a jit of its own: the module's may have this trace cached
        lambda arr: optimizer._exchange_batches_impl(arr, BATCH, sharding)
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
        np.testing.assert_array_equal(np.asarray(got), batches_of(host, WIDE_BATCH))
    assert X_b.sharding.is_equivalent_to(batched_sharding(mesh, 2), 3)
    assert y_b.sharding.is_equivalent_to(batched_sharding(mesh, 1), 2)


def test_sparse_leaves_take_the_exchange(rows_minor):
    mesh = data_mesh(4)
    indices, values = (host_column(k, WIDE_ROWS, width=WIDE_DIM) for k in ("indices", "values"))
    y = host_column("y", WIDE_ROWS)
    (X_b, y_b, _), exchanged, general = lay_out(
        mesh, (by_rows(mesh, indices), by_rows(mesh, values)), by_rows(mesh, y), batch=WIDE_BATCH
    )
    assert (exchanged, general) == (2, 1)  # both leaves; y; the default weights are made in place
    np.testing.assert_array_equal(np.asarray(X_b[0]), batches_of(indices, WIDE_BATCH))
    np.testing.assert_array_equal(np.asarray(X_b[1]), batches_of(values, WIDE_BATCH))
    np.testing.assert_array_equal(np.asarray(y_b), batches_of(y, WIDE_BATCH))


def test_host_table_that_divides_is_staged_by_rows_and_exchanged(rows_minor):
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    (X_b, y_b, _), exchanged, general = lay_out(mesh, X, y, batch=WIDE_BATCH)  # numpy in: staged, owned, donated
    assert (exchanged, general) == (1, 1)
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH))
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
    np.testing.assert_array_equal(np.asarray(X_b[0]), batches_of(indices, batch))
    np.testing.assert_array_equal(np.asarray(X_b[1]), batches_of(values, batch))


def test_a_table_sharded_another_way_keeps_the_general_form(rows_minor):
    mesh = data_mesh(4)
    X, y = host_column("X", WIDE_ROWS, width=WIDE_DIM), host_column("y", WIDE_ROWS)
    replicated = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
    (X_b, y_b, _), exchanged, general = lay_out(mesh, replicated(X), replicated(y), batch=WIDE_BATCH)
    assert (exchanged, general) == (0, 2)
    np.testing.assert_array_equal(np.asarray(X_b), batches_of(X, WIDE_BATCH))
    assert X_b.sharding.is_equivalent_to(batched_sharding(mesh, 2), 3)


def dense_table(mesh, rows):
    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, WIDE_DIM)).astype(np.float32)
    y = (X @ rng.normal(size=WIDE_DIM) > 0).astype(np.float32)
    return Table({"features": by_rows(mesh, X), "label": by_rows(mesh, y)})


def sparse_table(mesh, rows):
    rng = np.random.default_rng(11)
    indices = np.sort(rng.integers(0, 40, (rows, WIDE_DIM)).astype(np.int32), axis=1)
    values = rng.random((rows, WIDE_DIM)).astype(np.float32)
    y = (values.sum(axis=1) > WIDE_DIM / 2).astype(np.float32)
    features = SparseBatch(40, by_rows(mesh, indices), by_rows(mesh, values))
    return Table({"features": features, "label": by_rows(mesh, y)})


@pytest.mark.parametrize("make_table", [dense_table, sparse_table], ids=["dense", "sparse"])
def test_four_shard_fit_is_bit_identical_by_either_form(make_table, rows_minor, monkeypatch):
    mesh = data_mesh(4)
    with mesh_lib.use_mesh(mesh):
        table = make_table(mesh, WIDE_ROWS)

        def fit():
            before = metrics.snapshot()
            model = LogisticRegression().set_global_batch_size(WIDE_BATCH).set_max_iter(12).fit(table)
            counters = metrics.snapshot_delta(before, metrics.snapshot())["counters"]
            return np.asarray(model.coefficient), counters

        exchanged, counters = fit()
        assert counters.get("layout.exchange", 0) >= 1 and counters.get("layout.general", 0) == 1  # y
        monkeypatch.setattr(optimizer, "_can_exchange", lambda *args: False)
        general, counters = fit()
        assert counters.get("layout.general", 0) >= 2 and "layout.exchange" not in counters
    assert np.all(np.isfinite(exchanged)) and np.any(exchanged != 0)
    np.testing.assert_array_equal(exchanged, general)


# --- compiled for the chip, at the benchmark's size; nothing runs ---------


@pytest.fixture(scope="module")
def four_v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices), (mesh_lib.DATA_AXIS,))


def compiled_for(mesh, fn, shape, dtype, **statics):
    table = jax.ShapeDtypeStruct(shape, dtype, sharding=mesh_lib.data_sharding(mesh, 2))
    return jax.jit(fn, static_argnames=tuple(statics)).lower(table, **statics).compile()


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


@pytest.mark.parametrize(
    "rows, width, dtype",
    [
        (32_000_000, 100, np.float32),
        (48_000_000, 100, np.float32),
        (32_000_000, 40, np.int32),
        (32_000_000, 40, np.float32),
    ],
    ids=["benchmark_cell", "48m_rows", "csr_indices", "csr_values"],
)
def test_compiled_for_four_v5e_the_exchange_is_one_all_to_all_by_xlas_name(four_v5e, rows, width, dtype):
    """A device trace's readers find collective time by XLA's own name of
    the op (`all-to-all`, perf/tracereduce.py); the table is never split
    along its rows by a loop over its columns; the temporaries stay under
    the general form's."""
    batch = 100_000
    sharding = batched_sharding(four_v5e, 2)
    exchange = compiled_for(
        four_v5e, optimizer._exchange_batches_impl, (rows, width), dtype, batch=batch, sharding=sharding
    )
    ops = instructions(exchange)
    exchanges = [name for name, opcode in ops if opcode == "all-to-all"]
    assert len(exchanges) == 1 and exchanges[0].startswith("all-to-all")
    assert sum(opcode == "while" for _, opcode in ops) == 2  # the slabs, and a slab's strips
    general = compiled_for(
        four_v5e, optimizer._layout_batches_impl, (rows, width), dtype,
        n=rows, num_batches=rows // batch, batch=batch, b_pad=batch, d_pad=None, sharding=sharding,
    )
    assert exchange.memory_analysis().temp_size_in_bytes < 0.7 * general.memory_analysis().temp_size_in_bytes
