"""Benchmark data generators — param-driven random table sources.

TPU-native re-design of flink-ml-benchmark/.../datagenerator/ (
DataGenerator.java, InputDataGenerator.java:NUM_VALUES/COL_NAMES/SEED,
common/DenseVectorGenerator.java, DenseVectorArrayGenerator.java,
DoubleGenerator.java, LabeledPointWithWeightGenerator.java,
RandomStringGenerator.java, RandomStringArrayGenerator.java,
clustering/KMeansModelDataGenerator.java). Same param names/JSON configs;
generation is vectorized numpy instead of per-row Flink sources.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ..common.param import HasSeed
from ..param import IntParam, LongParam, Param, ParamValidators
from ..table import DictTokenMatrix, Table
from ..utils.lazyjit import lazy_jit

# Rows at or above this threshold are generated directly in device HBM with
# jax.random — the analogue of the reference generating data *inside* the
# cluster (InputTableGenerator.java runs as a Flink source, not a client
# upload). Below it, numpy keeps tiny test tables host-side and cheap.
# NOTE: the two paths draw from different RNGs, so a fixed seed yields
# different values (and float32 vs float64) across the threshold. Set
# FLINK_ML_TPU_DEVICE_DATAGEN=0 to force the numpy path at every size when
# cross-size seeded reproducibility matters more than ingest speed.
# The value is low on purpose — a host-born table costs an upload at fit
# time while device generation is an async dispatch once compiled — but
# it was chosen on an installation that is gone: to be re-measured
# (ROADMAP.md S2).
DEVICE_GEN_THRESHOLD = 1_024


_prefer_host = False


def set_prefer_host(value: bool) -> None:
    """Generate the next tables host-side. The runner sets this for stages
    whose compute is inherently host-resident (categorical string
    rendering): device-born data would be pulled back to the host
    wholesale. Placing birth next to compute is the data-loading layer's
    job — the reference's generator sources likewise run inside the
    cluster."""
    global _prefer_host
    _prefer_host = value


def _device_gen_enabled() -> bool:
    import os

    if _prefer_host:
        return False
    return os.environ.get("FLINK_ML_TPU_DEVICE_DATAGEN", "1") != "0"


def _birth_sharding(shape):
    """Rows split over the default mesh's data axis, so on several devices
    a table is born where training will read it instead of whole on device
    0 (a 10M x 100 table is a quarter of one chip's HBM). None — one
    device, unsharded — when there is a single data shard or the rows do
    not divide. The values do not depend on the layout (partitionable
    threefry)."""
    from ..parallel import mesh as mesh_lib

    mesh = mesh_lib.default_mesh()
    shards = mesh_lib.num_data_shards(mesh)
    if shards == 1 or shape[0] % shards:
        return None
    return mesh_lib.data_sharding(mesh, len(shape))


def _constrain(x, sharding):
    import jax

    return x if sharding is None else jax.lax.with_sharding_constraint(x, sharding)


def _uniform_impl(key, shape, sharding):
    import jax

    return _constrain(
        jax.random.uniform(key, shape, dtype=jax.numpy.float32), sharding
    )


def _randint_float_impl(key, shape, arity, sharding):
    import jax

    return _constrain(
        jax.random.randint(key, shape, 0, arity).astype(jax.numpy.float32), sharding
    )


# one compiled program per shape (static_argnames); lazy_jit keeps the
# wrappers on the jit.kernels accounting like every other kernel
_uniform_kernel = lazy_jit(_uniform_impl, static_argnames=("shape", "sharding"))
_randint_kernel = lazy_jit(
    _randint_float_impl, static_argnames=("shape", "arity", "sharding")
)


def _device_uniform(seed: int, shape):
    import jax

    shape = tuple(shape)
    return _uniform_kernel(jax.random.PRNGKey(seed), shape, _birth_sharding(shape))


def _device_randint_float(seed: int, shape, arity: int):
    import jax

    shape = tuple(shape)
    return _randint_kernel(
        jax.random.PRNGKey(seed), shape, int(arity), _birth_sharding(shape)
    )


class _ColNamesParam(Param):
    """String[][] colNames (InputDataGenerator.java COL_NAMES)."""

    def json_encode(self, value):
        return value

    def json_decode(self, json_value):
        return json_value


class DataGenerator(HasSeed):
    """Base generator: getData() -> list of Tables (DataGenerator.java)."""

    NUM_VALUES = LongParam(
        "numValues", "Number of data rows to generate.", 10, ParamValidators.gt(0)
    )
    COL_NAMES = _ColNamesParam("colNames", "Column names of the generated tables.", None)

    def get_num_values(self) -> int:
        return self.get(self.NUM_VALUES)

    def set_num_values(self, value: int):
        return self.set(self.NUM_VALUES, value)

    def get_col_names(self):
        return self.get(self.COL_NAMES)

    def set_col_names(self, *values):
        return self.set(self.COL_NAMES, [list(v) for v in values])

    def _rng(self) -> np.random.RandomState:
        return np.random.RandomState(self.get_seed() % (2**32))

    def get_data(self) -> List[Table]:
        raise NotImplementedError


class DenseVectorGenerator(DataGenerator):
    """Random uniform dense vectors (common/DenseVectorGenerator.java)."""

    VECTOR_DIM = IntParam("vectorDim", "Dimension of generated vectors.", 1, ParamValidators.gt(0))

    def get_vector_dim(self) -> int:
        return self.get(self.VECTOR_DIM)

    def set_vector_dim(self, value: int):
        return self.set(self.VECTOR_DIM, value)

    def get_data(self) -> List[Table]:
        (names,) = self.get_col_names()
        n, d = self.get_num_values(), self.get_vector_dim()
        if n >= DEVICE_GEN_THRESHOLD and _device_gen_enabled():
            X = _device_uniform(self.get_seed() % (2**32), (n, d))
        else:
            X = self._rng().rand(n, d)
        return [Table({names[0]: X})]


class DenseVectorArrayGenerator(DenseVectorGenerator):
    """Arrays of dense vectors per row (common/DenseVectorArrayGenerator.java)."""

    ARRAY_SIZE = IntParam("arraySize", "Size of the vector array.", 1, ParamValidators.gt(0))

    def get_array_size(self) -> int:
        return self.get(self.ARRAY_SIZE)

    def set_array_size(self, value: int):
        return self.set(self.ARRAY_SIZE, value)

    def get_data(self) -> List[Table]:
        from ..linalg import DenseVector

        (names,) = self.get_col_names()
        rng = self._rng()
        n, k, d = self.get_num_values(), self.get_array_size(), self.get_vector_dim()
        col = np.empty(n, dtype=object)
        for i in range(n):
            col[i] = [DenseVector(rng.rand(d)) for _ in range(k)]
        return [Table({names[0]: col})]


class DoubleGenerator(DataGenerator):
    """Random doubles (common/DoubleGenerator.java): uniform [0,1) by
    default; with arity > 0, integer-valued doubles in [0, arity)."""

    ARITY = IntParam(
        "arity",
        "Arity of the generated values: 0 means continuous in [0, 1).",
        0,
        ParamValidators.gt_eq(0),
    )

    def get_arity(self) -> int:
        return self.get(self.ARITY)

    def set_arity(self, value: int):
        return self.set(self.ARITY, value)

    def get_data(self) -> List[Table]:
        # Device-born like the other generators: the scalar consumers
        # (imputer, binarizer, bucketizer) aggregate on device now, and for
        # the remaining host-columnar stages ONE bulk D2H pull (~GB/s) is
        # still cheaper than single-core numpy generation of 1e8+ doubles.
        (names,) = self.get_col_names()
        n, arity = self.get_num_values(), self.get_arity()
        if n >= DEVICE_GEN_THRESHOLD and _device_gen_enabled():
            seed = self.get_seed() % (2**32)
            cols = {}
            for i, name in enumerate(names):
                if arity > 0:
                    cols[name] = _device_randint_float(seed + i, (n,), arity)
                else:
                    cols[name] = _device_uniform(seed + i, (n,))
            return [Table(cols)]
        rng = self._rng()
        if arity > 0:
            return [
                Table({name: rng.randint(0, arity, size=n).astype(np.float64) for name in names})
            ]
        return [Table({name: rng.rand(n) for name in names})]


class LabeledPointWithWeightGenerator(DataGenerator):
    """(features, label, weight) rows (common/LabeledPointWithWeightGenerator.java):
    feature values uniform in [0,1) or categorical of featureArity; label
    uniform integer in [0, labelArity); weight uniform in [0,1)."""

    FEATURE_ARITY = IntParam(
        "featureArity",
        "Arity of each feature: 0 means continuous in [0, 1).",
        2,
        ParamValidators.gt_eq(0),
    )
    LABEL_ARITY = IntParam(
        "labelArity", "Arity of the label.", 2, ParamValidators.gt(1)
    )
    VECTOR_DIM = IntParam("vectorDim", "Dimension of the feature vector.", 1, ParamValidators.gt(0))

    def get_feature_arity(self) -> int:
        return self.get(self.FEATURE_ARITY)

    def set_feature_arity(self, value: int):
        return self.set(self.FEATURE_ARITY, value)

    def get_label_arity(self) -> int:
        return self.get(self.LABEL_ARITY)

    def set_label_arity(self, value: int):
        return self.set(self.LABEL_ARITY, value)

    def get_vector_dim(self) -> int:
        return self.get(self.VECTOR_DIM)

    def set_vector_dim(self, value: int):
        return self.set(self.VECTOR_DIM, value)

    def get_data(self) -> List[Table]:
        (names,) = self.get_col_names()
        n, d = self.get_num_values(), self.get_vector_dim()
        arity = self.get_feature_arity()
        # Categorical tables are device-born like everything else: the
        # categorical consumers (NaiveBayes fit/transform) aggregate on
        # device now, so nothing pulls the table back to the host.
        if n >= DEVICE_GEN_THRESHOLD and _device_gen_enabled():
            seed = self.get_seed() % (2**32)
            if arity == 0:
                X = _device_uniform(seed, (n, d))
            else:
                X = _device_randint_float(seed, (n, d), arity)
            y = _device_randint_float(seed + 1, (n,), self.get_label_arity())
            w = _device_uniform(seed + 2, (n,))
            return [Table({names[0]: X, names[1]: y, names[2]: w})]
        rng = self._rng()
        if arity == 0:
            X = rng.rand(n, d)
        else:
            X = rng.randint(0, arity, size=(n, d)).astype(np.float64)
        y = rng.randint(0, self.get_label_arity(), size=n).astype(np.float64)
        w = rng.rand(n)
        return [Table({names[0]: X, names[1]: y, names[2]: w})]


def _string_vocab(m: int) -> np.ndarray:
    """Decimal token vocabulary at MINIMAL unicode width: astype(str) alone
    yields '<U21' (84 bytes/element), which makes a 10Mx100 token matrix
    17GB and string sorting glacial; '<U{digits}' keeps it 8 bytes at
    m<=100 so the dictionary-encode fast path can view it as int64."""
    return np.arange(m).astype(str).astype(f"<U{len(str(max(m - 1, 1)))}")


class RandomStringGenerator(DataGenerator):
    """Random strings from a fixed-size token universe
    (common/RandomStringGenerator.java)."""

    NUM_DISTINCT_VALUES = IntParam(
        "numDistinctValues", "Number of distinct string values.", 10, ParamValidators.gt(0)
    )

    def get_num_distinct_values(self) -> int:
        return self.get(self.NUM_DISTINCT_VALUES)

    def set_num_distinct_values(self, value: int):
        return self.set(self.NUM_DISTINCT_VALUES, value)

    def get_data(self) -> List[Table]:
        (names,) = self.get_col_names()
        rng = self._rng()
        n, m = self.get_num_values(), self.get_num_distinct_values()
        # vocab fancy-indexing generates fixed-width unicode columns without
        # a per-row Python loop (the reference generates rows inside the
        # cluster; a 10M-iteration host loop here would dominate the stage)
        vocab = _string_vocab(m)
        cols = {}
        for name in names:
            cols[name] = vocab[rng.randint(0, m, size=n)]
        return [Table(cols)]


class RandomStringArrayGenerator(RandomStringGenerator):
    """Arrays of random strings (common/RandomStringArrayGenerator.java)."""

    ARRAY_SIZE = IntParam("arraySize", "Size of the string arrays.", 1, ParamValidators.gt(0))

    def get_array_size(self) -> int:
        return self.get(self.ARRAY_SIZE)

    def set_array_size(self, value: int):
        return self.set(self.ARRAY_SIZE, value)

    def get_data(self) -> List[Table]:
        (names,) = self.get_col_names()
        n, m, k = self.get_num_values(), self.get_num_distinct_values(), self.get_array_size()
        vocab = _string_vocab(m)
        cols = {}
        if n >= DEVICE_GEN_THRESHOLD and _device_gen_enabled():
            # dictionary-encoded, ids born in HBM: string stages compute on
            # the id matrix device-side (a billion-token host loop on the
            # single-core driver would dominate every downstream stage)
            from ..ops import tokens as tokens_ops

            seed = self.get_seed() % (2**32)
            for i, name in enumerate(names):
                ids = tokens_ops.random_token_ids(seed + i, n, k, m)
                cols[name] = DictTokenMatrix(vocab, ids)
            return [Table(cols)]
        rng = self._rng()
        for name in names:
            # (n, k) fixed-width unicode token matrix — the columnar layout
            # string stages consume vectorized (each row is one token array)
            cols[name] = vocab[rng.randint(0, m, size=(n, k))]
        return [Table(cols)]


class KMeansModelDataGenerator(DataGenerator):
    """Random KMeansModelData (clustering/KMeansModelDataGenerator.java)."""

    ARRAY_SIZE = IntParam("arraySize", "Number of centroids.", 2, ParamValidators.gt(0))
    VECTOR_DIM = IntParam("vectorDim", "Dimension of centroids.", 1, ParamValidators.gt(0))

    def get_data(self) -> List[Table]:
        from ..linalg import DenseVector

        (names,) = self.get_col_names()
        rng = self._rng()
        k, d = self.get(self.ARRAY_SIZE), self.get(self.VECTOR_DIM)
        centroids = [DenseVector(rng.rand(d)) for _ in range(k)]
        weights = DenseVector(np.zeros(k))
        return [Table({names[0]: [centroids], names[1]: [weights]})]
