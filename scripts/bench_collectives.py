#!/usr/bin/env python
"""Multichip collectives microbench — one JSON line per run.

Standalone driver for the `multichipCollectives` BENCH entry (bench.py):
self-provisions an N-virtual-device CPU platform (the dryrun_multichip
substrate — env vars must win before jax's backend initializes, hence a
separate process per device count) and measures, for that N:

- the bucketed all-reduce (`all_reduce_sum_chunked`): bucket count and
  per-participant payload bytes at the configured chunk size, plus warm
  wall time vs the monolithic psum;
- the SparCML index-value gradient reduce at the sparseWideLR shape
  (dim=1M, nnz=39): sparse wire bytes vs the dense-equivalent psum
  payload — the traffic-proportionality number;
- a dense SGD fit with `config.collective_overlap` off vs on (bit-identical
  coefficients asserted) — the overlap schedule's end-to-end wall delta.

Usage: python scripts/bench_collectives.py [--devices N]
Prints exactly one JSON object on the LAST stdout line.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time


def _provision(n_devices: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    )
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()


def main(argv) -> int:
    n_devices = 8
    if "--devices" in argv:
        n_devices = int(argv[argv.index("--devices") + 1])
    _provision(n_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import numpy as np
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu import config
    from flink_ml_tpu.ops.losses import BINARY_LOGISTIC_LOSS
    from flink_ml_tpu.ops.optimizer import SGD
    from flink_ml_tpu.parallel import collectives as coll
    from flink_ml_tpu.parallel import mesh as mesh_lib
    from flink_ml_tpu.utils import metrics

    mesh = mesh_lib.create_mesh(("data",), devices=jax.devices()[:n_devices])
    result = {"devices": n_devices, "chunkBytes": config.resolve_chunk_bytes(None)}

    def timed_best(fn, repeats=5):
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        return min(times) * 1000.0

    # --- bucketed dense all-reduce: an 8MB f32 gradient ----------------------
    vec = np.random.default_rng(0).standard_normal((n_devices, 2 << 20)).astype(np.float32)
    chunked = jax.jit(
        coll.shard_map_over(
            mesh, P("data", None), P("data", None),
            fn=lambda v: coll.all_reduce_sum_chunked(v),
        )
    )
    mono = jax.jit(
        coll.shard_map_over(
            mesh, P("data", None), P("data", None),
            fn=lambda v: coll.all_reduce_sum(v),
        )
    )
    before = metrics.snapshot()
    out_c, out_m = chunked(vec), mono(vec)  # traces fire the accounting
    assert np.array_equal(np.asarray(out_c), np.asarray(out_m)), "chunked != psum"
    delta = metrics.snapshot_delta(before, metrics.snapshot())
    result["denseAllReduce"] = {
        "payloadBytes": int(vec[0].nbytes),
        "chunkCount": int(delta["counters"].get("collective.chunked.chunks", 1)),
        "collectiveBytes": int(delta["counters"].get("collective.chunked.bytes", 0)),
        "chunkedMs": timed_best(lambda: chunked(vec)),
        "monolithicMs": timed_best(lambda: mono(vec)),
    }

    # --- sparse index-value gradient reduce at the sparseWideLR shape --------
    dim, nnz, rows_per_shard = 1_000_000, 39, 1024
    rng = np.random.default_rng(1)
    idx = rng.integers(0, dim, size=(n_devices, rows_per_shard * nnz)).astype(np.int32)
    val = rng.standard_normal((n_devices, rows_per_shard * nnz)).astype(np.float32)
    sparse_fn = jax.jit(
        coll.shard_map_over(
            mesh, (P("data", None), P("data", None)), P(),
            fn=lambda i, v: coll.sparse_all_reduce_sum(i[0], v[0], dim),
        )
    )
    dense_fn = jax.jit(
        coll.shard_map_over(
            mesh, (P("data", None), P("data", None)), P(),
            fn=lambda i, v: coll.all_reduce_sum_chunked(
                jax.numpy.zeros((dim,), v.dtype).at[i[0]].add(v[0], mode="drop")
            ),
        )
    )
    before = metrics.snapshot()
    out_s, out_d = sparse_fn(idx, val), dense_fn(idx, val)
    # same addends, float rounding apart (duplicate indices within a shard
    # reassociate — collectives.sparse_all_reduce_sum), not bitwise
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_d), rtol=1e-5, atol=1e-5,
        err_msg="sparse != dense",
    )
    delta = metrics.snapshot_delta(before, metrics.snapshot())
    sparse_bytes = int(delta["counters"].get("collective.sparse.bytes", 0))
    dense_equiv = int(delta["counters"].get("collective.sparse.dense_equiv_bytes", 0))
    result["sparseGradReduce"] = {
        "dim": dim,
        "nnzPerRow": nnz,
        "rowsPerShard": rows_per_shard,
        "sparseBytes": sparse_bytes,
        "denseEquivalentBytes": dense_equiv,
        "sparseRatio": sparse_bytes / dense_equiv if dense_equiv else None,
        "sparseMs": timed_best(lambda: sparse_fn(idx, val)),
        "denseMs": timed_best(lambda: dense_fn(idx, val)),
    }

    # --- overlap-scheduled SGD: off vs on, bit-identical ---------------------
    n_rows, d = 8192, 256
    X = rng.standard_normal((n_rows, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    kw = dict(max_iter=30, global_batch_size=2048, tol=0.0, learning_rate=0.1)
    with mesh_lib.use_mesh(mesh):
        fits = {}
        for overlap in (False, True):
            sgd = SGD(collective_overlap=overlap, **kw)

            def run(sgd=sgd):
                return sgd.optimize(
                    np.zeros(d, np.float32), X, y, None, BINARY_LOGISTIC_LOSS,
                    mesh=mesh,
                )

            coeff, loss, epochs = run()  # warm (compile)
            fits[overlap] = (coeff, timed_best(run, repeats=3))
        assert np.array_equal(fits[False][0], fits[True][0]), "overlap != eager"
    result["overlapSgd"] = {
        "rows": n_rows,
        "dim": d,
        "maxIter": kw["max_iter"],
        "eagerMs": fits[False][1],
        "overlapMs": fits[True][1],
        "bitIdentical": True,
    }

    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
