"""AgglomerativeClustering — hierarchical clustering with 4 linkages.

TPU-native re-design of clustering/agglomerativeclustering/
AgglomerativeClustering.java (nearest-neighbor-chain agglomeration; linkage
ward/complete/single/average via Lance-Williams updates; stop at
numClusters OR distanceThreshold; computeFullTree continues merging for
the merge-info side output; ward requires euclidean). Outputs two tables:
the input plus the prediction column, and the merge log
(clusterId1, clusterId2, distance, sizeOfMergedCluster).
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...api import AlgoOperator
from ...common.param import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasPredictionCol,
    HasWindows,
)
from ...ops.distance import DistanceMeasure
from ...param import BooleanParam, DoubleParam, IntParam, ParamValidators, StringParam
from ...common.window import CountTumblingWindows, GlobalWindows
from ...table import Table, as_dense_matrix

LINKAGE_WARD = "ward"
LINKAGE_COMPLETE = "complete"
LINKAGE_SINGLE = "single"
LINKAGE_AVERAGE = "average"


class AgglomerativeClusteringParams(
    HasDistanceMeasure, HasFeaturesCol, HasPredictionCol, HasWindows
):
    NUM_CLUSTERS = IntParam("numClusters", "The max number of clusters to create.", 2)
    DISTANCE_THRESHOLD = DoubleParam(
        "distanceThreshold",
        "Threshold to decide whether two clusters should be merged.",
        None,
    )
    LINKAGE = StringParam(
        "linkage",
        "Criterion for computing distance between two clusters.",
        LINKAGE_WARD,
        ParamValidators.in_array(
            [LINKAGE_WARD, LINKAGE_COMPLETE, LINKAGE_AVERAGE, LINKAGE_SINGLE]
        ),
    )
    COMPUTE_FULL_TREE = BooleanParam(
        "computeFullTree",
        "Whether computes the full tree after convergence.",
        False,
        ParamValidators.not_null(),
    )

    def get_num_clusters(self):
        return self.get(self.NUM_CLUSTERS)

    def set_num_clusters(self, value):
        return self.set(self.NUM_CLUSTERS, value)

    def get_distance_threshold(self):
        return self.get(self.DISTANCE_THRESHOLD)

    def set_distance_threshold(self, value):
        return self.set(self.DISTANCE_THRESHOLD, value)

    def get_linkage(self) -> str:
        return self.get(self.LINKAGE)

    def set_linkage(self, value: str):
        return self.set(self.LINKAGE, value)

    def get_compute_full_tree(self) -> bool:
        return self.get(self.COMPUTE_FULL_TREE)

    def set_compute_full_tree(self, value: bool):
        return self.set(self.COMPUTE_FULL_TREE, value)


def _lance_williams_update(d_ik, d_jk, d_ij, size_i, size_j, size_k, linkage):
    """Distance of merged cluster (i+j) to every other cluster k."""
    if linkage == LINKAGE_SINGLE:
        return np.minimum(d_ik, d_jk)
    if linkage == LINKAGE_COMPLETE:
        return np.maximum(d_ik, d_jk)
    if linkage == LINKAGE_AVERAGE:
        return (size_i * d_ik + size_j * d_jk) / (size_i + size_j)
    # ward (on euclidean distances)
    total = size_i + size_j + size_k
    return np.sqrt(
        ((size_i + size_k) * d_ik**2 + (size_j + size_k) * d_jk**2 - size_k * d_ij**2)
        / total
    )


_LINKAGE_CODES = {
    LINKAGE_SINGLE: 0,
    LINKAGE_COMPLETE: 1,
    LINKAGE_AVERAGE: 2,
    LINKAGE_WARD: 3,
}


def _cluster_block_native(dist, linkage, num_clusters, threshold, compute_full_tree):
    """Run the merge loop in C (native/src/agglomerative.cc — the same
    algorithm and arithmetic as the numpy loop below, ~100x faster on this
    single-core host). Returns (pred, merges) or None without the lib."""
    import ctypes

    from ...native import load as _load_native

    lib = _load_native()
    if lib is None or not hasattr(lib, "agg_cluster"):
        return None  # source may have failed to compile; numpy loop below
    n = dist.shape[0]
    dist = np.ascontiguousarray(dist)  # consumed in place; caller is done with it
    merges_out = np.empty((max(n - 1, 1), 4), dtype=np.float64)
    pred = np.empty(n, dtype=np.int32)
    num = lib.agg_cluster(
        dist.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_long(n),
        ctypes.c_int(_LINKAGE_CODES[linkage]),
        ctypes.c_double(threshold if threshold is not None else 0.0),
        ctypes.c_int(1 if threshold is not None else 0),
        ctypes.c_long(num_clusters),
        ctypes.c_int(1 if compute_full_tree else 0),
        merges_out.ctypes.data_as(ctypes.c_void_p),
        pred.ctypes.data_as(ctypes.c_void_p),
    )
    merges = [
        (int(a), int(b), float(d), int(s)) for a, b, d, s in merges_out[:num]
    ]
    _, pred = np.unique(pred, return_inverse=True)
    return pred.astype(np.int32), merges


def _pairwise_host(X: np.ndarray, measure_name: str):
    """Float64 pairwise distances in host numpy, mirroring
    ops/distance.py's formulas. The local clustering consumes the full
    (n, n) matrix on the host anyway, and the reference's
    LocalAgglomerativeClusteringFunction computes CPU doubles — device
    pairwise would add an (n, n) D2H readback for LESS precision. None
    for unknown measures."""
    X = np.asarray(X, dtype=np.float64)
    if measure_name == "euclidean":
        x2 = np.einsum("ij,ij->i", X, X)
        sq = x2[:, None] - 2.0 * (X @ X.T) + x2[None, :]
        return np.sqrt(np.maximum(sq, 0.0))
    if measure_name == "cosine":
        xn = np.sqrt(np.einsum("ij,ij->i", X, X))
        sim = (X @ X.T) / np.maximum(np.outer(xn, xn), 1e-12)
        return 1.0 - sim
    if measure_name == "manhattan":
        n = X.shape[0]
        out = np.empty((n, n), dtype=np.float64)
        step = max(1, (8 << 20) // max(X.size, 1))  # ~8M-element temporaries
        for s in range(0, n, step):
            out[s : s + step] = np.abs(X[s : s + step, None, :] - X[None, :, :]).sum(-1)
        return out
    return None


def _cluster_block(X, linkage, measure, num_clusters, threshold, compute_full_tree):
    """Agglomerate one window of rows; returns (pred, merges) with
    window-local cluster ids (LocalAgglomerativeClusteringFunction.process)."""
    n = X.shape[0]
    if n == 0:
        return np.zeros(0, np.int32), []
    dist = _pairwise_host(np.asarray(X), measure.name)
    if dist is None:
        import jax.numpy as jnp

        dist = np.asarray(
            measure.pairwise(jnp.asarray(X), jnp.asarray(X)), dtype=np.float64
        )
    np.fill_diagonal(dist, np.inf)
    native = _cluster_block_native(
        dist, linkage, num_clusters, threshold, compute_full_tree
    )
    if native is not None:
        return native
    num_active = n
    sizes = np.ones(n, dtype=np.int64)
    # fresh id for every merged cluster (n, n+1, ...) — the reference's
    # reOrderNnChain convention for the merge log
    cluster_ids = list(range(n))
    members = {i: [i] for i in range(n)}
    merges = []  # (id1, id2, distance, merged size)
    merge_members = []  # row sets merged at each step, for labeling
    next_merge_stopped = None  # merge count at which the stop criterion hit
    # cached per-row nearest neighbours: the global closest pair is then
    # an O(n) scan instead of an O(n^2) full-matrix argmin per merge —
    # the difference between O(n^3) and ~O(n^2) total (the r3 benchmark
    # ran this loop at 90.6 records/s)
    row_min = dist.min(axis=1) if n > 1 else np.full(n, np.inf)
    row_arg = dist.argmin(axis=1) if n > 1 else np.zeros(n, np.int64)
    row_ids = np.arange(n)
    while num_active > 1:
        i = int(np.argmin(row_min))
        j = int(row_arg[i])
        d_ij = row_min[i]
        stop_hit = (
            threshold is not None and d_ij > threshold
        ) or (threshold is None and num_active <= num_clusters)
        if stop_hit and next_merge_stopped is None:
            next_merge_stopped = len(merges)
            if not compute_full_tree:
                break
        # merge j into i (log the pre-merge cluster ids, sorted)
        id_i, id_j = cluster_ids[i], cluster_ids[j]
        lo, hi = (id_i, id_j) if id_i < id_j else (id_j, id_i)
        merges.append((lo, hi, float(d_ij), int(sizes[i] + sizes[j])))
        # Lance-Williams row update against every other live cluster
        new_row = _lance_williams_update(
            dist[i], dist[j], d_ij, sizes[i], sizes[j], sizes, linkage
        )
        finite = np.isfinite(dist[i]) & np.isfinite(dist[j])
        dist[i, finite] = new_row[finite]
        dist[finite, i] = new_row[finite]
        dist[i, i] = np.inf
        dist[j, :] = np.inf
        dist[:, j] = np.inf
        # nearest-neighbour cache maintenance: j dies; i recomputes; a
        # row whose distance to the merged cluster improved points at i;
        # a row whose cached nearest was i or j (and didn't improve) is
        # stale and rescans
        row_min[j], row_arg[j] = np.inf, j
        row_min[i], row_arg[i] = dist[i].min(), int(dist[i].argmin())
        nr = np.where(finite, new_row, np.inf)
        better = nr < row_min
        better[i] = False
        row_min[better] = nr[better]
        row_arg[better] = i
        stale = np.flatnonzero(
            ((row_arg == i) | (row_arg == j)) & ~better & (row_ids != i) & finite
        )
        for k in stale:
            row_min[k] = dist[k].min()
            row_arg[k] = int(dist[k].argmin())
        sizes[i] += sizes[j]
        cluster_ids[i] = n + len(merges) - 1
        members[i].extend(members.pop(j))
        merge_members.append(list(members[i]))
        num_active -= 1
    # labels: replay merges up to the stop point
    stop_at = next_merge_stopped if next_merge_stopped is not None else len(merges)
    pred = np.arange(n, dtype=np.int64)
    for rows in merge_members[:stop_at]:
        pred[rows] = min(pred[r] for r in rows)
    _, pred = np.unique(pred, return_inverse=True)
    return pred.astype(np.int32), merges


class AgglomerativeClustering(AlgoOperator, AgglomerativeClusteringParams):
    fusable = False
    fusable_reason = "O(n^2) host linkage build (prefers_host_input); no record-wise device kernel exists"

    # the linkage matrix is built row-by-row on host (no device kernels at
    # all), so device-born input costs a full D2H pull of the dataset
    # before any work starts — the slowest per-record entry in round 5's
    # SWEEP was exactly that pull, not the clustering
    prefers_host_input = True

    @staticmethod
    def _window_row_groups(table: Table, n: int, windows) -> List[np.ndarray]:
        """Row-index groups each LOCAL clustering runs over, per window
        descriptor. Count windows fire only when full (ragged tail
        dropped); event-time windows read the table's 'timestamp' column
        (ms) and fire in window-start order; a bounded table arrives at
        one instant, so processing-time windows degenerate to one global
        window (what a fast bounded source does in the reference)."""
        from ...common.window import (
            EventTimeSessionWindows,
            EventTimeTumblingWindows,
            ProcessingTimeSessionWindows,
            ProcessingTimeTumblingWindows,
        )
        from ...utils.datastream import event_time_groups_from_table

        if isinstance(windows, CountTumblingWindows):
            size = int(windows.size)
            n_whole = (n // size) * size
            return [
                np.arange(start, start + size) for start in range(0, n_whole, size)
            ]
        if isinstance(windows, GlobalWindows) or isinstance(
            windows, (ProcessingTimeTumblingWindows, ProcessingTimeSessionWindows)
        ):
            return [np.arange(n)] if n else []
        if isinstance(windows, (EventTimeTumblingWindows, EventTimeSessionWindows)):
            return event_time_groups_from_table(table, windows)
        raise ValueError(f"Unsupported windows descriptor {type(windows).__name__}")

    def transform(self, *inputs: Table) -> List[Table]:
        (table,) = inputs
        linkage = self.get_linkage()
        measure_name = self.get_distance_measure()
        if linkage == LINKAGE_WARD and measure_name != "euclidean":
            raise ValueError(
                f"{measure_name} was provided as distance measure while linkage was "
                "ward. Ward only works with euclidean."
            )
        X = as_dense_matrix(table.column(self.get_features_col()))
        num_clusters = self.get_num_clusters()
        threshold = self.get_distance_threshold()
        if threshold is not None:
            num_clusters = 1  # threshold decides instead (reference semantics)
        measure = DistanceMeasure.get_instance(measure_name)
        compute_full_tree = self.get_compute_full_tree()

        # The windows param picks the rows each LOCAL clustering runs over
        # (AgglomerativeClustering.java:122-133: windowAllAndProcess +
        # LocalAgglomerativeClusteringFunction per window).
        windows = self.get_windows()
        groups = self._window_row_groups(table, X.shape[0], windows)
        kept_rows = (
            np.concatenate(groups) if groups else np.zeros(0, np.int64)
        )
        n_total = len(kept_rows)
        preds, all_merges = [], []
        offset = 0
        for group in groups:
            pred, merges = _cluster_block(
                X[group],
                linkage,
                measure,
                num_clusters,
                threshold,
                compute_full_tree,
            )
            preds.append(pred)
            # remap window-local cluster ids to global ones so the
            # concatenated merge log stays decodable: local row id i ->
            # output row offset+i (rows are emitted in window order);
            # local merged id local_n+j (the window's j-th merge) ->
            # n_total + (merges logged so far) + j — the same "rows first,
            # then merges in log order" convention the single-window
            # output uses
            local_n = len(pred)
            merge_base = n_total + len(all_merges)

            def remap(cid, offset=offset, local_n=local_n, merge_base=merge_base):
                if cid < local_n:
                    return cid + offset
                return merge_base + (cid - local_n)

            all_merges.extend(
                (remap(a), remap(b), dist_, size_) for a, b, dist_, size_ in merges
            )
            offset += local_n
        pred = np.concatenate(preds) if preds else np.zeros(0, np.int32)
        out = table
        # reorder/select whenever kept_rows is not the identity — event-time
        # groups can be a full-cover PERMUTATION (unsorted timestamps), where
        # a length check alone would leave predictions attached to the wrong
        # rows (array_equal also covers the shorter-selection case)
        if not np.array_equal(kept_rows, np.arange(table.num_rows)):
            out = out.take(kept_rows)
        out = out.with_column(self.get_prediction_col(), pred)
        merge_table = Table(
            {
                "clusterId1": [m[0] for m in all_merges],
                "clusterId2": [m[1] for m in all_merges],
                "distance": [m[2] for m in all_merges],
                "sizeOfMergedCluster": [m[3] for m in all_merges],
            }
        )
        return [out, merge_table]
