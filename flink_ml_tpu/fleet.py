"""FitFleet: N estimator fits trained as ONE vmapped resident dispatch.

Whole-fit residency (docs/performance.md §3) collapsed a fit to one
dispatch + one packed readback; this module amortizes N fits into ONE
program — hyperparameter sweeps, CV folds, and per-tenant personalized
models train as a *fleet*. The whole-fit SGD / stream-SGD / Lloyd loops
are vmapped over a leading fleet axis (`ops.optimizer._sgd_fleet_*`,
`models.clustering.kmeans._lloyd_fleet_train`):

- the packed hyper-parameter vector becomes a [N, 5] array, so every
  member carries its own maxIter/tol/lr/reg/elasticNet;
- the per-member convergence mask is the vmapped `while_loop` itself —
  it runs until EVERY member's condition is false and select-freezes
  finished members, so each member's stop epoch and coefficients are
  bit-identical to its solo fit on the CPU (every contraction in the
  member bodies is vmap-batching bit-stable there — see ops/losses.py
  module docstring; on a TPU see below);
- the staged dataset is closed over UNBATCHED: input bytes are paid once
  for N models, and the members find their batch once an epoch, at the
  fleet's furthest epoch (`ops.optimizer.FLEET_AXIS`; a batch indexed by
  each member's own counter is a gather of N copies of it);
- readback is ONE packed [N, result_pack] array.

In place or laid out: on ONE device, over a device table in whole
batches, dense of the engine's dtype or a padded-CSR pair, the programs read
the caller's table where it lies (`SGD._in_place`, an
`optimizer.FlatBatches` view of each array; `optimizer._can_train_in_place`
decides from the arrays and the mesh, and the fit counts `fleet.in_place`),
as `SGD._stage_flat` trains a solo fit: a laid-out copy of a table that
fills half a chip has no room beside it. Several data shards, the
fleet-sharded regime, a host or ragged table and a checkpointed fleet lay
their table out as before (`SGD._batchify`); a `StreamTable` keeps its
stacked segments.

Sharding over the fleet axis: when N x per-member state crosses
`config.fleet_shard_state_bytes` (and N divides the data shards), the
fleet axis rides the mesh `data` axis — each device owns whole members —
and the training data is replicated instead (`mesh.fleet_sharding`).
Parity per regime: the default (replicated-fleet) regime batches over
the SAME data-sharded reductions as a solo fit, so members are
bit-identical to their solo fits on the same mesh; the fleet-sharded
regime runs each member's reductions over replicated data in
single-shard order, so members are bit-identical to their solo fits on
ONE data shard (and allclose to any shard count — the across-mesh
reduction-order doctrine of docs/fault_tolerance.md).

"Bit-identical" above is a statement about the CPU backend, where the
tests pin it. On a TPU the fleet's epochs do not take the reduce form at
all: over a dense float32 table the members' row-dots and gradients are
two float32 matrix products at `Precision.HIGHEST`, one for all members
each (`ops.losses.product_variant`, decided by
`ops.optimizer._fleet_multiplies` and counted as `fleet.product.matrix`
or `fleet.product.reduce` a fleet fit), on the matrix unit where the two
reductions ran on the vector unit (PERF.md §6, PR 40). They sum in
another order than a solo fit, so a member agrees with its solo fit to
rounding; even the reduce form's vmapped program rounded differently
there (a 32-member fleet within 2.2e-7 of its solo fits, PR 21,
`bench.bench_fleet_sweep`, which asserts 1e-5 and reports
`bitIdenticalToSolo` as measured). The benchmark's cell
`lr-regpath-100.path` holds every member of a 100-member path to the
plain reference's solo fits (PERF.md §4). Over a padded-CSR float32 table
on a TPU the whole-fit route of one fleet takes the member-row form instead
(`ops.optimizer._fleet_rows`, `fleet.product.rows`): a program of
its own, `_sgd_fleet_rows_whole_fit`, holds the members' coefficients [d, N]
and gathers and segment-sums an entry's N of them as ONE row, over the
column plan made once a fleet fit (`ops.sparse_epoch.plan_fit`) over the
rows the longest member's epochs reach, as the cell
`lr-regpath-criteo-1m.resident-path` runs it (2M of its 28M rows).

A fleet fit is ONE fit to the observability layer (`fit.total`,
`fit.outer`, the `stage.fit` span), and a fleet of linear members over an
in-memory table reports a solo fit's phases once a FLEET fit:
`fit.extract`, `fit.stage`, `fit.launch`, `fit.readback`, its one
blocking read through `tracing.sync` (docs/observability.md, "Fit
phases").

Fleet checkpointing rides the JobSnapshot coordinator (ckpt/snapshot.py)
as one cut over the fleet-axis-sharded carry (section "fleet", tag
`data`); the memory ledger accounts fleet state under the `fleet`
category, and `hbm.peak.fit` is namespaced per member index
(obs.memledger.record_fleet_fit_peak).

Snap ML's hierarchical data x model scheme (arXiv:1803.06333) and the
batched-objective framing of distributed function minimization ground
the design: many small convex fits are one batched objective to the
hardware.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .obs import tracing
from .parallel import mesh as mesh_lib
from .parallel import prefetch as h2d

__all__ = ["FitFleet", "promote_fleet_winner", "fleet_model_arrays"]

#: estimator class name -> (loss name, validate_binomial). The loss is
#: resolved lazily so importing fleet.py does not pull every model module.
_LINEAR_KINDS = {
    "LogisticRegression": ("binary_logistic", True),
    "LinearSVC": ("hinge", False),
    "LinearRegression": ("least_square", False),
}


def _loss_by_name(name: str):
    from .ops import losses

    return {
        "binary_logistic": losses.BINARY_LOGISTIC_LOSS,
        "hinge": losses.HINGE_LOSS,
        "least_square": losses.LEAST_SQUARE_LOSS,
    }[name]


def _product_form(X_b, loss_func, rows: bool = False):
    """The loss the fleet's programs take for the table as they are handed
    it: the matrix-product form where `optimizer._fleet_multiplies` says so
    (a dense float32 table on a TPU), the member-row form where `rows` (the
    whole-fit route of one fleet, which has a program for it) and
    `optimizer._fleet_rows` say so (a padded-CSR float32 table on a TPU),
    else `loss_func` as it is; one tick a fleet fit, `fleet.product.matrix`,
    `fleet.product.rows` or `fleet.product.reduce`."""
    from .ops.losses import product_variant, rows_variant
    from .ops.optimizer import _fleet_multiplies, _fleet_rows
    from .utils import metrics

    if _fleet_multiplies(X_b, loss_func):
        metrics.inc_counter("fleet.product.matrix")
        return product_variant(loss_func)
    if rows and _fleet_rows(X_b, loss_func):
        metrics.inc_counter("fleet.product.rows")
        return rows_variant(loss_func)
    metrics.inc_counter("fleet.product.reduce")
    return loss_func


def _linear_model_for(est):
    """Instantiate the estimator's fitted-model class (mirrors each
    estimator's own `fit` tail: model + update_existing_params)."""
    from .utils.param_utils import update_existing_params

    kind = type(est).__name__
    if kind == "LogisticRegression":
        from .models.classification.logisticregression import LogisticRegressionModel

        model = LogisticRegressionModel()
    elif kind == "LinearSVC":
        from .models.classification.linearsvc import LinearSVCModel

        model = LinearSVCModel()
    else:
        from .models.regression.linearregression import LinearRegressionModel

        model = LinearRegressionModel()
    update_existing_params(model, est)
    return model


def _member_hyper(est) -> List[float]:
    """One member's packed hyper row — the [N, 5] fleet extension of
    `SGD._hyper` ([maxIter, tol, lr, reg, elasticNet], f32)."""
    return [
        float(est.get_max_iter()),
        float(est.get_tol()),
        float(est.get_learning_rate()),
        float(est.get_reg()),
        float(est.get_elastic_net()),
    ]


def _require_same(estimators, getter: str, what: str):
    values = [getattr(e, getter)() for e in estimators]
    if any(v != values[0] for v in values[1:]):
        raise ValueError(
            f"FitFleet members must share {what} (the fleet trains on ONE "
            f"staged dataset / batch schedule); got {sorted(set(map(str, values)))}"
        )
    return values[0]


class FitFleet:
    """Train N same-class estimators as one fleet: `FitFleet([e1..eN])
    .fit(table)` returns N fitted models, each bit-identical to the model
    `ei.fit(table)` would produce solo — in one resident dispatch and one
    packed readback.

    Members must share the structural params that define the staged data
    and batch schedule (featuresCol / labelCol / weightCol /
    globalBatchSize; `k` for KMeans). Per-member hyper-parameters
    (maxIter, tol, learningRate, reg, elasticNet; seed/maxIter for
    KMeans) ride the [N, pack] hyper array and may all differ.

    `shard_fleet_axis` forces (True) or forbids (False) the
    fleet-axis-sharded regime; None decides automatically from
    `config.fleet_shard_state_bytes` and `mesh.fleet_axis_shardable`.
    In the sharded regime data is replicated, so members match their
    solo fits on ONE data shard bit-exactly (module docstring)."""

    def __init__(self, estimators: Sequence, *, shard_fleet_axis: Optional[bool] = None):
        estimators = list(estimators)
        if not estimators:
            raise ValueError("FitFleet needs at least one estimator")
        kind = type(estimators[0]).__name__
        if any(type(e).__name__ != kind for e in estimators):
            raise ValueError(
                "FitFleet members must be the same estimator class; got "
                f"{sorted({type(e).__name__ for e in estimators})}"
            )
        if kind not in _LINEAR_KINDS and kind != "KMeans":
            raise ValueError(
                f"FitFleet does not support {kind}; supported: "
                f"{sorted(_LINEAR_KINDS) + ['KMeans']}"
            )
        self.estimators = estimators
        self.kind = kind
        self.shard_fleet_axis = shard_fleet_axis

    # -- regime ------------------------------------------------------------

    def _decide_sharded(self, mesh, state_bytes: int) -> bool:
        from . import config

        n = len(self.estimators)
        if self.shard_fleet_axis is not None:
            if self.shard_fleet_axis and not mesh_lib.fleet_axis_shardable(mesh, n):
                raise ValueError(
                    f"shard_fleet_axis=True but a fleet of {n} cannot shard "
                    f"over {mesh_lib.num_data_shards(mesh)} data shard(s) "
                    "(needs >1 shards dividing the fleet evenly)"
                )
            return bool(self.shard_fleet_axis)
        return (
            config.fleet_shard_state_bytes is not None
            and state_bytes > config.fleet_shard_state_bytes
            and mesh_lib.fleet_axis_shardable(mesh, n)
        )

    def _stage_fleet_state(self, mesh, n: int, d: int, sharded: bool):
        """Member carry [coeff, grad, wsum, epochs] + criteria, staged
        through the accounted H2D funnel under the `fleet` ledger
        category, fleet-axis-sharded or replicated per the regime."""
        spec2 = (
            mesh_lib.fleet_sharding(mesh, 2) if sharded
            else mesh_lib.replicated_sharding(mesh)
        )
        spec1 = (
            mesh_lib.fleet_sharding(mesh, 1) if sharded
            else mesh_lib.replicated_sharding(mesh)
        )
        carry = (
            h2d.stage_to_device(np.zeros((n, d), np.float32), spec2, category="fleet"),
            h2d.stage_to_device(np.zeros((n, d), np.float32), spec2, category="fleet"),
            h2d.stage_to_device(np.zeros((n,), np.float32), spec1, category="fleet"),
            h2d.stage_to_device(np.zeros((n,), np.int32), spec1, category="fleet"),
        )
        crit = h2d.stage_to_device(
            np.full((n,), np.inf, np.float32), spec1, category="fleet"
        )
        return carry, crit

    @staticmethod
    def _pack_sharding(mesh):
        """The packed [N, result_pack] readback layout: replicated. The
        per-member concatenate inside the vmapped result pack must never
        see a sharded operand on a multi-axis mesh (the GSPMD partial-sum
        miscompile `_pack_train_result` documents), and in the
        fleet-sharded regime an explicit all-gather-on-pack is ONE
        collective at fit end vs. N shard reads at readback."""
        if len(mesh.axis_names) > 1 or mesh_lib.num_data_shards(mesh) > 1:
            return NamedSharding(mesh, P())
        return None

    # -- public API --------------------------------------------------------

    def fit(self, table) -> List:
        """Train every member on `table`; returns the N fitted models (same
        order as the estimators). A linear member's coefficient is a row of
        the fit's one float32 readback, as a solo fit's is a view of its own:
        a model kept alone keeps that readback, which `np.array` of its
        coefficient lets go."""
        from .obs import memledger
        from .utils import metrics

        mesh = mesh_lib.default_mesh()
        n = len(self.estimators)
        metrics.set_gauge("fleet.size", n)
        tok = memledger.mark_peak()
        try:
            if self.kind == "KMeans":
                models = self._fit_kmeans(table, mesh)
            else:
                models = self._fit_linear(table, mesh)
        finally:
            memledger.record_fleet_fit_peak(memledger.peak_since(tok), n)
        metrics.inc_counter("fleet.fits")
        metrics.inc_counter("fleet.modelsTrained", n)
        return models

    # -- linear (SGD) driver -----------------------------------------------

    def _fit_linear(self, table, mesh) -> List:
        from . import config
        from .models import _linear
        from .utils import metrics
        from .ops import sparse_epoch
        from .ops.losses import ROW_VARIANTS, sparse_variant
        from .ops.optimizer import SGD, _can_train_in_place
        from .table import StreamTable

        ests = self.estimators
        loss_name, validate = _LINEAR_KINDS[self.kind]
        loss_func = _loss_by_name(loss_name)
        if isinstance(table, StreamTable):
            return self._fit_linear_stream(
                table, mesh, loss_func, *self._shared_params(validate), validate
            )
        # the fleet's phases are a solo fit's (`_linear.run_sgd`,
        # `SGD.optimize_async`), once a FLEET fit: one job, N models
        with tracing.phase("fit.extract"):
            hyper, gmax, features_col, label_col, weight_col, gbs = self._shared_params(validate)
            X, y, w = _linear.extract_train_data(
                table, features_col, label_col, weight_col, keep_sparse=True
            )
            validate_on_device = False
            if validate:
                if isinstance(y, jax.Array):
                    validate_on_device = True  # fused into the fleet program
                else:
                    _linear.validate_binomial_labels(y)
            if isinstance(X, tuple):  # sparse padded-CSR, never densified
                indices, values, d = X
                X = (indices, values)
                loss_func = sparse_variant(loss_func.name)
                n_rows = int(indices.shape[0])
            else:
                n_rows, d = map(int, X.shape)

        with tracing.phase("fit.stage"):
            # coeff + grad are the dim-proportional member state
            sharded = self._decide_sharded(mesh, state_bytes=2 * len(ests) * d * 4)
            metrics.set_gauge("fleet.sharded", 1.0 if sharded else 0.0)
            template = SGD(global_batch_size=gbs)
            in_place = (
                not sharded
                and config.iteration_checkpoint_dir is None
                and _can_train_in_place(X, y, w, gbs, template.dtype, mesh)
            )
            if in_place:
                metrics.inc_counter("fleet.in_place")
                X_b, y_b, w_b = template._in_place(mesh, X, y, w)
            else:
                X_b, y_b, w_b = template._batchify(mesh, X, y, w, replicate_data=sharded)
            whole = not sharded and config.iteration_checkpoint_dir is None
            loss_func = _product_form(X_b, loss_func, rows=whole)
            if loss_func in ROW_VARIANTS.values():
                # the row program makes its member-minor state itself; the
                # plan is the fleet's, once a fit, as a solo fit's is, over
                # the rows the fleet's furthest epoch reaches
                carry = crit = None
                plan = sparse_epoch.plan_fit(X, loss_func, mesh, gbs, gmax)
            else:
                carry, crit = self._stage_fleet_state(mesh, len(ests), d, sharded)
                plan = (None, None)

        flags, coeffs, crits, epochs = self._run_fleet_sgd(
            mesh, X_b, y_b, w_b, carry, crit, loss_func, hyper, gmax, d,
            validate_on_device, sharded, gbs, plan,
        )
        if flags is not None:
            _linear._raise_if_invalid(float(np.min(flags)))
        # counted from rows and epochs: an epoch trains batch k = epoch mod
        # the table's batches, whose last may be short
        num_batches = max(1, -(-n_rows // gbs))
        metrics.inc_counter(
            "fleet.examplesTrained",
            sum(int(e // num_batches) * n_rows + min(int(e % num_batches) * gbs, n_rows) for e in epochs),
        )
        models = []
        for i, est in enumerate(ests):
            model = _linear_model_for(est)
            # a row of the fit's one readback, as a solo fit's coefficient is
            # a view of its own: a copy a member is 400 MB of fresh host
            # memory a fit of 100 members of a million coefficients (0.4 s on
            # a v5e's host; 0.9 s as float64, PERF.md section 6)
            model.coefficient = coeffs[i]
            models.append(model)
        return models

    def _shared_params(self, validate: bool):
        """What every member has to share and the packed per-member rest:
        (hyper [N, 5], the longest maxIter, featuresCol, labelCol, weightCol,
        globalBatchSize)."""
        ests = self.estimators
        features_col = _require_same(ests, "get_features_col", "featuresCol")
        label_col = _require_same(ests, "get_label_col", "labelCol")
        weight_col = _require_same(ests, "get_weight_col", "weightCol")
        gbs = int(_require_same(ests, "get_global_batch_size", "globalBatchSize"))
        if validate:
            for est in ests:
                if est.get_multi_class() == "multinomial":
                    raise ValueError(
                        "Multinomial classification is not supported yet. "
                        "Supported options: [auto, binomial]."
                    )
        hyper = np.asarray([_member_hyper(e) for e in ests], np.float32)
        return hyper, int(hyper[:, 0].max()), features_col, label_col, weight_col, gbs

    def _run_fleet_sgd(
        self, mesh, X_b, y_b, w_b, carry, crit, loss_func, hyper, gmax, d,
        check_labels, sharded, gbs, plan=(None, None),
    ):
        """The fleet SGD loop: ONE whole-fit dispatch + ONE packed readback
        when no checkpoint boundary lands mid-fit, else the chunked path
        with fleet-axis-sharded snapshot cuts. Without a `carry` the member-row
        program (`_fleet_rows`) runs, over the fleet's column `plan` (widths,
        dictionaries). Returns host (flags|None, coeffs [N, d], criteria [N],
        epochs [N])."""
        from . import config
        from .ckpt import faults
        from .ckpt import snapshot as _snapshot
        from .ops import optimizer as opt
        from .parallel import dispatch
        from .utils.packing import packed_device_get

        n = len(self.estimators)
        pack_sharding = self._pack_sharding(mesh)
        hyper_dev = jnp.asarray(hyper)
        if carry is None:
            if dispatch.whole_fit_enabled():
                dispatch.account_whole_fit("fleet")
            with tracing.span("iteration.run", mode="fleet_rows", epochs=gmax, fleet=n):
                packed = dispatch.timed_dispatch(
                    opt._sgd_fleet_rows_whole_fit,
                    X_b, y_b, w_b, loss_func, hyper_dev, d, check_labels, pack_sharding, *plan,
                    start=0, end=gmax,
                )
                flags, coeffs, crits, epochs = opt.unpack_fleet_train_result(
                    opt._read_packed(packed), d, check_labels
                )
                faults.tick("chunk")  # the whole fleet fit is one chunk
            return flags, coeffs, crits, epochs
        ckpt_dir = config.iteration_checkpoint_dir
        planned = 0

        specs = {"fleet": ("data",) * 5 if sharded else ("replicated",) * 5}
        meta = {
            "numBatches": int(y_b.shape[0]),
            "globalBatchSize": gbs,
            "fleetSize": n,
            "dim": d,
        }
        job_key = self._job_key() if ckpt_dir is not None else None
        interval = max(1, int(config.iteration_checkpoint_interval))
        if ckpt_dir is not None:
            template = tuple(np.zeros(l.shape, l.dtype) for l in carry + (crit,))
            snap = _snapshot.load_job_snapshot(
                ckpt_dir, job_key, templates={"fleet": template}, expect_meta=meta
            )
            if snap is not None:
                leaves = _snapshot.stage_section(
                    snap, "fleet", mesh=mesh, specs=specs["fleet"], category="fleet"
                )
                carry, crit = tuple(leaves[:4]), leaves[4]
                planned = snap.epoch

        take_whole = ckpt_dir is None
        if not take_whole:
            take_whole, _ = dispatch.whole_fit_plan(
                start_epoch=planned, max_iter=gmax, checkpoint_interval=interval
            )

        if take_whole:
            if dispatch.whole_fit_enabled():
                dispatch.account_whole_fit("fleet")
            with tracing.span(
                "iteration.run", mode="fleet", epochs=gmax, fleet=n
            ):
                carry, crit, packed = dispatch.timed_dispatch(
                    opt._sgd_fleet_whole_fit,
                    X_b, y_b, w_b, carry, crit, loss_func, hyper_dev,
                    check_labels, pack_sharding,
                    start=planned, end=gmax,
                )
                flags, coeffs, crits, epochs = opt.unpack_fleet_train_result(
                    opt._read_packed(packed), d, check_labels
                )
                if (
                    ckpt_dir is not None
                    and int(epochs.max()) > planned
                    and gmax % interval == 0
                ):
                    _snapshot.save_job_snapshot(
                        ckpt_dir, job_key, {"fleet": carry + (crit,)},
                        epoch=gmax, criteria=float(np.max(crits)),
                        specs=specs, meta=meta,
                    )
                faults.tick("chunk")  # the whole fleet fit is one chunk
            return flags, coeffs, crits, epochs

        # chunked path: the snapshot cadence lands mid-fit
        K = config.iteration_chunk_for(gmax)
        max_iters, tols = hyper[:, 0].astype(np.int64), hyper[:, 1]
        with tracing.span(
            "iteration.run", mode="fleet_chunked", chunk=K, fleet=n
        ):
            stopped = False
            while planned < gmax and not stopped:
                boundary = dispatch.next_boundary(planned, interval)
                end = min(planned + K, gmax, boundary if boundary else gmax)
                with tracing.span("iteration.chunk", epoch=planned, end=end):
                    carry, crit, packed = dispatch.timed_dispatch(
                        opt._sgd_fleet_chunk,
                        X_b, y_b, w_b, carry, crit, loss_func, hyper_dev,
                        jnp.asarray(end, jnp.int32),
                        start=planned, end=end,
                    )
                # ONE packed [N, 2] (epoch, criteria) drain per chunk — the
                # all-members-stopped check needs every member's state
                (chunk_host,) = packed_device_get(packed, sync_kind="drain")
                e_m = np.asarray(chunk_host)[:, 0].astype(np.int64)
                c_m = np.asarray(chunk_host)[:, 1]
                if end % interval == 0:
                    _snapshot.save_job_snapshot(
                        ckpt_dir, job_key, {"fleet": carry + (crit,)},
                        epoch=end, criteria=float(np.max(c_m)),
                        specs=specs, meta=meta,
                    )
                faults.tick("chunk")
                planned = end
                stopped = bool(np.all((e_m >= max_iters) | (c_m <= tols)))
        packed = dispatch.timed_dispatch(
            opt._sgd_fleet_final, carry, crit, hyper_dev, pack_sharding,
            start=planned, end=planned,
        )
        flags, coeffs, crits, epochs = opt.unpack_fleet_train_result(
            opt._read_packed(packed), d, False
        )
        if check_labels:
            flag = packed_device_get(
                opt._binomial_labels_ok(y_b), sync_kind="fit"
            )[0]
            flags = np.full((n,), float(flag))
        return flags, coeffs, crits, epochs

    def _job_key(self) -> str:
        """Fleet job identity: "fleet-" + a hash of every member's own
        checkpoint job key, so two fleets differing in ANY member's
        non-termination params write distinct snapshot files."""
        import hashlib

        from .parallel.iteration import checkpoint_job_key

        member_keys = "|".join(checkpoint_job_key(e) for e in self.estimators)
        return f"fleet-{hashlib.sha1(member_keys.encode()).hexdigest()[:10]}"

    # -- linear stream (out-of-core) driver --------------------------------

    def _fit_linear_stream(
        self, table, mesh, loss_func, hyper, gmax,
        features_col, label_col, weight_col, gbs, validate,
    ) -> List:
        """Out-of-core fleet fit: the stream's chunks are stacked into the
        [X | y | w] segment array ONCE (shared across members — the HBM
        segment residency is paid once for N models) and the whole fleet
        trains as one `_sgd_fleet_stream_whole_fit` dispatch."""
        from .models import _linear
        from .obs import tracing
        from .utils import metrics
        from .ops import optimizer as opt
        from .parallel import dispatch
        from .utils.packing import packed_device_get

        ests = self.estimators
        chunks = list(
            _linear._stream_chunks(table, features_col, label_col, weight_col, validate)
        )
        if not chunks:
            raise ValueError("FitFleet stream fit: the stream yielded no batches")
        shapes = {np.shape(X) for X, _, _ in chunks}
        if len(shapes) != 1:
            raise ValueError(
                "FitFleet stream training needs uniform batch shapes "
                f"(got {sorted(shapes)}); ragged tails fall back to solo "
                "fits (dispatch.whole_fit_fallback.ragged_batches)"
            )
        (b, d) = next(iter(shapes))
        nb = len(chunks)
        packed_np = np.stack(
            [
                np.concatenate(
                    [
                        np.asarray(X, np.float32),
                        np.asarray(y, np.float32)[:, None],
                        (
                            np.ones((b, 1), np.float32)
                            if w is None
                            else np.asarray(w, np.float32)[:, None]
                        ),
                    ],
                    axis=1,
                )
                for X, y, w in chunks
            ]
        )
        sharded = self._decide_sharded(mesh, state_bytes=2 * len(ests) * d * 4)
        metrics.set_gauge("fleet.sharded", 1.0 if sharded else 0.0)
        seg_sharding = NamedSharding(
            mesh,
            P() if sharded else P(None, mesh_lib.DATA_AXIS, None),
        )
        packed_all = h2d.stage_to_device(
            packed_np, seg_sharding, category="streamSegments"
        )
        loss_func = _product_form(packed_all, loss_func)
        carry, crit = self._stage_fleet_state(mesh, len(ests), d, sharded)
        if dispatch.whole_fit_enabled():
            dispatch.account_whole_fit("fleet")
        with tracing.span(
            "iteration.run", mode="fleet_stream", epochs=gmax, fleet=len(ests)
        ):
            carry, crit, packed = dispatch.timed_dispatch(
                opt._sgd_fleet_stream_whole_fit,
                packed_all, carry, crit, loss_func, jnp.asarray(hyper), d,
                self._pack_sharding(mesh),
                start=0, end=gmax,
            )
            (host,) = packed_device_get(packed, sync_kind="fit")
        _, coeffs, crits, epochs = opt.unpack_fleet_train_result(
            np.asarray(host), d, False
        )
        metrics.inc_counter("fleet.examplesTrained", int(np.sum(epochs)) * b)
        models = []
        for i, est in enumerate(ests):
            model = _linear_model_for(est)
            model.coefficient = np.asarray(coeffs[i], np.float64)
            models.append(model)
        return models

    # -- KMeans (Lloyd) driver ---------------------------------------------

    def _fit_kmeans(self, table, mesh) -> List:
        """N Lloyd fits in one vmapped resident program: the staged point
        set is shared; each member contributes its own seed-derived init
        centroids and maxIter. Readback is ONE [N, k*d + k] pack."""
        from .models.clustering import kmeans as km
        from .obs import tracing
        from .utils import metrics
        from .table import StreamTable, as_dense_matrix
        from .parallel import dispatch
        from .utils.packing import packed_device_get
        from .utils.param_utils import update_existing_params

        if isinstance(table, StreamTable):
            raise ValueError(
                "FitFleet does not support out-of-core KMeans yet; fit "
                "StreamTable KMeans members solo"
            )
        ests = self.estimators
        features_col = _require_same(ests, "get_features_col", "featuresCol")
        k = int(_require_same(ests, "get_k", "k"))
        measure = _require_same(ests, "get_distance_measure", "distanceMeasure")
        X = as_dense_matrix(table.column(features_col), allow_device=True)
        n, d = X.shape
        if n < k:
            raise ValueError(f"Number of points ({n}) is less than k ({k})")
        X_host = np.asarray(X, dtype=np.float32)
        # per-member seeded init: selectRandomCentroids per member
        inits = np.stack(
            [
                X_host[
                    np.random.RandomState(e.get_seed() % (2**32)).choice(
                        n, size=k, replace=False
                    )
                ]
                for e in ests
            ]
        )
        max_iters = np.asarray([int(e.get_max_iter()) for e in ests], np.int32)
        sharded = self._decide_sharded(mesh, state_bytes=2 * len(ests) * k * d * 4)
        metrics.set_gauge("fleet.sharded", 1.0 if sharded else 0.0)
        shards = 1 if sharded else mesh_lib.num_data_shards(mesh)
        n_pad = -(-n // shards) * shards
        mat_sharding = NamedSharding(
            mesh, P() if sharded else P(mesh_lib.DATA_AXIS, None)
        )
        row_sharding = NamedSharding(mesh, P() if sharded else P(mesh_lib.DATA_AXIS))
        X_pad, _ = mesh_lib.pad_to_multiple(X_host, shards)
        X_dev = h2d.stage_to_device(X_pad, mat_sharding)
        w_dev = km._unit_weights(n, n_pad, row_sharding) if n_pad != n else None
        init_spec = (
            mesh_lib.fleet_sharding(mesh, 3) if sharded
            else mesh_lib.replicated_sharding(mesh)
        )
        inits_dev = h2d.stage_to_device(inits, init_spec, category="fleet")
        if dispatch.whole_fit_enabled():
            dispatch.account_whole_fit("fleet")
        gmax = int(max_iters.max())
        with tracing.span(
            "iteration.run", mode="fleet", epochs=gmax, fleet=len(ests)
        ):
            packed = dispatch.timed_dispatch(
                km._lloyd_fleet_train,
                X_dev, w_dev, inits_dev, jnp.asarray(max_iters), measure,
                self._pack_sharding(mesh),
                mesh if shards > 1 else None,  # the rows' mesh (_lloyd_partials)
                start=0, end=gmax,
            )
            (host,) = packed_device_get(packed, sync_kind="fit")
        host = np.asarray(host)
        metrics.inc_counter("fleet.examplesTrained", int(np.sum(max_iters)) * n)
        models = []
        for i, est in enumerate(ests):
            model = km.KMeansModel()
            model.centroids = np.asarray(
                host[i, : k * d].reshape(k, d), dtype=np.float64
            )
            model.weights = np.asarray(host[i, k * d :], dtype=np.float64)
            update_existing_params(model, est)
            models.append(model)
        return models


# a fleet fit is one fit to the observability layer: `fit.total`, the
# outermost fit (`fit.outer`: one job, N models) and the `stage.fit` span
tracing.instrument_stage_methods(FitFleet)


# ---------------------------------------------------------------------------
# fleet -> lifecycle bridge
# ---------------------------------------------------------------------------

def fleet_model_arrays(model) -> Tuple:
    """The swap-protocol array tuple for a fleet-trained model — the same
    leaves the model's `model_arrays()` would publish, copied: a linear
    member's coefficient is a row of its fleet's readback, which a published
    version should not keep."""
    if hasattr(model, "centroids"):
        return (
            np.array(model.centroids, np.float32),
            np.array(model.weights, np.float32),
        )
    return (np.array(model.coefficient, np.float32),)


def promote_fleet_winner(lifecycle, models: Sequence, scores: Sequence[float], mode: str = "max"):
    """Promote the fleet winner (by held-out metric) straight into a
    `ModelLifecycle` version ring: picks argmax (`mode="max"`) or argmin
    (`mode="min"`) of `scores`, publishes that member's arrays through
    `lifecycle.promote` (gates, retention, and rollback semantics apply
    unchanged). Returns (winner_index, ModelVersion)."""
    from .utils import metrics

    if len(models) != len(scores):
        raise ValueError(
            f"{len(models)} models but {len(scores)} scores — every fleet "
            "member needs its held-out metric"
        )
    if mode not in ("max", "min"):
        raise ValueError(f"Unknown winner mode {mode!r} (use 'max' or 'min')")
    scores = np.asarray(list(scores), np.float64)
    if np.any(np.isnan(scores)):
        raise ValueError("fleet winner selection got NaN scores")
    winner = int(np.argmax(scores) if mode == "max" else np.argmin(scores))
    version = lifecycle.promote(fleet_model_arrays(models[winner]))
    metrics.inc_counter("fleet.winnerPromoted")
    metrics.set_gauge("fleet.winnerIndex", float(winner))
    metrics.set_gauge("fleet.winnerScore", float(scores[winner]))
    return winner, version
