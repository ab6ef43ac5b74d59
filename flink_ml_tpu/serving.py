"""Micro-batch serving — double-buffered, overload-graceful fused inference.

The throughput path the ROADMAP north star asks for: drive a fused
`PipelineModel` transform plan (pipeline.py) over an unbounded stream of
mini-batches at a bounded, stage-count-independent host-sync cost — and
keep that true when the offered load exceeds capacity or a dependency
flakes. Mechanisms on top of the fusion planner:

1. **Bucket padding** — a jitted segment program is specialized to its
   input shapes, so free-running batch sizes would recompile every batch.
   Each incoming batch is padded up to the smallest configured bucket
   (default: powers of two) by REPEATING ITS LAST ROW; compile count is
   bounded by the number of buckets, and the padding rows are copies of a
   real row, so they can never fire a validation guard the real data
   would not. Outputs are sliced back to the true row count on device.

2. **Bounded in-flight window** — the transform of batch i is dispatched
   with its exit guard drain DEFERRED (PipelineModel.transform_deferred),
   and the (output, pending-guards) pair parks in a `flow.BoundedChannel`
   of capacity `in_flight`. Batch i+1's H2D upload and segment dispatch
   overlap batch i's device compute; the single blocking guard readback
   happens only when a batch leaves the window. Per-batch host syncs are
   therefore O(1) regardless of pipeline depth.

3. **Admission control + deadlines** (`submit`/`results`, the push API) —
   an admission `BoundedChannel` with the `reject` policy in front of the
   dispatch loop: once `admission` requests wait, `submit` fast-fails
   with a typed `ServerOverloaded` carrying the live queue depth, so an
   overloaded server sheds load at the door with bounded memory and
   bounded client latency instead of growing a queue until the host
   dies. A request may carry a deadline: expired-before-dispatch requests
   are shed without paying compute (`serving.deadlineMiss` +
   status `"expired"`), finished-after-deadline results deliver marked
   `"late"`.

4. **Transient-fault resilience** — batch dispatch runs under
   `flow.with_retries` (`config.transient_retries`, the
   `serving.batch` fault site), so a transiently-failing backend retries
   with backoff instead of killing the stream; non-transient errors
   surface per-request (`status "error"`), never silently dropped. A
   `flow.StragglerWatchdog` times every dispatch and flags executions
   beyond `config.straggler_factor`× the trailing mean. `health()`
   returns a `ServerHealth` snapshot of all of it.

5. **Model hot-swap hooks** — a `lifecycle.ModelLifecycle` attached via
   the `lifecycle` param receives every retired batch's guard outcome:
   swap-capable stages in the served plan (online models) take their
   model tensors as versioned runtime operands, so a trainer promoting
   versions mid-serve never pauses this server, and a run of guard
   errors rolls traffic back to the last-good version automatically
   (docs/model_lifecycle.md).

6. **Continuous batching + the multi-tenant model store** — with
   `batching="continuous"` the dispatch worker admits requests into the
   FORMING batch mid-flight instead of dispatching each submit alone: a
   forming batch goes out the moment it fills its target bucket
   (`form_rows`) OR its oldest request's deadline margin hits the
   forming budget (`config.serving_form_budget_ms`), so throughput at
   saturation gets full buckets while latency at low offered QPS stays
   bounded by the budget. `batching="fixed"` is the classic baseline
   (wait for a full batch, however long that takes); results are
   bit-identical across all three modes because the kernels are row-wise
   and the pad rows are copies of real rows. Requests carry an optional `tenant`: a forming batch never
   coalesces across tenants, each tenant may route to its own model via
   a `data.modelstore.ModelStore` (HBM-paged under an LRU byte budget —
   far more models than fit on device serve from one mesh, zero
   recompiles on page-in because model tensors are runtime operands),
   and per-tenant reject-policy quota gates keep one tenant's overload
   from starving another (docs/serving.md).

Pull-loop (`serve`) results are yielded IN ORDER. Push-loop results
retire in dispatch order, which is submission order WITHIN a tenant
(forming batches flush FIFO per tenant); across tenants, coalescing may
legitimately reorder. A batch's guard failure (e.g. Bucketizer
handleInvalid='error') raises when that batch is yielded — at most
`in_flight` batches later than the eager path would have raised, never
reordered and never dropped. When the consumer abandons `serve` early (a
`close()`/GeneratorExit) or a deferred guard error terminates it, the
still-in-flight window is drained and released — no staged device buffers
or queue slots leak (`serving.cancelled` counts the released batches).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import config, flow
from .ckpt import faults
from .obs import hist, memledger, timeline, tracing
from .parallel.prefetch import next_bucket, pad_rows, slice_rows, stage_to_device
from .pipeline import PipelineModel, _drain_guards
from .table import SparseBatch, Table
from .utils import metrics

__all__ = [
    "MicroBatchServer",
    "ServerHealth",
    "ServerOverloaded",
    "ServeResult",
    "serve_stream",
]

# The bucket schedule and repeat-last-row pad live in
# parallel/prefetch.py, shared with the stream-training staging paths —
# same policy, same guard-safety argument, one implementation.
_next_bucket, _pad_rows, _slice_rows = next_bucket, pad_rows, slice_rows

BATCHING_MODES = ("request", "fixed", "continuous")


class ServerOverloaded(flow.ChannelRejected):
    """`submit` fast-fail: the admission queue (or the submitting
    tenant's quota gate — `channel` = `serving.tenant.<name>`) is full.
    Carries the live queue depth and capacity (inherited from
    `flow.ChannelRejected`) so a client can back off / divert instead of
    parsing a message."""


@dataclass
class ServeResult:
    """One retired request from the push API, FIFO per tenant.
    `status` is `"ok"`, `"late"` (finished past its deadline), `"expired"`
    (deadline passed before dispatch — no compute paid, `table` is None)
    or `"error"` (`error` holds the exception; the stream continues)."""

    seq: int
    status: str
    table: Optional[Table] = None
    error: Optional[BaseException] = None
    tenant: Optional[str] = None


@dataclass
class ServerHealth:
    """Point-in-time server snapshot — the serving analogue of
    `DeviceEpochCache.stats`: every overload decision the server made,
    queriable without scraping the metrics registry."""

    inFlight: int  # window capacity
    windowDepth: int  # transformed-but-undrained batches right now
    admissionCapacity: int
    admissionDepth: int  # submitted-but-undispatched requests right now
    submitted: int  # requests accepted by submit()
    rejected: int  # submits refused at the door (ServerOverloaded)
    completed: int  # results delivered (any status)
    expired: int  # shed before dispatch: deadline already passed
    late: int  # delivered after their deadline
    errors: int  # per-request failures delivered as status "error"
    retries: int  # transient-fault retries paid by batch dispatch
    cancelled: int  # in-flight batches released by an early serve() exit
    bucketsSeen: int
    emaBatchMs: float  # dispatch trailing-mean latency (watchdog EMA)
    stragglers: int  # dispatches flagged beyond straggler_factor x mean
    # HBM ledger view (obs/memledger.py): total ledgered device-resident
    # bytes and the global peak watermark at snapshot time — memory sits
    # on the SLO surface next to the stage latencies, because the paging
    # work (ROADMAP item 3) is graded against exactly these numbers
    hbmLiveBytes: int = 0
    hbmPeakBytes: int = 0
    # per-stage latency percentiles from obs/hist.py (p50/p90/p99/p999 +
    # count per stage). EVERY stage label is present; a stage with zero
    # observations maps to None — never percentiles interpolated from an
    # empty bucket array (the Prometheus exporter likewise skips empty
    # histograms entirely)
    stageLatencyMs: Dict[str, Optional[Dict[str, float]]] = None
    # per-tenant quota-gate view: {tenant: {admitted, rejected, depth,
    # capacity}} for every tenant that has a quota gate (empty when no
    # tenant quotas are configured) — the fairness soak reads this
    tenantAdmission: Dict[str, Dict[str, int]] = None
    # attached ModelStore stats (models/resident/bytes/hits/misses/
    # evictions) or None when the server serves a single model
    modelStore: Optional[Dict[str, int]] = None

    #: The serving stage-attribution histograms (obs/hist.py names, all
    #: in milliseconds): queue-wait (submit -> dequeue), forming wait
    #: (dequeue -> the coalesced batch's flush; continuous/fixed modes
    #: only), batch formation (pad + H2D upload), dispatch (fused-plan
    #: launch), readback (the one blocking guard drain), and the
    #: remaining deadline margin at delivery (clamped at 0; lateness
    #: lands in `serving.lateByMs` and the deadlineMiss.late counter).
    STAGES = (
        ("queueWait", "serving.queueWaitMs"),
        ("formWait", "serving.formWaitMs"),
        ("batchForm", "serving.batchFormMs"),
        ("dispatch", "serving.dispatchMs"),
        ("readback", "serving.readbackMs"),
        ("deadlineMargin", "serving.deadlineMarginMs"),
    )


class _Forming:
    """One tenant's forming batch: requests coalescing toward a bucket.
    `flush_at` is the earliest member's forming deadline — `inf` under
    fixed batching (only a full bucket or server close flushes)."""

    __slots__ = ("tenant", "sig", "reqs", "rows", "flush_at")

    def __init__(self, tenant, sig):
        self.tenant = tenant
        self.sig = sig
        self.reqs: List[Tuple[int, Table, Optional[float], float]] = []
        self.rows = 0
        self.flush_at = float("inf")

    def add(self, seq: int, batch: Table, deadline: Optional[float], flush_at: float) -> None:
        self.reqs.append((seq, batch, deadline, time.monotonic()))
        self.rows += batch.num_rows
        self.flush_at = min(self.flush_at, flush_at)


class MicroBatchServer:
    """Drives fused transform plans over a batch stream.

    `in_flight` bounds the transformed-but-undrained window (default
    `config.serving_in_flight`); `buckets` optionally pins the padded
    batch-shape schedule (sorted ascending), otherwise batches pad to the
    next power of two. `device_input=True` uploads each padded batch's
    numeric host columns to device HBM before dispatch, so the whole
    pipeline — upload included — runs ahead of the previous batch's drain.
    `admission` bounds the push API's submit queue (default
    `config.serving_admission`); `deadline_ms` is the default per-request
    deadline (None = none); `retries` the transient-fault retry budget for
    batch dispatch (default `config.transient_retries`).

    Batching policy (`batching`): `"request"` (default) dispatches every
    submitted batch alone; `"continuous"` coalesces per-tenant forming
    batches that flush on bucket-full OR forming-budget expiry
    (`form_budget_ms`, default `config.serving_form_budget_ms`);
    `"fixed"` flushes only on bucket-full (the classic fixed-batch
    baseline). `form_rows` is the target bucket (default: the largest
    configured bucket, else 64).

    Multi-tenancy: pass a `data.modelstore.ModelStore` as `store` and
    submit with `tenant=<key>` — each request dispatches against its
    tenant's (HBM-paged) model. Per-tenant admission quotas come from
    the store's registrations or the `tenant_quotas` mapping; a tenant
    past its quota gets `ServerOverloaded` without consuming shared
    admission capacity.

    Two consumption styles:

    - `serve(stream)` — the pull loop: the caller owns pacing, the window
      gives lossless credit-based backpressure (the `block` policy).
    - `submit(batch)` + `results()` — the push loop: a dispatch worker
      consumes an admission queue with the `reject` policy; `submit`
      raises `ServerOverloaded` once `admission` requests wait.
    """

    def __init__(
        self,
        model: Optional[PipelineModel] = None,
        in_flight: Optional[int] = None,
        buckets: Optional[Sequence[int]] = None,
        device_input: bool = True,
        admission: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        retries: Optional[int] = None,
        lifecycle=None,
        batching: str = "request",
        form_rows: Optional[int] = None,
        form_budget_ms: Optional[float] = None,
        store=None,
        tenant_quotas: Optional[Dict[str, int]] = None,
    ):
        if model is None and store is None:
            raise TypeError("MicroBatchServer needs a model, a ModelStore, or both")
        if model is not None and not isinstance(model, PipelineModel):
            raise TypeError(f"MicroBatchServer serves a PipelineModel, got {type(model).__name__}")
        if batching not in BATCHING_MODES:
            raise ValueError(f"unknown batching mode {batching!r} (one of {BATCHING_MODES})")
        self.model = model
        self.store = store
        self.batching = batching
        self.in_flight = max(1, int(in_flight if in_flight is not None else config.serving_in_flight))
        self.buckets = sorted(int(b) for b in buckets) if buckets else None
        self.form_rows = max(
            1,
            int(
                form_rows
                if form_rows is not None
                else (self.buckets[-1] if self.buckets else 64)
            ),
        )
        self.form_budget_ms = (
            form_budget_ms if form_budget_ms is not None else config.serving_form_budget_ms
        )
        self.device_input = device_input
        self.admission = max(
            1, int(admission if admission is not None else config.serving_admission)
        )
        self.deadline_ms = deadline_ms if deadline_ms is not None else config.serving_deadline_ms
        self.retries = retries
        # optional lifecycle.ModelLifecycle: every retired batch's guard
        # outcome feeds its sliding health window, so a run of guard
        # errors (a bad promotion that slipped the gate) triggers the
        # automatic rollback WITHOUT restarting this server — the swap is
        # a pointer exchange the next batch picks up
        self.lifecycle = lifecycle
        self.watchdog = flow.StragglerWatchdog("serving.batch")
        self._tenant_quotas = dict(tenant_quotas) if tenant_quotas else {}
        self._tenant_gates: Dict[str, flow.BoundedChannel] = {}
        self._buckets_seen: set = set()
        self._counts: Dict[str, int] = {
            "completed": 0,
            "expired": 0,
            "late": 0,
            "errors": 0,
            "retries": 0,
            "cancelled": 0,
        }
        self._window: Optional[flow.BoundedChannel] = None  # latest serve window
        self._requests: Optional[flow.BoundedChannel] = None
        self._out: Optional[flow.BoundedChannel] = None
        self._worker = None
        self._start_lock = threading.Lock()
        self._seq = 0

    # -- batch staging -------------------------------------------------------
    def _stage_batch(self, batch: Table) -> Tuple[Table, int]:
        """Pad `batch` to its bucket and (optionally) upload numeric host
        columns — the H2D leg of the double buffer. All uploadable columns
        go through ONE `device_put` call (per-column puts would each pay a
        dispatch)."""
        n = batch.num_rows
        bucket = _next_bucket(n, self.buckets)
        self._buckets_seen.add(bucket)
        cols: Dict[str, Any] = {}
        uploads: Dict[str, Any] = {}
        for name in batch.column_names:
            col = _pad_rows(batch.column(name), n, bucket)
            if self.device_input and self._uploadable(col):
                uploads[name] = col
            else:
                cols[name] = col
        if uploads:
            from .table import register_device_pytrees

            register_device_pytrees()  # SparseBatch uploads as a pytree
            # accounted (h2d.bytes/count) + ledgered: the in-flight window
            # holds these buffers until the batch retires, so `serving`
            # residency tracks the window depth live
            uploads = stage_to_device(uploads, category="serving")
        return Table(
            {name: uploads.get(name, cols.get(name)) for name in batch.column_names}
        ), n

    @staticmethod
    def _uploadable(col) -> bool:
        if isinstance(col, SparseBatch):
            return isinstance(col.indices, np.ndarray)
        return (
            isinstance(col, np.ndarray)
            and col.dtype != object
            and col.dtype.kind not in ("U", "S")
        )

    def _model_for(self, tenant: Optional[str]) -> PipelineModel:
        """Resolve a request's model: the tenant's store entry (paged in
        on the spot — an LRU hit is a dict touch, a miss stages through
        the accounted funnel) or the server-wide default."""
        if self.store is not None and tenant is not None:
            return self.store.acquire(tenant)
        if self.model is None:
            raise TypeError(
                "MicroBatchServer has no default model: submit with tenant= "
                "or construct with model="
            )
        return self.model

    def _dispatch(self, batch: Table, index: int, model: Optional[PipelineModel] = None):
        """Stage + dispatch one batch under the transient-retry budget
        and the straggler watchdog. The `serving.batch` fault site sits
        inside the retried unit, so a `faults.flaky` plan exercises the
        retry path end to end; staging re-runs with the dispatch (an
        upload that failed mid-flight cannot be trusted half-done)."""
        served = model if model is not None else self._model_for(None)

        def attempt():
            faults.tick("serving.batch")
            t0 = time.perf_counter()
            staged, n = self._stage_batch(batch)
            t1 = time.perf_counter()
            out, pending = served.transform_deferred(staged)
            t2 = time.perf_counter()
            # stage attribution (obs/hist.py): where a request's latency
            # sits BEFORE the blocking drain — the serving mirror of the
            # training loop's dispatch-wall split
            hist.record("serving.batchFormMs", (t1 - t0) * 1000.0)
            hist.record("serving.dispatchMs", (t2 - t1) * 1000.0)
            if timeline.enabled():
                timeline.record_complete(
                    timeline.LANE_SERVING,
                    "serving.batchForm",
                    int(t0 * 1e9),
                    int((t1 - t0) * 1e9),
                    index=index,
                )
                timeline.record_complete(
                    timeline.LANE_SERVING,
                    "serving.dispatch",
                    int(t1 * 1e9),
                    int((t2 - t1) * 1e9),
                    index=index,
                )
            return out, pending, n

        with tracing.span("serving.batch", index=index, op="dispatch"):
            with self.watchdog.observe():
                return flow.with_retries(
                    attempt,
                    site="serving.batch",
                    retries=self.retries,
                    on_retry=lambda e, a: self._count("retries"),
                )

    def _count(self, key: str, n: int = 1) -> None:
        self._counts[key] = self._counts.get(key, 0) + n

    # -- warmup: the no-compile serving SLA ----------------------------------
    @staticmethod
    def _example_rows(example: Table, rows: int) -> Table:
        """Resize an example batch to exactly `rows` rows (slice down or
        repeat-last-row pad up) so its staged form lands on one bucket."""
        cols: Dict[str, Any] = {}
        n = example.num_rows
        for name in example.column_names:
            col = example.column(name)
            cols[name] = (
                _slice_rows(col, rows) if n >= rows else _pad_rows(col, n, rows)
            )
        return Table(cols)

    def warmup(
        self,
        example: Table,
        tenants: Optional[Sequence[Optional[str]]] = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> Dict[str, float]:
        """Drive every (tenant x bucket) serving program once ahead of
        traffic, so the first real request finds its program resident.

        `example` is a schema template (one real batch — column names,
        dtypes, sparse layouts); each declared bucket gets a synthetic
        batch of exactly that many rows dispatched through the normal
        `_dispatch` funnel, which pages the tenant's model in through
        the ModelStore and compiles (or bank-loads) the fused segment
        program. With an active AOT program bank
        (`config.program_bank_dir`, compilebank.py) the compiled
        programs back-fill the bank, so the NEXT process's warmup is
        pure warm-loads — zero traces, zero XLA compiles — and its
        first request meets the no-compile SLA
        (`scripts/coldstart_smoke.py` asserts exactly this).

        Returns {"programs", "warmupMs", "bankHits", "bankMisses"} for
        the run; a guard tripped by synthetic rows is swallowed (the
        program is compiled either way — warmup must never take the
        server down)."""
        from .utils.metrics import snapshot_delta

        if buckets is None:
            buckets = self.buckets or [_next_bucket(self.form_rows, None)]
        buckets = sorted({int(b) for b in buckets})
        if tenants is None:
            tenants = list(self.store.keys()) if self.store is not None else [None]
        if self.store is not None:
            # page every tenant's model in first: warmup compiles against
            # resident model operands exactly as live dispatches will
            self.store.prefetch([t for t in tenants if t is not None], wait=True)
        t0 = time.perf_counter()
        before = metrics.snapshot()
        programs = 0
        for tenant in tenants:
            model = self._model_for(tenant)
            for bucket in buckets:
                synth = self._example_rows(example, bucket)
                try:
                    out, pending, n = self._dispatch(synth, index=-1, model=model)
                    self._finish(out, pending, n)
                except ValueError:
                    pass  # a guard fired on the synthetic rows; program is live
                programs += 1
        wall_ms = (time.perf_counter() - t0) * 1000.0
        metrics.record_time("serving.warmup", wall_ms / 1000.0)
        delta = snapshot_delta(before, metrics.snapshot())["counters"]
        return {
            "programs": float(programs),
            "warmupMs": wall_ms,
            "bankHits": float(delta.get("bank.hits", 0)),
            "bankMisses": float(delta.get("bank.misses", 0)),
        }

    def _finish(self, out: Table, pending: List[Tuple[str, Any]], n: int) -> Table:
        """Retire one batch from the in-flight window: ONE packed guard
        readback (the batch's only blocking sync), then slice the padding
        off on device. The guard outcome feeds the attached lifecycle's
        health window (rollback trigger)."""
        t0 = time.perf_counter()
        try:
            _drain_guards(pending)
        except Exception as e:
            if self.lifecycle is not None:
                self.lifecycle.record_guard_error(e)
            raise
        finally:
            dt = time.perf_counter() - t0
            hist.record("serving.readbackMs", dt * 1000.0)
            if timeline.enabled():
                timeline.record_complete(
                    timeline.LANE_SERVING,
                    "serving.readback",
                    int(t0 * 1e9),
                    int(dt * 1e9),
                )
        if self.lifecycle is not None:
            self.lifecycle.record_serve_ok()
        if out.num_rows == n:
            return out
        return Table({name: _slice_rows(out.column(name), n) for name in out.column_names})

    def _release(self, window: flow.BoundedChannel) -> None:
        """Early-exit cleanup: drop every still-in-flight batch — staged
        device buffers and pending guard handles release with their
        references, and the window's queue slots free — so an abandoned
        serve() (consumer close, deferred-guard error) leaks nothing.
        The abandoned guards are never drained: raising NEW errors out of
        a generator teardown would mask the one the consumer saw."""
        leaked = window.cancel()
        if leaked:
            metrics.inc_counter("serving.cancelled", len(leaked))
            self._count("cancelled", len(leaked))
        metrics.set_gauge("serving.buckets", len(self._buckets_seen))

    # -- the pull serving loop ----------------------------------------------
    def serve(self, stream: Iterable[Table]) -> Iterator[Table]:
        """Transform every batch of `stream`, yielding output Tables in
        input order. Output columns may be device-resident; callers that
        need host values materialize them (that readback is theirs)."""
        window = flow.BoundedChannel(self.in_flight, policy=flow.BLOCK, name="serving.window")
        self._window = window
        num_batches = 0
        metrics.set_gauge("serving.in_flight", self.in_flight)
        try:
            for batch in stream:
                entry = self._dispatch(batch, num_batches)
                if not window.offer(entry):  # window full: retire the oldest
                    # tpulint: disable=untimed-wait -- single-threaded pull loop: offer() just returned False, so the window is non-empty and get() cannot block
                    yield self._finish(*window.get())
                    window.offer(entry)
                num_batches += 1
                metrics.inc_counter("serving.batches")
                metrics.inc_counter("serving.records", entry[2])
                metrics.set_gauge("serving.buckets", len(self._buckets_seen))
            while len(window):
                # tpulint: disable=untimed-wait -- single-threaded pull loop: guarded by len(window) > 0, get() cannot block
                yield self._finish(*window.get())
        finally:
            self._release(window)

    # -- the push serving loop: admission control + deadlines ----------------
    def start(self) -> None:
        """Bring up the dispatch worker and its channels (idempotent;
        `submit` auto-starts). Locked double-check: a `results()`
        consumer thread and the first `submit()` race here, and two
        winners would each spawn a dispatch worker over its own channel
        pair — the loser's results would emit into an orphaned stream."""
        if self._worker is not None:
            return
        with self._start_lock:
            if self._worker is not None:
                return
            self._requests = flow.BoundedChannel(
                self.admission, policy=flow.REJECT, name="serving.admit"
            )
            # results buffer: sized so a retired batch never blocks the
            # worker while the admission queue and window both stay full —
            # the consumer's pull pace backpressures through it. Forming
            # batches can coalesce many admitted requests into one window
            # entry, so the retire fan-out is still bounded by `admission`
            self._out = flow.BoundedChannel(
                self.admission + self.in_flight + 1, policy=flow.BLOCK, name="serving.results"
            )
            metrics.set_gauge("serving.in_flight", self.in_flight)
            # assigned last: `submit`/`results` treat a non-None worker as
            # "channels are live", so this publish orders after them
            self._worker = flow.spawn(self._run, name="serving.dispatch")

    # -- per-tenant quota gates ----------------------------------------------
    def _quota_gate(self, tenant: Optional[str]) -> Optional[flow.BoundedChannel]:
        """The tenant's reject-policy admission gate (created lazily from
        `tenant_quotas` or the store's registration), or None for
        unquota'd tenants. Each admitted request holds one credit until
        it leaves the queue+forming pipeline (dispatch/expiry)."""
        if tenant is None:
            return None
        gate = self._tenant_gates.get(tenant)
        if gate is None:
            quota = self._tenant_quotas.get(tenant)
            if quota is None and self.store is not None and tenant in self.store:
                quota = self.store.quota(tenant)
            if quota is None:
                return None
            gate = flow.BoundedChannel(
                max(1, int(quota)), policy=flow.REJECT, name=f"serving.tenant.{tenant}"
            )
            self._tenant_gates[tenant] = gate
        return gate

    def _quota_release(self, tenant: Optional[str]) -> None:
        if tenant is None:
            return
        gate = self._tenant_gates.get(tenant)
        if gate is None:
            return
        try:
            gate.get(timeout=0)
        except (TimeoutError, flow.ChannelClosed):
            pass

    def submit(
        self,
        batch: Table,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> int:
        """Admit one batch, returning its sequence number. Raises
        `ServerOverloaded` (with the live queue depth) when `admission`
        requests already wait — or when `tenant`'s quota gate is full —
        the typed fast-fail of the `reject` policy. `deadline_ms`
        overrides the server default; `tenant` routes to that tenant's
        store model and quota."""
        if self._worker is None:
            self.start()
        if self.store is not None and tenant is not None and tenant not in self.store:
            raise KeyError(f"tenant {tenant!r} is not registered in the model store")
        ms = deadline_ms if deadline_ms is not None else self.deadline_ms
        deadline = None if ms is None else time.monotonic() + ms / 1000.0
        seq = self._seq
        gate = self._quota_gate(tenant)
        if gate is not None:
            try:
                gate.put(seq)
            except flow.ChannelRejected as e:
                metrics.inc_counter("serving.rejected")
                metrics.inc_counter(f"serving.rejected.tenant.{tenant}")
                raise ServerOverloaded(e.channel, e.depth, e.capacity) from None
        try:
            self._requests.put((seq, tenant, batch, deadline, time.monotonic()))
        except flow.ChannelRejected as e:
            if gate is not None:  # refund the tenant credit
                self._quota_release(tenant)
            metrics.inc_counter("serving.rejected")
            raise ServerOverloaded(e.channel, e.depth, e.capacity) from None
        self._seq += 1
        metrics.inc_counter("serving.batches")
        metrics.inc_counter("serving.records", batch.num_rows)
        return seq

    def close(self) -> None:
        """No more submits; the worker drains what was admitted (flushing
        any partial forming batches) and closes the results stream."""
        if self._requests is not None:
            self._requests.close()

    def results(self) -> Iterator[ServeResult]:
        """Retired requests (`ServeResult`), FIFO per tenant; ends when
        `close()` has been called and every admitted request retired."""
        if self._worker is None:
            self.start()
        yield from self._out

    def health(self) -> ServerHealth:
        """A `ServerHealth` snapshot of queues, overload decisions, retry
        spend, dispatch latency, and the per-stage latency percentiles
        (`stageLatencyMs`, from the obs/hist.py histograms)."""
        stage_latency: Dict[str, Optional[Dict[str, float]]] = {}
        for label, hist_name in ServerHealth.STAGES:
            p = hist.percentiles(hist_name)
            # a stage with zero observations reports None — percentiles
            # interpolated from an empty bucket array would be fiction
            stage_latency[label] = (
                None
                if p is None
                else {k: p[k] for k in ("count", "p50", "p90", "p99", "p999")}
            )
        tenants: Dict[str, Dict[str, int]] = {}
        for tenant, gate in self._tenant_gates.items():
            tenants[tenant] = {
                "admitted": gate.stats.puts,
                "rejected": gate.stats.rejected,
                "depth": len(gate),
                "capacity": gate.capacity,
            }
        window_depth = len(self._window) if self._window is not None else 0
        adm_depth = len(self._requests) if self._requests is not None else 0
        rejected = (
            self._requests.stats.rejected if self._requests is not None else 0
        )
        rejected += sum(g.stats.rejected for g in self._tenant_gates.values())
        submitted = self._requests.stats.puts if self._requests is not None else 0
        return ServerHealth(
            inFlight=self.in_flight,
            windowDepth=window_depth,
            admissionCapacity=self.admission,
            admissionDepth=adm_depth,
            submitted=submitted,
            rejected=rejected,
            completed=self._counts["completed"],
            expired=self._counts["expired"],
            late=self._counts["late"],
            errors=self._counts["errors"],
            retries=self._counts["retries"],
            cancelled=self._counts["cancelled"],
            bucketsSeen=len(self._buckets_seen),
            emaBatchMs=self.watchdog.trailing_mean_s * 1000.0,
            stragglers=metrics.get_counter("flow.straggler.serving.batch", 0),
            hbmLiveBytes=memledger.live_bytes(),
            hbmPeakBytes=memledger.peak_bytes(),
            stageLatencyMs=stage_latency,
            tenantAdmission=tenants,
            modelStore=self.store.stats if self.store is not None else None,
        )

    def _run(self) -> None:
        """Dispatch worker: admission queue → (forming) → window →
        results, deadlines enforced at every hop. Any worker-level
        failure closes the results channel with the error — consumers
        re-raise instead of hanging."""
        window = flow.BoundedChannel(self.in_flight, policy=flow.BLOCK, name="serving.window")
        self._window = window
        try:
            if self.batching == "request":
                self._run_per_request(window)
            else:
                self._run_forming(window)
            while len(window):
                # tpulint: disable=untimed-wait -- dispatch-worker-local window: guarded by len(window) > 0, get() cannot block
                self._retire(window.get())
            self._out.close()
        except BaseException as e:  # worker death must not strand consumers
            self._out.close(error=e)
        finally:
            self._release(window)

    def _run_per_request(self, window: flow.BoundedChannel) -> None:
        """The classic loop: every submitted batch dispatches alone."""
        for seq, tenant, batch, deadline, submitted in self._requests:
            hist.record(
                "serving.queueWaitMs", (time.monotonic() - submitted) * 1000.0
            )
            self._quota_release(tenant)
            if deadline is not None and time.monotonic() > deadline:
                # shed BEFORE paying staging/compute: the client
                # already gave up on this request. Cause-attributed:
                # expired-IN-QUEUE (vs late-after-dispatch below) —
                # `serving.deadlineMiss` stays the compatibility sum
                metrics.inc_counter("serving.deadlineMiss")
                metrics.inc_counter("serving.deadlineMiss.expired")
                self._count("expired")
                self._emit(ServeResult(seq, "expired", tenant=tenant))
                continue
            try:
                model = self._model_for(tenant)
                out, pending, n = self._dispatch(batch, seq, model=model)
            except Exception as e:  # per-request failure: stream survives
                self._count("errors")
                self._emit(ServeResult(seq, "error", error=e, tenant=tenant))
                continue
            entry = (((seq, deadline, 0, n, tenant),), out, pending, n)
            if not window.offer(entry):
                # tpulint: disable=untimed-wait -- dispatch-worker-local window: offer() just returned False, so the window is non-empty and get() cannot block
                self._retire(window.get())
                window.offer(entry)

    # -- continuous batching: the forming buffer -----------------------------
    def _run_forming(self, window: flow.BoundedChannel) -> None:
        """Admit requests into per-tenant FORMING batches mid-flight. A
        batch flushes on bucket-full (`form_rows`), forming-budget expiry
        (continuous mode), an incompatible next request (per-tenant FIFO
        is preserved: the old batch always dispatches first), or close."""
        forming: Dict[Optional[str], _Forming] = {}
        while True:
            timeout = None
            if forming:
                soonest = min(g.flush_at for g in forming.values())
                if soonest != float("inf"):
                    timeout = max(0.0, soonest - time.monotonic())
            if timeout is None and len(window):
                # no flush pending but batches sit in flight: poll the
                # queue and, when it is empty, take the blocking readback
                # NOW — a finished result must not wait for the NEXT
                # arrival (or close) to retire. Under load the poll finds
                # a queued request and the double buffer stays pipelined.
                timeout = 0.0
            try:
                req = self._requests.get(timeout=timeout)
            except TimeoutError:  # a forming budget expired: flush what's due
                self._flush_due(forming, window)
                if timeout == 0.0 and len(window):
                    # tpulint: disable=untimed-wait -- dispatch-worker-local window: guarded by len(window) > 0, get() cannot block
                    self._retire(window.get())
                continue
            except flow.ChannelClosed:
                break
            self._admit_forming(req, forming, window)
            self._flush_due(forming, window)
        for tenant in list(forming):  # close(): partial batches still dispatch
            self._flush_group(forming.pop(tenant), window)

    def _form_flush_at(self, deadline: Optional[float]) -> float:
        """A request's forming deadline: flush when its deadline margin
        hits the forming budget (it must still dispatch + compute inside
        the margin), and never hold a request in FORMING longer than the
        budget itself. Both legs are measured from admission into
        forming, not from submit: under a backlog the queue wait alone
        exceeds the budget, and an already-blown margin cannot be saved
        by flushing a tiny batch — it would only shrink every batch to
        ~1 request and collapse saturated goodput, which is exactly the
        regime where full buckets matter most. Fixed batching never
        flushes on time — only on a full bucket."""
        if self.batching == "fixed":
            return float("inf")
        budget = self.form_budget_ms / 1000.0
        now = time.monotonic()
        flush_at = now + budget
        if deadline is not None and deadline - budget > now:
            flush_at = min(flush_at, deadline - budget)
        return flush_at

    @staticmethod
    def _batch_sig(batch: Table) -> Optional[tuple]:
        """Coalescing signature: two batches may share a forming batch iff
        their column names, kinds, dtypes and trailing shapes all match
        (row-wise kernels make the concatenation semantically the union
        of the requests). None = host-concat is unsafe (device-resident
        or object columns): the request dispatches alone."""
        sig = []
        for name in batch.column_names:
            col = batch.column(name)
            if isinstance(col, SparseBatch):
                if not isinstance(col.indices, np.ndarray):
                    return None
                sig.append(
                    ("sparse", name, col.size, col.indices.shape[1:], str(col.values.dtype))
                )
            elif isinstance(col, np.ndarray) and col.dtype != object:
                sig.append(("np", name, col.shape[1:], str(col.dtype)))
            else:
                return None
        return tuple(sig)

    @staticmethod
    def _concat_batches(batches: List[Table]) -> Table:
        """Host-side concatenation of signature-compatible batches — the
        forming batch the fused plan sees as ONE bucket-padded dispatch."""
        cols: Dict[str, Any] = {}
        for name in batches[0].column_names:
            vals = [b.column(name) for b in batches]
            first = vals[0]
            if isinstance(first, SparseBatch):
                cols[name] = SparseBatch(
                    first.size,
                    np.concatenate([v.indices for v in vals], axis=0),
                    np.concatenate([v.values for v in vals], axis=0),
                )
            else:
                cols[name] = np.concatenate(vals, axis=0)
        return Table(cols)

    def _admit_forming(
        self,
        req: tuple,
        forming: Dict[Optional[str], _Forming],
        window: flow.BoundedChannel,
    ) -> None:
        seq, tenant, batch, deadline, submitted = req
        now = time.monotonic()
        hist.record("serving.queueWaitMs", (now - submitted) * 1000.0)
        if deadline is not None and now > deadline:
            self._quota_release(tenant)
            metrics.inc_counter("serving.deadlineMiss")
            metrics.inc_counter("serving.deadlineMiss.expired")
            self._count("expired")
            self._emit(ServeResult(seq, "expired", tenant=tenant))
            return
        sig = self._batch_sig(batch)
        group = forming.get(tenant)
        n = batch.num_rows
        if group is not None and (
            sig is None or group.sig != sig or group.rows + n > self.form_rows
        ):
            # incompatible or over-target: the older batch flushes FIRST,
            # preserving per-tenant FIFO
            self._flush_group(forming.pop(tenant), window)
            group = None
        if sig is None:  # non-coalescable: dispatch alone, right now
            solo = _Forming(tenant, None)
            solo.add(seq, batch, deadline, flush_at=0.0)
            self._flush_group(solo, window)
            return
        if group is None:
            group = forming[tenant] = _Forming(tenant, sig)
        group.add(seq, batch, deadline, self._form_flush_at(deadline))
        if group.rows >= self.form_rows:  # bucket full: go now
            self._flush_group(forming.pop(tenant), window)

    def _flush_due(
        self, forming: Dict[Optional[str], _Forming], window: flow.BoundedChannel
    ) -> None:
        now = time.monotonic()
        for tenant in [t for t, g in forming.items() if g.flush_at <= now]:
            self._flush_group(forming.pop(tenant), window)

    def _flush_group(self, group: _Forming, window: flow.BoundedChannel) -> None:
        """Dispatch one forming batch: concat members, one fused dispatch,
        one window entry carrying each member's row span so `_retire`
        hands every request ITS rows back."""
        now = time.monotonic()
        live: List[Tuple[int, Table, Optional[float]]] = []
        for seq, batch, deadline, admitted in group.reqs:
            self._quota_release(group.tenant)
            if deadline is not None and now > deadline:  # expired while forming
                metrics.inc_counter("serving.deadlineMiss")
                metrics.inc_counter("serving.deadlineMiss.expired")
                self._count("expired")
                self._emit(ServeResult(seq, "expired", tenant=group.tenant))
                continue
            hist.record("serving.formWaitMs", (now - admitted) * 1000.0)
            live.append((seq, batch, deadline))
        if not live:
            return
        merged = live[0][1] if len(live) == 1 else self._concat_batches([b for _, b, _ in live])
        parts: List[Tuple[int, Optional[float], int, int, Optional[str]]] = []
        offset = 0
        for seq, batch, deadline in live:
            parts.append((seq, deadline, offset, offset + batch.num_rows, group.tenant))
            offset += batch.num_rows
        try:
            model = self._model_for(group.tenant)
            out, pending, n = self._dispatch(merged, live[0][0], model=model)
        except Exception as e:  # whole forming batch fails per-request
            for seq, _, _ in live:
                self._count("errors")
                self._emit(ServeResult(seq, "error", error=e, tenant=group.tenant))
            return
        if len(live) > 1:
            metrics.inc_counter("serving.coalesced", len(live))
        entry = (tuple(parts), out, pending, n)
        if not window.offer(entry):
            # tpulint: disable=untimed-wait -- dispatch-worker-local window: offer() just returned False, so the window is non-empty and get() cannot block
            self._retire(window.get())
            window.offer(entry)

    @staticmethod
    def _slice_span(col, start: int, stop: int):
        if isinstance(col, SparseBatch):
            return SparseBatch(col.size, col.indices[start:stop], col.values[start:stop])
        return col[start:stop]

    @staticmethod
    def _to_host(col):
        if isinstance(col, SparseBatch):
            return SparseBatch(col.size, np.asarray(col.indices), np.asarray(col.values))
        return col if isinstance(col, np.ndarray) else np.asarray(col)

    def _retire(self, entry) -> None:
        """Retire one window entry: the single guard readback, then each
        member request gets its row span, deadline verdict, and result.

        Pad-undo and per-part span slicing happen on HOST: an eager
        device slice compiles one XLA program per distinct (shape, span)
        pair, and continuous forming produces an open-ended set of those
        — steady-state paging would keep compiling, breaking the
        zero-recompile contract tests/test_modelstore.py pins. Push results
        are terminal per-request responses, so the one materialization
        here replaces the consumer's own later pull; an unpadded solo
        batch still retires device-resident, untouched."""
        parts, out, pending, n = entry
        padded = out.num_rows
        try:
            table = self._finish(out, pending, padded)
        except Exception as e:  # deferred guard error: per-request, in order
            for seq, _deadline, _start, _stop, tenant in parts:
                self._count("errors")
                self._emit(ServeResult(seq, "error", error=e, tenant=tenant))
            return
        sliced = len(parts) > 1 or n != padded
        if sliced:
            table = Table(
                {name: self._to_host(table.column(name)) for name in table.column_names}
            )
        now = time.monotonic()
        for seq, deadline, start, stop, tenant in parts:
            if not sliced:
                sub = table
            else:
                sub = Table(
                    {
                        name: self._slice_span(table.column(name), start, stop)
                        for name in table.column_names
                    }
                )
            status = "ok"
            if deadline is not None:
                margin_ms = (deadline - now) * 1000.0
                if margin_ms < 0:
                    # cause-attributed miss: finished LATE after dispatch
                    # (the compute was paid — contrast deadlineMiss.expired)
                    metrics.inc_counter("serving.deadlineMiss")
                    metrics.inc_counter("serving.deadlineMiss.late")
                    hist.record("serving.lateByMs", -margin_ms)
                    self._count("late")
                    status = "late"
                else:
                    hist.record("serving.deadlineMarginMs", margin_ms)
            self._emit(ServeResult(seq, status, table=sub, tenant=tenant))

    def _emit(self, result: ServeResult) -> None:
        self._count("completed")
        try:
            self._out.put(result)
        except flow.ChannelClosed:  # consumer cancelled results(): drop
            pass


def serve_stream(
    model: PipelineModel,
    stream: Iterable[Table],
    in_flight: Optional[int] = None,
    buckets: Optional[Sequence[int]] = None,
) -> List[Table]:
    """One-shot convenience: serve the whole stream, collect the outputs."""
    return list(MicroBatchServer(model, in_flight=in_flight, buckets=buckets).serve(stream))
