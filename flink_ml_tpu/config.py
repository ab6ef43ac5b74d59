"""Runtime configuration knobs.

The analogue of the reference's Flink ConfigOptions — a single option there
too (`iteration.data-cache.path`, config/IterationOptions.java:30-37).
`iteration_checkpoint_dir` enables epoch-boundary checkpoint/resume of
iterative training (SGD); estimators pick it up process-wide, as Flink jobs
pick up cluster configuration.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

iteration_checkpoint_dir: Optional[str] = None
iteration_checkpoint_interval: int = 1

# --- dispatch pipeline (parallel/dispatch.py) ---------------------------------
# Epochs fused into one device program by the host-driven iteration loops
# (the reference batches per-epoch progress the same way with its epoch
# watermarks + chunked all-reduce). None = adaptive: ~maxIter/8 clamped to
# [1, 32], so short runs keep per-epoch visibility and long runs amortize
# the dispatch+readback round trip over many epochs.
iteration_chunk_size: Optional[int] = None
# Max dispatched-but-undrained chunks per loop. Depth > 1 lets host Python
# run ahead of the device instead of serializing on every chunk's
# convergence readback; tol semantics stay exact because speculative
# chunks are criteria-guarded no-ops once tol has fired.
iteration_dispatch_depth: int = 2


def iteration_chunk_for(max_iter: int, chunk_size: Optional[int] = None) -> int:
    """Resolve the epoch-chunk length K for a loop of `max_iter` epochs:
    explicit argument > process-wide `iteration_chunk_size` > adaptive."""
    k = chunk_size if chunk_size is not None else iteration_chunk_size
    if k is None:
        k = max(1, min(32, -(-max_iter // 8)))
    return max(1, min(int(k), max(1, int(max_iter))))


# --- whole-fit resident programs (parallel/dispatch.py) -----------------------
# "auto": eligible fits compile the ENTIRE epoch loop — per-epoch tol
# check, final model update, and the packed result — into ONE resident
# device program per (shape-bucket x packed-hyperparam layout), so a
# maxIter=200 fit is exactly one dispatch and one packed readback
# (host_sync_count == 1) regardless of the chunk knobs above. Ineligible
# fits (a checkpoint boundary lands mid-fit, the stream data source
# exceeds the device-cache budget, ragged stream batch shapes, a
# per-epoch listener) fall back to the chunked DrainQueue path, counted
# per reason under `dispatch.whole_fit_fallback` (docs/performance.md).
# "off": always the chunked/per-epoch reference path — whole-fit results
# are bit-identical to it by construction, pinned by
# tests/test_dispatch_pipeline.py.
whole_fit: str = "auto"


@contextmanager
def whole_fit_mode(mode: str):
    """Scoped override of `whole_fit` ("auto" | "off")."""
    global whole_fit
    if mode not in ("auto", "off"):
        raise ValueError(f"Unknown whole_fit mode {mode!r}")
    prev = whole_fit
    whole_fit = mode
    try:
        yield
    finally:
        whole_fit = prev


if os.environ.get("FLINK_ML_TPU_WHOLE_FIT") in ("auto", "off"):
    whole_fit = os.environ["FLINK_ML_TPU_WHOLE_FIT"]


# --- fleet training (fleet.py) ------------------------------------------------
# A FitFleet shards its member (fleet) axis over the mesh data axis —
# replicating the training data instead — once the per-member state total
# (N x carry bytes) crosses this threshold AND the fleet divides the data
# shards evenly (mesh.fleet_axis_shardable). Below it, member state is
# replicated like any other model state and the data stays data-sharded.
# None disables automatic fleet sharding (FitFleet(shard_fleet_axis=True)
# still forces it).
fleet_shard_state_bytes: Optional[int] = 256 << 20


@contextmanager
def fleet_shard_threshold(nbytes: Optional[int]):
    """Scoped override of `fleet_shard_state_bytes` (None = never auto)."""
    global fleet_shard_state_bytes
    prev = fleet_shard_state_bytes
    fleet_shard_state_bytes = nbytes
    try:
        yield
    finally:
        fleet_shard_state_bytes = prev


# --- collectives: chunking, sparse reduction, comm/compute overlap ------------
# (parallel/collectives.py + parallel/overlap.py)
# Bucket size for all_reduce_sum_chunked: a large gradient pytree is
# decomposed into size-targeted buckets and each bucket reduced on its own.
# The reference hand-rolls the same decomposition at 32KB per chunk over
# netty shuffles (AllReduceImpl.java:56-103, tuned for TCP framing); ICI
# moves MB-class buckets at line rate, so the default is 4MB — small enough
# that a multi-bucket reduce can pipeline, large enough to amortize
# per-collective launch cost. None/0 = one bucket (no chunking).
collective_chunk_bytes: Optional[int] = 4 << 20
# Density threshold for the SparCML-style index-value gradient reduction:
# the sparse path is used when its wire bytes (per-shard (index, value)
# pairs) are at most this fraction of the dense-equivalent psum payload
# (dim * itemsize); above it, the gradient densifies and rides the chunked
# dense reduce. Decided at trace time from static shapes.
collective_sparse_threshold: float = 0.5
# Route each bucket through the ring-pipelined ppermute reduction instead
# of reduce_scatter+all_gather. The ring rotates shard contributions and
# folds them in replica order (bit-identical to psum), letting bucket i+1's
# hops overlap bucket i's fold — the latency-bound small-bucket regime; the
# default reduce_scatter+all_gather path is the bandwidth-optimal one.
collective_ring: bool = False
# Comm/compute overlap in the SGD/Lloyd training loops: the loop carries
# the UNREDUCED per-shard gradient and defers its all-reduce to the top of
# the next epoch, so the reduction of batch b's gradient overlaps the
# forward of batch b+1 (carry-delayed apply; bit-identical by construction
# — see docs/performance.md §7 and tests/test_collective_chunks.py).
collective_overlap: bool = False


@contextmanager
def collective_overlap_mode(enabled: bool = True):
    """Scoped override of `collective_overlap`."""
    global collective_overlap
    prev = collective_overlap
    collective_overlap = bool(enabled)
    try:
        yield
    finally:
        collective_overlap = prev


# True 2D (data × model) sparse training (docs/performance.md "2D mesh"):
# "auto" routes a feature-sharded sparse fit on a mesh with a real model
# axis through the explicit-SPMD 2D programs (parallel/overlap.py
# sgd2d_*: coeff + optimizer carries live as model-axis slices, gradients
# reduce over the data axis only). "off" keeps the GSPMD 1D program —
# the replicated-residency reference the 2D parity tests compare against.
sparse_2d: str = "auto"


@contextmanager
def sparse_2d_mode(mode: str):
    """Scoped override of `sparse_2d` ("auto" | "off")."""
    global sparse_2d
    if mode not in ("auto", "off"):
        raise ValueError(f"sparse_2d must be 'auto' or 'off', got {mode!r}")
    prev = sparse_2d
    sparse_2d = mode
    try:
        yield
    finally:
        sparse_2d = prev


def resolve_chunk_bytes(chunk_bytes: Optional[int] = None) -> Optional[int]:
    """Effective collective bucket size: explicit argument > process-wide
    `collective_chunk_bytes`. None/<=0 means unchunked (one bucket)."""
    v = chunk_bytes if chunk_bytes is not None else collective_chunk_bytes
    if v is None or v <= 0:
        return None
    return int(v)


if os.environ.get("FLINK_ML_TPU_COLLECTIVE_OVERLAP") in ("1", "true", "on"):
    collective_overlap = True
if os.environ.get("FLINK_ML_TPU_SPARSE_2D") in ("auto", "off"):
    sparse_2d = os.environ["FLINK_ML_TPU_SPARSE_2D"]
if os.environ.get("FLINK_ML_TPU_COLLECTIVE_CHUNK_BYTES"):
    collective_chunk_bytes = int(os.environ["FLINK_ML_TPU_COLLECTIVE_CHUNK_BYTES"])


# --- pipeline transform fusion (pipeline.py) ----------------------------------
# "auto": PipelineModel.transform compiles maximal runs of fusable stages
# into single device programs when their input columns are device-resident
# (one dispatch per segment instead of one per stage). "off": always the
# eager per-stage path — the reference for the fused-vs-eager parity suite.
pipeline_fusion: str = "auto"

# Max transformed-but-undrained micro-batches the serving runner keeps in
# flight (serving.MicroBatchServer): batch i+1's H2D upload and compute
# overlap batch i's pending guard drain instead of serializing on it.
serving_in_flight: int = 2


@contextmanager
def pipeline_fusion_mode(mode: str):
    """Scoped override of `pipeline_fusion` ("auto" | "off")."""
    global pipeline_fusion
    if mode not in ("auto", "off"):
        raise ValueError(f"Unknown pipeline_fusion mode {mode!r}")
    prev = pipeline_fusion
    pipeline_fusion = mode
    try:
        yield
    finally:
        pipeline_fusion = prev


if os.environ.get("FLINK_ML_TPU_PIPELINE_FUSION") in ("auto", "off"):
    pipeline_fusion = os.environ["FLINK_ML_TPU_PIPELINE_FUSION"]


# --- input pipeline: device epoch cache, prefetch, bucketing ------------------
# (data/devicecache.py + parallel/prefetch.py)
# HBM budget for the device-resident epoch cache fronting replayed stream
# training (cache-once/replay-every-epoch, the ReplayOperator contract
# lifted from host numpy into device memory): epoch 0 uploads each batch
# once, epochs >= 1 read device-resident shards back with zero H2D bytes.
# None = unbounded (cache everything), 0 = disabled (the eager re-upload
# reference path); any budget computes bit-identical results — over-budget
# batches are LRU-evicted back to the native host cache and re-staged
# (accounted) on their next access.
device_cache_bytes: Optional[int] = None
# Max batches the input stager runs ahead of the consuming training loop:
# one worker thread reads + packs + uploads batch b+1 while the device
# computes batch b (parallel/prefetch.Prefetcher, data/devicecache.
# CachedEpochLoader). Depth > 2 rarely helps — the worker is serial and
# the device consumes one batch at a time.
input_prefetch_depth: int = 2
# Serving-style batch-shape bucketing on the stream-training staging paths
# (pad to the next power-of-two row count by repeating the last row, mask
# the pad with weight 0): free-running micro-batch sizes then hit a
# bounded set of compiled programs instead of recompiling per shape.
# Bit-exact by construction — a repeated row at weight 0 contributes +0.0
# to every reduction. "off" is the exact-shape reference path.
input_bucketing: bool = True


@contextmanager
def device_cache_budget(budget_bytes: Optional[int]):
    """Scoped override of `device_cache_bytes` (None = unbounded, 0 = off)."""
    global device_cache_bytes
    prev = device_cache_bytes
    device_cache_bytes = budget_bytes
    try:
        yield
    finally:
        device_cache_bytes = prev


@contextmanager
def input_bucketing_mode(enabled: bool = True):
    """Scoped override of `input_bucketing`."""
    global input_bucketing
    prev = input_bucketing
    input_bucketing = bool(enabled)
    try:
        yield
    finally:
        input_bucketing = prev


if os.environ.get("FLINK_ML_TPU_DEVICE_CACHE_BYTES"):
    device_cache_bytes = int(os.environ["FLINK_ML_TPU_DEVICE_CACHE_BYTES"])
if os.environ.get("FLINK_ML_TPU_INPUT_PREFETCH_DEPTH"):
    input_prefetch_depth = int(os.environ["FLINK_ML_TPU_INPUT_PREFETCH_DEPTH"])


# --- HBM budget admission (obs/memledger.py) ----------------------------------
# Device-memory admission budget over the ledger's live bytes: every
# accounted staging funnel pre-checks "would this upload push ledgered
# residency past the budget?" and raises a typed
# `memledger.HbmBudgetExceeded` (carrying the per-category breakdown)
# BEFORE the allocating dispatch — so OOM paths are exercised
# deterministically on the CPU tier-1 mesh, and a budgeted production run
# fails with attribution instead of an opaque RESOURCE_EXHAUSTED. None =
# off (no admission check). Admission only raises or passes — it never
# changes what a surviving fit computes, so a loose budget is
# bit-identical to no budget.
hbm_budget_bytes: Optional[int] = None


@contextmanager
def hbm_budget_mode(budget_bytes: Optional[int]):
    """Scoped override of `hbm_budget_bytes` (None = admission off)."""
    global hbm_budget_bytes
    prev = hbm_budget_bytes
    hbm_budget_bytes = None if budget_bytes is None else max(0, int(budget_bytes))
    try:
        yield
    finally:
        hbm_budget_bytes = prev


if os.environ.get("FLINK_ML_TPU_HBM_BUDGET_BYTES"):
    hbm_budget_bytes = max(0, int(os.environ["FLINK_ML_TPU_HBM_BUDGET_BYTES"]))


# --- flow control + transient-fault resilience (flow.py) ---------------------
# Retry budget for transiently-failing I/O sites (snapshot write/read,
# DataCache spill reads, serving batch execution): extra attempts after the
# first failure, 0 = fail fast (the pre-flow behavior). Only
# `flow.TRANSIENT_ERRORS` are retried — data errors and injected kills
# propagate immediately, and an exhausted budget re-raises the ORIGINAL
# error with `retry_attempts` attached (docs/flow_control.md).
transient_retries: int = 2
# Exponential-backoff schedule for those retries: attempt k sleeps
# min(retry_max_delay_s, retry_base_delay_s * 2**(k-1)) with full jitter.
retry_base_delay_s: float = 0.005
retry_max_delay_s: float = 0.25
# A stage execution exceeding this multiple of its trailing-mean latency
# is flagged by flow.StragglerWatchdog (`flow.straggler.*` counters).
straggler_factor: float = 4.0
# Watchdog ESCALATION (opt-in): after this many CONSECUTIVE flagged
# samples on one stage, StragglerWatchdog raises a typed
# `flow.PersistentStraggler` instead of only bumping counters — the
# signal a supervisor can act on (quarantine, re-dispatch) where a
# counter is only a breadcrumb. 0 = off (the counter-only default); a
# healthy sample resets the streak, so a one-off blip never escalates.
straggler_escalate: int = 0
# Overload policy of the online-estimator ingest channel
# (OnlineKMeans/OnlineLogisticRegression global-batch staging): "block" is
# lossless credit-based backpressure — every batch is folded, results are
# deterministic (the test/reference mode). "shed_oldest" bounds BOTH queue
# memory and model staleness under a producer that outruns the training
# step (consumed lag < channel capacity, tracked via flow.lag.* /
# flow.shed); "sample" bounds memory only (the queue degrades to a prefix
# sample of the stream). Shedding trades exactly-once folding for
# liveness, so it is opt-in.
online_overload_policy: str = "block"
# Admission-queue capacity of MicroBatchServer's push API: submit() raises
# a typed ServerOverloaded (carrying live queue depth) once this many
# requests are waiting — bounded memory and bounded client latency instead
# of a queue that grows until the host dies.
serving_admission: int = 16
# Default per-request deadline for submitted serving batches (None = no
# deadline): a request whose deadline passes before dispatch is shed
# (`serving.deadlineMiss`), one that finishes late is delivered marked late.
serving_deadline_ms: Optional[float] = None
# Continuous-batching forming budget (serving.MicroBatchServer with
# batching="continuous"): the longest a request may wait in the FORMING
# bucket before the partial batch dispatches anyway. A forming batch goes
# out when it fills its target bucket OR when its oldest request's
# deadline margin (deadline - now; submit time + budget when the request
# has no deadline) hits this budget — so latency at low offered QPS is
# bounded by the budget while throughput at high QPS gets full buckets.
serving_form_budget_ms: float = 5.0
# HBM byte budget for the multi-tenant device-resident model store
# (data/modelstore.py): registered models page host<->HBM under an LRU
# policy so far more models than fit in device memory serve from one
# mesh. Ledgered under the memledger `model` category — the store keeps
# `hbm.live.model` at or below this budget. None = unbounded (no paging
# pressure; everything stays resident after first touch).
model_store_bytes: Optional[int] = None


@contextmanager
def serving_form_budget(budget_ms: float):
    """Scoped override of `serving_form_budget_ms`."""
    global serving_form_budget_ms
    prev = serving_form_budget_ms
    serving_form_budget_ms = max(0.0, float(budget_ms))
    try:
        yield
    finally:
        serving_form_budget_ms = prev


@contextmanager
def model_store_budget(budget_bytes: Optional[int]):
    """Scoped override of `model_store_bytes` (None = unbounded)."""
    global model_store_bytes
    prev = model_store_bytes
    model_store_bytes = None if budget_bytes is None else max(0, int(budget_bytes))
    try:
        yield
    finally:
        model_store_bytes = prev


if os.environ.get("FLINK_ML_TPU_SERVING_FORM_BUDGET_MS"):
    serving_form_budget_ms = max(
        0.0, float(os.environ["FLINK_ML_TPU_SERVING_FORM_BUDGET_MS"])
    )
if os.environ.get("FLINK_ML_TPU_MODEL_STORE_BYTES"):
    model_store_bytes = max(0, int(os.environ["FLINK_ML_TPU_MODEL_STORE_BYTES"]))


@contextmanager
def straggler_escalation_mode(consecutive: int):
    """Scoped override of `straggler_escalate` (0 disables escalation)."""
    global straggler_escalate
    prev = straggler_escalate
    straggler_escalate = max(0, int(consecutive))
    try:
        yield
    finally:
        straggler_escalate = prev


@contextmanager
def transient_retry_mode(retries: int):
    """Scoped override of `transient_retries` (0 disables retries)."""
    global transient_retries
    prev = transient_retries
    transient_retries = max(0, int(retries))
    try:
        yield
    finally:
        transient_retries = prev


@contextmanager
def online_overload_mode(policy: str):
    """Scoped override of `online_overload_policy`."""
    global online_overload_policy
    if policy not in ("block", "shed_oldest", "sample", "reject"):
        raise ValueError(f"Unknown overload policy {policy!r}")
    prev = online_overload_policy
    online_overload_policy = policy
    try:
        yield
    finally:
        online_overload_policy = prev


if os.environ.get("FLINK_ML_TPU_TRANSIENT_RETRIES"):
    transient_retries = max(0, int(os.environ["FLINK_ML_TPU_TRANSIENT_RETRIES"]))
if os.environ.get("FLINK_ML_TPU_ONLINE_OVERLOAD_POLICY") in (
    "block",
    "shed_oldest",
    "sample",
):
    online_overload_policy = os.environ["FLINK_ML_TPU_ONLINE_OVERLOAD_POLICY"]


# --- multi-host snapshot coordination (ckpt/coordinator.py) -------------------
# Simulated host count for the sharded JobSnapshot path: with N >= 1, each
# (simulated) host writes ONLY its own per-leaf slices as
# `snap-<key>.c<cut>.host<i>.npz` and a coordinator commits an atomic
# manifest recording per-shard content digests, the leaf->shard layout and
# the host count — the DCN-ready write path ROADMAP item 1 needs, chaos-
# tested on the virtual-device substrate (hosts are contiguous mesh device
# groups, parallel/mesh.host_groups). None = the single-file snapshot path.
# Restore reads EITHER format regardless of this knob (a sharded manifest
# wins when both exist), and re-stitches N-host shards onto an M-host mesh
# through `stage_section` — elastic in both directions.
snapshot_hosts: Optional[int] = None
# Committed snapshot cuts retained per job key (manifest + shard files):
# commit-time GC keeps the last N, so rollback-to-previous-cut is always
# possible (the restore fallback when the newest cut is torn or bit-rotten)
# and disk use stays bounded. Must be >= 1; >= 2 to actually have a
# fallback target.
snapshot_retained: int = 2
# Straggler deadline for one host's shard write (seconds, wall time
# including retry backoff): a host that cannot land its shard within the
# deadline ABORTS THE CUT — the cut's partial files are deleted, the
# previous committed snapshot stays restorable, and training continues to
# the next boundary (`checkpoint.abort`). None = no deadline (retries
# bound the wait via config.transient_retries alone).
snapshot_host_deadline_s: Optional[float] = None
# Include the stream-training cache CONTENTS (the packed [X|y|w] segments
# of SGD.optimize_stream) as a per-host-sharded `cache` section in sharded
# snapshots, written ONCE per job key (immutable for the fit, reused by
# reference across cuts): a resumed stream fit rebuilds its segments from
# the snapshot and never re-consumes the input stream.
snapshot_cache_contents: bool = True


@contextmanager
def snapshot_hosts_mode(hosts: Optional[int]):
    """Scoped override of `snapshot_hosts` (None = single-file path)."""
    global snapshot_hosts
    if hosts is not None and int(hosts) < 1:
        raise ValueError(f"snapshot_hosts must be >= 1, got {hosts!r}")
    prev = snapshot_hosts
    snapshot_hosts = None if hosts is None else int(hosts)
    try:
        yield
    finally:
        snapshot_hosts = prev


@contextmanager
def snapshot_retention_mode(retained: int):
    """Scoped override of `snapshot_retained` (>= 1)."""
    global snapshot_retained
    prev = snapshot_retained
    snapshot_retained = max(1, int(retained))
    try:
        yield
    finally:
        snapshot_retained = prev


if os.environ.get("FLINK_ML_TPU_SNAPSHOT_HOSTS"):
    snapshot_hosts = max(1, int(os.environ["FLINK_ML_TPU_SNAPSHOT_HOSTS"]))
if os.environ.get("FLINK_ML_TPU_SNAPSHOT_RETAINED"):
    snapshot_retained = max(1, int(os.environ["FLINK_ML_TPU_SNAPSHOT_RETAINED"]))
if os.environ.get("FLINK_ML_TPU_SNAPSHOT_HOST_DEADLINE_S"):
    snapshot_host_deadline_s = float(
        os.environ["FLINK_ML_TPU_SNAPSHOT_HOST_DEADLINE_S"]
    )


# --- elastic training supervisor (parallel/supervisor.py) ---------------------
# Hang-watchdog deadline multiplier: a supervised fit that makes no
# dispatch/drain/commit progress for more than `hang_factor` times the
# EMA of its chunk wall (flow.StragglerWatchdog's trailing mean, fed by
# every `dispatch.timed_dispatch` / DrainQueue drain) is declared a
# `CollectiveHang` — the survivors-blocked-in-a-collective failure mode
# a counter can never surface.
hang_factor: float = 8.0
# Floor under the hang deadline (seconds): protects against a tiny EMA
# (fast warm chunks) declaring a hang on ordinary scheduler jitter.
hang_min_deadline_s: float = 1.0
# A (simulated) host whose heartbeat is older than this is declared a
# `HostFailure`. Heartbeats ride the supervisor's side channel (the DCN
# heartbeat analogue), NOT the training loop, so a host that is alive
# but stuck in a collective keeps beating — that case is the hang
# watchdog's, which is why the two detectors are separate.
host_heartbeat_timeout_s: float = 1.0
# Supervisor monitor poll cadence (seconds): bounds detection latency
# from below; heartbeat refresh and deadline checks run once per poll.
supervisor_poll_interval_s: float = 0.02
# Automatic recoveries (quarantine + mesh re-form + elastic restore +
# resume) the supervisor may spend on one fit before giving up and
# raising `RecoveryBudgetExhausted` carrying the typed failures.
recovery_budget: int = 2


@contextmanager
def recovery_budget_mode(budget: int):
    """Scoped override of `recovery_budget` (0 = detect but never resume)."""
    global recovery_budget
    prev = recovery_budget
    recovery_budget = max(0, int(budget))
    try:
        yield
    finally:
        recovery_budget = prev


if os.environ.get("FLINK_ML_TPU_RECOVERY_BUDGET"):
    recovery_budget = max(0, int(os.environ["FLINK_ML_TPU_RECOVERY_BUDGET"]))
if os.environ.get("FLINK_ML_TPU_HOST_HEARTBEAT_TIMEOUT_S"):
    host_heartbeat_timeout_s = float(
        os.environ["FLINK_ML_TPU_HOST_HEARTBEAT_TIMEOUT_S"]
    )
if os.environ.get("FLINK_ML_TPU_HANG_FACTOR"):
    hang_factor = float(os.environ["FLINK_ML_TPU_HANG_FACTOR"])


# --- model lifecycle: hot-swap, promotion gate, rollback (lifecycle.py) -------
# Promoted model versions retained in the lifecycle ring (host copies):
# rollback targets live here, so a bad promotion can be rolled back to the
# last-good version bit-exactly without restarting the server. Must be
# >= 2 (current + at least one rollback target).
model_versions_retained: int = 4
# Relative tolerance of the promotion gate's optional canary-batch parity
# check: the candidate's canary outputs must stay within this of the
# OUTGOING version's outputs, or the promotion is refused
# (`lifecycle.promoteRejected`). Generous by default — a healthy online
# step moves predictions a little; a diverged trainer moves them a lot.
lifecycle_canary_rtol: float = 0.5
# Sliding health window (per-serve-batch outcomes) feeding the automatic
# rollback trigger, and the guard-error rate over that window that fires
# it: at >= the trigger rate over a FULL window, traffic rolls back to the
# last-good version and the trainer's output is quarantined.
lifecycle_health_window: int = 16
lifecycle_error_rate_trigger: float = 0.5


@contextmanager
def model_retention_mode(retained: int):
    """Scoped override of `model_versions_retained`."""
    global model_versions_retained
    prev = model_versions_retained
    model_versions_retained = max(2, int(retained))
    try:
        yield
    finally:
        model_versions_retained = prev


if os.environ.get("FLINK_ML_TPU_MODEL_VERSIONS_RETAINED"):
    model_versions_retained = max(
        2, int(os.environ["FLINK_ML_TPU_MODEL_VERSIONS_RETAINED"])
    )
if os.environ.get("FLINK_ML_TPU_LIFECYCLE_CANARY_RTOL"):
    lifecycle_canary_rtol = float(os.environ["FLINK_ML_TPU_LIFECYCLE_CANARY_RTOL"])


# --- persistent XLA compilation cache ----------------------------------------
# Compiled executables survive process restarts, so the first fit of a new
# process reuses the previous process's XLA programs. WHERE the cache
# lives is decided here and nowhere else: the directory is part of what a
# later process must find again, so it is either the one the environment
# names (JAX_COMPILATION_CACHE_DIR, read by jax itself at import — the
# program then sets no directory in code) or a fixed path in the checkout.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
compilation_cache_dir: Optional[str] = None


def enable_compilation_cache(path: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in use: `JAX_COMPILATION_CACHE_DIR` when the environment
    sets it (it wins over `path`), else `path`, else `.jax_cache` at the
    root of the checkout this module was imported from — never the
    working directory, so every process of one checkout shares a cache."""
    global compilation_cache_dir
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        path = env_dir
    else:
        path = path or CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # every kernel here is worth persisting — the hot loops are small
    # programs that compile in well under the default 1s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache_dir = path
    return path


# --- AOT program bank (compilebank.py) ----------------------------------------
# The persistent XLA cache above only memoizes the *backend compile* after
# a trace has happened; the program bank goes further: serialized
# executables keyed by (kernel id x abstract shapes/dtypes x static args x
# mesh topology x jax version) are warm-loaded at process start, so a
# bank hit bypasses trace AND compile entirely (docs/performance.md §12).
# None = bank off — every kernel behaves exactly as before.
program_bank_dir: Optional[str] = None
# keyed_jit factory caches are LRU-bounded at this many entries; an
# eviction ticks jit.kernelCacheEvict and the re-touched key re-traces
# with identical results (pinned by tests/test_compilebank.py).
kernel_cache_size: int = 256


@contextmanager
def program_bank_mode(path: Optional[str]):
    """Scoped override of `program_bank_dir` (None = bank off). The
    active ProgramBank singleton is reset on entry and exit so the scope
    sees a bank freshly warm-loaded from `path`."""
    global program_bank_dir
    prev = program_bank_dir
    program_bank_dir = path
    from . import compilebank

    compilebank.reset_active_bank()
    try:
        yield
    finally:
        program_bank_dir = prev
        compilebank.reset_active_bank()


@contextmanager
def kernel_cache_limit(size: int):
    """Scoped override of `kernel_cache_size` (>= 1)."""
    global kernel_cache_size
    prev = kernel_cache_size
    kernel_cache_size = max(1, int(size))
    try:
        yield
    finally:
        kernel_cache_size = prev


if os.environ.get("FLINK_ML_TPU_PROGRAM_BANK_DIR"):
    program_bank_dir = os.environ["FLINK_ML_TPU_PROGRAM_BANK_DIR"]
if os.environ.get("FLINK_ML_TPU_KERNEL_CACHE_SIZE"):
    kernel_cache_size = max(1, int(os.environ["FLINK_ML_TPU_KERNEL_CACHE_SIZE"]))

# Spillable data-cache defaults for training on StreamTable inputs (the
# analogue of `iteration.data-cache.path` + managed-memory weights in the
# reference). Batches beyond the in-memory budget spill to disk segments.
datacache_memory_budget_bytes: int = 64 << 20
datacache_spill_dir: Optional[str] = None


def set_iteration_checkpoint_dir(path: Optional[str], interval: int = 1) -> None:
    global iteration_checkpoint_dir, iteration_checkpoint_interval
    iteration_checkpoint_dir = path
    iteration_checkpoint_interval = interval


@contextmanager
def iteration_checkpointing(path: str, interval: int = 1):
    """Scoped checkpoint/resume for iterative training."""
    global iteration_checkpoint_dir, iteration_checkpoint_interval
    prev = (iteration_checkpoint_dir, iteration_checkpoint_interval)
    iteration_checkpoint_dir, iteration_checkpoint_interval = path, interval
    try:
        yield
    finally:
        iteration_checkpoint_dir, iteration_checkpoint_interval = prev
