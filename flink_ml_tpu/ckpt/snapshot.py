"""JobSnapshot — the full-job, preemption-safe checkpoint format.

The reference's hardest subsystem is checkpoint/resume: epoch watermarks,
exactly-once feedback-record snapshots, and a JobManager-side aligner
(iteration/checkpoint/Checkpoints.java:43-143). Under synchronous SPMD the
equivalent is radically simpler — an epoch boundary IS a consistent cut —
but the carry-only checkpoints of `parallel/iteration.py` capture just one
slice of a job. A JobSnapshot captures the whole of it, per *section*:

- `model`   — the training carry (coefficients/centroids, gradient
              accumulators, weight sums, epoch counter — the optimizer
              state lives here for SGD/FTRL);
- `rng`     — host PRNG state for fits that hold a live generator
              (KMeans stream init);
- further sections are open: the format stores named pytrees.

Mesh-independent by construction: device leaves are gathered to FULL host
arrays in ONE packed transfer at save (`sync_kind="checkpoint"`), and the
manifest records a *sharding-spec tag* per leaf (`replicated` / `data` /
`model` / `host`). Restoring onto a different mesh re-shards each leaf
through `parallel/mesh.py`'s spec constructors (`stage_section`) — the
elastic shrink/grow path the reference's HeadOperator only gestures at.

On-disk format (version 1): ONE `.npz` file per job key,
`snap-<jobkey>.npz`, holding a JSON `manifest` entry (version, job key,
epoch, criteria, per-section leaf inventory with dtype/shape/spec, free
meta) plus one array entry per leaf. Written atomically: temp file in the
same directory, then `os.replace` — a reader never observes a torn
snapshot, and a crash mid-write leaves the previous snapshot intact
(pinned by tests/test_job_snapshot.py via the `snapshot.write` fault
site). Meta carries the data-plane cursors: input-iterator/stream offsets
(`numBatches`/`numSegments`, `streamOffset`), the device-epoch-cache key
cursor, the global batch size — `load_job_snapshot(expect_meta=...)`
refuses a snapshot whose cursors disagree with the job being resumed.

Legacy migration (one-way): when no snapshot exists, the loader falls
back to the carry-only `ckpt-*.npz` files `save_iteration_checkpoint`
wrote, so pre-existing `checkpoint_dir` users resume instead of
restarting; the first save after resume writes the new format.

Obs: `checkpoint.save` / `checkpoint.restore` spans, `checkpoint.bytes` /
`checkpoint.count` (+ `checkpoint.restore.count`) counters — the same
pattern as the `h2d.*` upload accounting.
"""

from __future__ import annotations

import json
import os
import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Union

import numpy as np

from .. import flow
from ..utils import metrics
from . import faults

__all__ = [
    "SNAPSHOT_VERSION",
    "JobSnapshot",
    "snapshot_file",
    "save_job_snapshot",
    "load_job_snapshot",
    "stage_section",
]

SNAPSHOT_VERSION = 1

# sharding-spec tags a leaf may carry in the manifest; resolution against
# a concrete mesh happens in `stage_section`
_SPEC_TAGS = ("replicated", "data", "model", "host")

_UNKEYED_WARNING = (
    "un-keyed job-snapshot restore: without a checkpoint_job_key, a "
    "structurally compatible snapshot from a DIFFERENT job sharing this "
    "directory would positionally cross-restore into this one. Pass "
    "checkpoint_job_key (parallel.iteration.checkpoint_job_key) to "
    "namespace the snapshot per job identity."
)


@dataclass
class JobSnapshot:
    """A restored (or about-to-be-inspected) snapshot. `sections` holds
    host pytrees (unflattened against the loader's templates; untemplated
    sections stay flat leaf lists); `specs` the per-leaf sharding tags in
    flattened order; `meta` the free-form JSON side channel."""

    job_key: Optional[str]
    epoch: int
    criteria: float
    sections: Dict[str, Any]
    specs: Dict[str, Sequence[str]] = field(default_factory=dict)
    meta: Dict[str, Any] = field(default_factory=dict)
    version: int = SNAPSHOT_VERSION
    path: Optional[str] = None


def snapshot_file(path: str, job_key: Optional[str]) -> str:
    if job_key is None:
        return os.path.join(path, "snap.npz")
    safe = re.sub(r"[^A-Za-z0-9._-]", "_", job_key)
    return os.path.join(path, f"snap-{safe}.npz")


def _tree_flatten(tree):
    import jax

    return jax.tree_util.tree_flatten(tree)


def _normalize_specs(
    specs: Union[None, str, Sequence[str]], num_leaves: int, section: str
) -> Sequence[str]:
    if specs is None:
        specs = "replicated"
    if isinstance(specs, str):
        specs = (specs,) * num_leaves
    specs = tuple(specs)
    if len(specs) != num_leaves:
        raise ValueError(
            f"section {section!r}: {len(specs)} spec tags for {num_leaves} leaves"
        )
    for tag in specs:
        if tag not in _SPEC_TAGS:
            raise ValueError(f"unknown sharding-spec tag {tag!r} (one of {_SPEC_TAGS})")
    return specs


def _gather_sections(
    sections: Dict[str, Any],
    specs: Dict[str, Union[str, Sequence[str]]],
):
    """Flatten every section to host arrays — device leaves across ALL
    sections gathered in ONE packed D2H transfer (a per-leaf pull pays
    one blocking readback per leaf) — and build the manifest inventory
    (key/spec/dtype/shape per leaf, plus a crc32 content digest of each
    leaf's bytes, verified on restore)."""
    import zlib

    import jax

    from ..utils.packing import packed_device_get

    arrays: Dict[str, np.ndarray] = {}
    manifest_sections: Dict[str, Any] = {}
    gather: list = []  # device leaves, gathered in one packed transfer
    gather_slots: list = []  # (section array key) aligned with `gather`
    for name, tree in sections.items():
        leaves, _ = _tree_flatten(tree)
        tags = _normalize_specs(specs.get(name), len(leaves), name)
        entries = []
        for i, leaf in enumerate(leaves):
            key = f"s_{name}_{i}"
            if isinstance(leaf, jax.Array):
                gather.append(leaf)
                gather_slots.append(key)
            else:
                arrays[key] = np.asarray(leaf)
            entries.append({"key": key, "spec": tags[i]})
        manifest_sections[name] = {"leaves": entries}
    if gather:
        host = packed_device_get(*gather, sync_kind="checkpoint")
        for key, arr in zip(gather_slots, host):
            arrays[key] = np.asarray(arr)
    for name, section in manifest_sections.items():
        for entry in section["leaves"]:
            arr = arrays[entry["key"]]
            entry["dtype"] = str(arr.dtype)
            entry["shape"] = list(arr.shape)
            entry["crc32"] = (
                zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF
            )
    return arrays, manifest_sections


def save_job_snapshot(
    path: str,
    job_key: Optional[str],
    sections: Dict[str, Any],
    *,
    epoch: int,
    criteria: float = 0.0,
    specs: Optional[Dict[str, Union[str, Sequence[str]]]] = None,
    meta: Optional[Dict[str, Any]] = None,
    hosts: Optional[int] = None,
    stable_sections: Optional[Dict[str, Any]] = None,
) -> Optional[str]:
    """Write a versioned snapshot atomically; returns the target path
    (the npz, or the committed manifest on the sharded path), or None
    when a sharded cut was ABORTED by a straggler host (the previous
    committed snapshot stays restorable; training may continue).

    Single-host (the default): ONE npz, temp-file-then-`os.replace` —
    the commit point is the rename, so a kill at any earlier instant
    (the `snapshot.write` fault site sits right before the rename)
    leaves the previous snapshot intact and restorable. Per-leaf crc32
    digests ride the manifest and are verified on restore.

    Multi-host (`hosts` argument > `config.snapshot_hosts`): the
    two-phase sharded protocol of `ckpt/coordinator.py` — each simulated
    host writes only its own per-leaf slices, the coordinator commits an
    atomic digest-carrying manifest, retention GC runs on commit.
    `stable_sections` maps section names to zero-arg providers of
    immutable host-leaf tuples (the stream-cache contents), written once
    per job key and reused by reference across cuts; ignored on the
    single-file path."""
    from .. import config
    from ..obs import tracing
    from . import coordinator

    specs = specs or {}
    n_hosts = hosts if hosts is not None else config.snapshot_hosts
    with tracing.span(
        "checkpoint.save", jobKey=job_key or "", epoch=int(epoch)
    ) as sp:
        arrays, manifest_sections = _gather_sections(sections, specs)
        nbytes = sum(a.nbytes for a in arrays.values())

        if n_hosts is not None:
            sp.set_attr("hosts", int(n_hosts))
            stable_specs = {
                name: tag
                for name, tag in specs.items()
                if isinstance(tag, str) and name in (stable_sections or {})
            }
            try:
                target = coordinator.save_sharded(
                    path,
                    job_key,
                    arrays,
                    manifest_sections,
                    epoch=epoch,
                    criteria=criteria,
                    meta=meta,
                    hosts=int(n_hosts),
                    stable_sections=stable_sections,
                    stable_specs=stable_specs,
                    snapshot_version=SNAPSHOT_VERSION,
                )
            except coordinator.SnapshotAborted as e:
                # abort-this-cut: the job keeps training; the previous
                # committed cut stays restorable and the next boundary
                # tries again
                warnings.warn(f"snapshot cut aborted (epoch {epoch}): {e}")
                sp.set_attr("aborted", True)
                return None
            metrics.inc_counter("checkpoint.count")
            metrics.inc_counter("checkpoint.bytes", nbytes)
            sp.set_attr("bytes", nbytes)
            return target

        manifest = {
            "version": SNAPSHOT_VERSION,
            "jobKey": job_key,
            "epoch": int(epoch),
            "criteria": float(criteria),
            "sections": manifest_sections,
            "meta": meta or {},
        }
        os.makedirs(path, exist_ok=True)
        target = snapshot_file(path, job_key)

        # the supervised mid-commit boundary (parallel/supervisor.py):
        # a host that dies/hangs here has not written anything yet — the
        # abort path has nothing to sweep on the single-file path
        from ..parallel import supervisor as _supervisor

        _supervisor.pulse_boundary(_supervisor.PHASE_COMMIT)
        # transient write faults (flaky filesystem, faults.flaky plans)
        # re-run the WHOLE temp-write-then-rename sequence — safe because
        # nothing before the os.replace is observable to a reader; a fatal
        # InjectedFault is not transient and still kills the job mid-write
        coordinator.atomic_commit(
            target,
            lambda tmp: np.savez(
                tmp, manifest=np.asarray(json.dumps(manifest)), **arrays
            ),
            site="snapshot.write",
        )

        metrics.inc_counter("checkpoint.count")
        metrics.inc_counter("checkpoint.bytes", nbytes)
        sp.set_attr("bytes", nbytes)
    return target


def _verify_leaf_digest(file: str, section: str, entry, arr) -> None:
    """Check a stored leaf's bytes against its manifest crc32 (absent in
    pre-digest snapshots: nothing to verify). A mismatch is bit rot on
    the ONLY copy — it fails loudly naming the leaf, is NOT a
    `flow.TransientError` (re-reading the same corrupt bytes cannot
    help, so the surrounding retry wrapper must not spin on it), and is
    deliberately not a refuse-and-return-None: silently training from
    scratch over a corrupt checkpoint hides the corruption."""
    if "crc32" not in entry:
        return
    import zlib

    from .coordinator import SnapshotIntegrityError

    got = zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF
    if got != entry["crc32"]:
        metrics.inc_counter("checkpoint.digest.mismatch")
        raise SnapshotIntegrityError(
            f"snapshot {file}: leaf {entry['key']!r} (section {section!r}) "
            f"is corrupt — stored crc32 {entry['crc32']}, actual {got}. "
            "The snapshot cannot be trusted; restore refused."
        )


def _leaf_mismatch(template_leaves, entries) -> Optional[str]:
    """Why the stored leaves cannot positionally restore into the
    template (None when they can) — the foreign-job structural guard."""
    if len(template_leaves) != len(entries):
        return f"{len(entries)} stored leaves vs {len(template_leaves)} expected"
    for i, (leaf, entry) in enumerate(zip(template_leaves, entries)):
        if hasattr(leaf, "shape") and tuple(entry["shape"]) != tuple(np.shape(leaf)):
            return f"leaf {i}: stored shape {entry['shape']} vs {np.shape(leaf)}"
    return None


def load_job_snapshot(
    path: str,
    job_key: Optional[str],
    templates: Optional[Dict[str, Any]] = None,
    *,
    expect_meta: Optional[Dict[str, Any]] = None,
) -> Optional[JobSnapshot]:
    """Restore a JobSnapshot, or None when absent / structurally foreign /
    from an unknown future format version / cursor-incompatible
    (`expect_meta` entries must match the stored meta when both are set).

    `templates` maps section names to pytrees of the expected structure:
    templated sections come back unflattened with leaves cast to the
    template's dtypes (host numpy — `stage_section` re-shards onto a
    mesh); untemplated sections come back as flat leaf lists.

    Falls back to the legacy carry-only `ckpt-*.npz` format (one-way
    migration) when no snapshot file exists and a `model` template is
    given. Un-keyed restores warn: see `_UNKEYED_WARNING`.

    When the directory holds committed SHARDED cuts for this key
    (ckpt/coordinator.py), they are authoritative: restore goes through
    the coordinator — per-shard digest validation, refusal of
    partial/torn commits, fallback to the last committed cut — and does
    NOT fall through to a stale single-file/legacy snapshot."""
    import jax

    from ..obs import tracing
    from . import coordinator

    if coordinator.has_sharded(path, job_key):
        with tracing.span(
            "checkpoint.restore", jobKey=job_key or "", sharded=True
        ) as sp:
            snap = coordinator.load_sharded(
                path, job_key, templates, expect_meta=expect_meta
            )
            if snap is None:
                return None
            if job_key is None:
                warnings.warn(_UNKEYED_WARNING)
            metrics.inc_counter("checkpoint.restore.count")
            sp.set_attr("epoch", int(snap.epoch))
            return snap

    file = snapshot_file(path, job_key)
    if not os.path.exists(file):
        return _load_legacy(path, job_key, templates)
    with tracing.span("checkpoint.restore", jobKey=job_key or "") as sp:

        def read():
            """The retried unit: open + parse the npz. Returns None when
            the snapshot is refused (foreign/future/cursor-mismatched) —
            a refusal is a decision, not an I/O failure, so it is never
            retried; a transient read fault (faults.flaky plans, flaky
            filesystems) re-runs this whole closure."""
            faults.tick("snapshot.read")
            with np.load(file) as f:
                manifest = json.loads(str(f["manifest"]))
                version = int(manifest.get("version", -1))
                if version > SNAPSHOT_VERSION or version < 1:
                    warnings.warn(
                        f"ignoring job snapshot {file}: format version {version} "
                        f"(this build reads <= {SNAPSHOT_VERSION})"
                    )
                    return None
                if expect_meta:
                    stored = manifest.get("meta", {})
                    for k, v in expect_meta.items():
                        if k in stored and stored[k] != v:
                            warnings.warn(
                                f"ignoring job snapshot {file}: meta {k!r} is "
                                f"{stored[k]!r}, resuming job expects {v!r} (the "
                                "snapshot belongs to a different data layout)"
                            )
                            return None
                sections: Dict[str, Any] = {}
                specs: Dict[str, Sequence[str]] = {}
                for name, section in manifest["sections"].items():
                    entries = section["leaves"]
                    specs[name] = tuple(e.get("spec", "replicated") for e in entries)
                    for e in entries:
                        _verify_leaf_digest(file, name, e, f[e["key"]])
                    template = (templates or {}).get(name)
                    if template is None:
                        sections[name] = [np.asarray(f[e["key"]]) for e in entries]
                        continue
                    leaves, treedef = _tree_flatten(template)
                    why = _leaf_mismatch(leaves, entries)
                    if why is not None:
                        warnings.warn(
                            f"ignoring job snapshot {file}: section {name!r} is "
                            f"structurally incompatible ({why}) — it belongs to a "
                            "different job"
                        )
                        return None
                    # restore on host: np keeps float64 leaves exact; staging
                    # onto the mesh is the caller's move (stage_section)
                    restored = [
                        np.asarray(f[e["key"]], dtype=leaf.dtype)
                        if hasattr(leaf, "dtype")
                        else np.asarray(f[e["key"]])
                        for leaf, e in zip(leaves, entries)
                    ]
                    sections[name] = jax.tree_util.tree_unflatten(treedef, restored)
            return manifest, sections, specs

        parsed = flow.with_retries(read, site="snapshot.read")
        if parsed is None:
            return None
        manifest, sections, specs = parsed
        if job_key is None:
            warnings.warn(_UNKEYED_WARNING)
        metrics.inc_counter("checkpoint.restore.count")
        sp.set_attr("epoch", int(manifest["epoch"]))
        return JobSnapshot(
            job_key=job_key,
            epoch=int(manifest["epoch"]),
            criteria=float(manifest["criteria"]),
            sections=sections,
            specs=specs,
            meta=manifest.get("meta", {}),
            version=int(manifest.get("version", -1)),
            path=file,
        )


def _load_legacy(
    path: str, job_key: Optional[str], templates: Optional[Dict[str, Any]]
) -> Optional[JobSnapshot]:
    """One-way migration: read a carry-only checkpoint written by
    `parallel.iteration.save_iteration_checkpoint` into a JobSnapshot
    with a single `model` section. Corrupt files raise (a directory that
    claims a checkpoint but cannot produce one is an operator error, not
    a fresh start)."""
    import jax

    template = (templates or {}).get("model")
    if template is None:
        return None
    from ..parallel.iteration import _checkpoint_file

    file = _checkpoint_file(path, job_key)
    if not os.path.exists(file):
        return None
    warnings.warn(
        f"legacy checkpoint {file}: the pre-JobSnapshot carry-only format "
        "records no integrity digests, so this restore CANNOT be verified "
        "against bit rot; the first save after resume migrates to the "
        "digest-carrying snapshot format"
    )
    with np.load(file) as f:
        leaves, treedef = _tree_flatten(template)
        if any(f"leaf_{i}" not in f for i in range(len(leaves))) or (
            f"leaf_{len(leaves)}" in f
        ):
            return None
        for i, leaf in enumerate(leaves):
            if hasattr(leaf, "shape") and tuple(f[f"leaf_{i}"].shape) != tuple(
                np.shape(leaf)
            ):
                return None
        restored = [
            np.asarray(f[f"leaf_{i}"], dtype=leaf.dtype)
            if hasattr(leaf, "dtype")
            else f[f"leaf_{i}"]
            for i, leaf in enumerate(leaves)
        ]
        carry = jax.tree_util.tree_unflatten(treedef, restored)
        epoch, criteria = int(f["epoch"]), float(f["criteria"])
    if job_key is None:
        warnings.warn(_UNKEYED_WARNING)
    metrics.inc_counter("checkpoint.restore.count")
    return JobSnapshot(
        job_key=job_key,
        epoch=epoch,
        criteria=criteria,
        sections={"model": carry},
        specs={"model": ("replicated",) * len(restored)},
        meta={"migratedFrom": os.path.basename(file)},
        version=0,  # pre-JobSnapshot
        path=file,
    )


def _sharding_for(tag: str, mesh, ndim: int):
    from ..parallel import mesh as mesh_lib

    if tag == "data":
        return mesh_lib.data_sharding(mesh, max(1, ndim))
    if tag == "model":
        return mesh_lib.model_sharding(mesh, max(1, ndim))
    return mesh_lib.replicated_sharding(mesh)


def stage_section(
    snap: JobSnapshot,
    name: str,
    mesh=None,
    specs: Union[None, str, Sequence[str]] = None,
    category: Optional[str] = "optimizer",
):
    """Stage a restored section's leaves onto `mesh` (default mesh when
    None) according to their sharding-spec tags — the elastic re-shard
    step: the snapshot stores full host arrays, so restoring onto a mesh
    of a DIFFERENT device count is the same accounted upload as restoring
    onto the original one, just against the new mesh's shardings. Leaves
    tagged `host` stay numpy. `specs` overrides the stored tags (a
    resuming job that knows its layout wins over the manifest).
    `category` ledgers the restored residency (obs/memledger.py) — the
    default `optimizer` fits the dominant caller (the training carry a
    resumed fit re-stages); pass None for transient sections."""
    import jax

    from ..parallel import mesh as mesh_lib
    from ..parallel import prefetch as h2d

    tree = snap.sections[name]
    leaves, treedef = _tree_flatten(tree)
    tags = (
        _normalize_specs(specs, len(leaves), name)
        if specs is not None
        else _normalize_specs(snap.specs.get(name), len(leaves), name)
    )
    mesh = mesh or mesh_lib.default_mesh()
    staged = [
        leaf
        if tag == "host"
        else h2d.stage_to_device(
            np.asarray(leaf),
            _sharding_for(tag, mesh, np.ndim(leaf)),
            category=category,
        )
        for leaf, tag in zip(leaves, tags)
    ]
    return jax.tree_util.tree_unflatten(treedef, staged)
