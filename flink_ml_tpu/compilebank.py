"""AOT program bank: precompiled executables with warm-load cold start.

The persistent XLA compilation cache (config.enable_compilation_cache,
PR 2) memoizes *backend compiles* after the fact — a fresh process still
pays every trace and still round-trips jaxpr->HLO before the cache can
hit. This module closes the rest of the cold-start wall: the known
program space (whole-fit kernels, fused serving segments, the declared
bucket schedules) is enumerated as **signatures** —

    kernel id x abstract shapes/dtypes (incl. weak_type) x static-arg
    tokens x sharding/mesh topology x jax/jaxlib version

— compiled ahead of time via ``jit(...).lower(...).compile()``,
serialized (``jax.experimental.serialize_executable``) to a versioned
on-disk bank, and warm-loaded at process start. A bank hit calls the
loaded executable directly: **no trace, no XLA compile** — the
``jit.traces`` and ``jit.compiles`` counters both stay flat, which is
what makes the serving SLA's zero-trace assertion
(scripts/coldstart_smoke.py) and the flat ``jit.compiles`` of steady-state
paging (tests/test_modelstore.py) honest rather than merely lucky.

Integration is at the ``utils/lazyjit.py`` funnel (every accounted
kernel consults the bank before tracing; a miss falls through to the
classic path and back-fills the bank) and at ``pipeline.FusedSegment``
(fused serving segments, with their trace-time guard messages persisted
as entry extras so a bank hit replays the same runtime guards).

On-disk format (``docs/performance.md`` §12):

- ``manifest.json`` — environment fingerprint (format version, jax +
  jaxlib versions, backend, device count) plus one record per entry
  (file name, sha256 content digest, kernel id). Written via the PR 14
  ``atomic_commit`` idiom: a reader never observes a torn manifest.
- ``<sighash>.pbx`` — pickle of the serialized executable payload, its
  in/out treedefs, the signature descriptor, and the extras dict. Also
  committed atomically.

Refusal semantics mirror PR 14 snapshot shards: a fingerprint mismatch
(different jax, different topology, unknown format) refuses the whole
bank; a per-entry digest mismatch or undeserializable payload refuses
that entry — always a loud warning plus a ``bank.refused`` tick, never a
crash, and always falling back to today's trace+compile path.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from . import config
from .utils.metrics import inc_counter, record_time, set_gauge

logger = logging.getLogger(__name__)

#: bump when the entry pickle schema or signature descriptor changes
FORMAT_VERSION = 2  # 2: manifest entries record their execution devices

MANIFEST = "manifest.json"
ENTRY_SUFFIX = ".pbx"


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

def _as_tuple(value) -> Tuple:
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return tuple(value)
    return (value,)


def static_token(value) -> Optional[str]:
    """A process-restart-stable token for one static argument, or None
    when the value has no stable identity (such a call is unbankable —
    it falls through to the classic trace+compile path, counted)."""
    if value is None or isinstance(value, (bool, int, float, str)):
        return repr(value)
    if isinstance(value, (tuple, list)):
        parts = [static_token(v) for v in value]
        if any(p is None for p in parts):
            return None
        return "(" + ",".join(parts) + ")"
    if isinstance(value, dict):
        items = []
        for k in sorted(value, key=repr):
            kt, vt = static_token(k), static_token(value[k])
            if kt is None or vt is None:
                return None
            items.append(f"{kt}:{vt}")
        return "{" + ",".join(items) + "}"
    # named singletons (LossFunc and friends): class + declared name
    name = getattr(value, "name", None)
    if isinstance(name, str):
        return f"{type(value).__name__}:{name}"
    return None


def _sharding_token(leaf) -> str:
    """Stable description of where a leaf lives: host values and
    uncommitted single-device arrays hash alike; a NamedSharding keys on
    the mesh axis layout + partition spec (topology, not device ids)."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return "host"
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is not None and spec is not None:
        axes = tuple((str(k), int(v)) for k, v in dict(mesh.shape).items())
        return f"named:{axes}:{spec}"
    return type(sharding).__name__


def _leaf_descriptor(leaf) -> Optional[str]:
    import jax

    try:
        aval = jax.api_util.shaped_abstractify(leaf)
    except Exception:
        return None
    weak = "w" if getattr(aval, "weak_type", False) else "s"
    return (
        f"{aval.dtype.name}[{','.join(str(d) for d in aval.shape)}]"
        f":{weak}:{_sharding_token(leaf)}"
    )


def split_static(
    args: Tuple, kwargs: Dict[str, Any], jit_kwargs: Dict[str, Any]
) -> Optional[Tuple[Tuple, Dict[str, Any], Dict[str, Any]]]:
    """Partition a call into (dynamic args, dynamic kwargs, statics).
    Serialized executables exclude static arguments from their input
    tree, so a bank hit must call with the dynamic operands only."""
    static_argnums = set(_as_tuple(jit_kwargs.get("static_argnums")))
    static_argnames = set(_as_tuple(jit_kwargs.get("static_argnames")))
    dyn_args = tuple(a for i, a in enumerate(args) if i not in static_argnums)
    dyn_kwargs = {k: v for k, v in kwargs.items() if k not in static_argnames}
    statics: Dict[str, Any] = {
        f"arg{i}": args[i] for i in sorted(static_argnums) if i < len(args)
    }
    statics.update({k: kwargs[k] for k in sorted(static_argnames) if k in kwargs})
    return dyn_args, dyn_kwargs, statics


def signature(
    kernel_id: str,
    args: Tuple,
    kwargs: Dict[str, Any],
    jit_kwargs: Dict[str, Any],
) -> Optional[Tuple[str, Dict[str, Any]]]:
    """(sig hash, descriptor) for one concrete call, or None when the
    call is not bankable (an untokenizable static, an unabstractifiable
    leaf). The hash keys the on-disk entry; the descriptor is persisted
    alongside for forensics and tests."""
    import jax

    split = split_static(args, kwargs, jit_kwargs)
    dyn_args, dyn_kwargs, statics = split
    static_tokens = {}
    for name, value in statics.items():
        token = static_token(value)
        if token is None:
            return None
        static_tokens[name] = token
    try:
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    except Exception:
        return None
    leaf_descs = []
    for leaf in leaves:
        desc = _leaf_descriptor(leaf)
        if desc is None:
            return None
        leaf_descs.append(desc)
    descriptor = {
        "kernel": kernel_id,
        "leaves": leaf_descs,
        "treedef": str(treedef),
        "statics": static_tokens,
        "donate": sorted(_as_tuple(jit_kwargs.get("donate_argnums"))),
    }
    digest = hashlib.sha256(
        json.dumps(descriptor, sort_keys=True).encode()
    ).hexdigest()[:32]
    return digest, descriptor


def env_fingerprint() -> Dict[str, Any]:
    """The bank-wide compatibility key: serialized executables are only
    loadable on the same jax/jaxlib under the same backend topology."""
    import jax

    return {
        "formatVersion": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(
            __import__("jaxlib"), "__version__", jax.__version__
        ),
        "backend": jax.default_backend(),
        "deviceCount": jax.device_count(),
    }


# ---------------------------------------------------------------------------
# the bank
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("fn", "extras", "source")

    def __init__(self, fn: Callable, extras: Optional[dict], source: str):
        self.fn = fn
        self.extras = extras
        self.source = source  # "load" | "backfill"


class ProgramBank:
    """One on-disk program bank plus its warm-loaded executables.

    Thread-safe; concurrent processes sharing a directory are safe
    against torn files (every write is an atomic replace) though a
    simultaneous manifest rewrite may drop the slower writer's entry —
    it back-fills again on next touch.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._execs: Dict[str, _Entry] = {}
        self._manifest_entries: Dict[str, Dict[str, Any]] = {}
        self._fingerprint = env_fingerprint()
        self._warned: set = set()
        self.load_ms = 0.0
        os.makedirs(path, exist_ok=True)
        self._warm_load()

    # -- warm load -----------------------------------------------------------
    def _warm_load(self) -> None:
        from .obs import tracing

        start = time.perf_counter()
        manifest_path = os.path.join(self.path, MANIFEST)
        if not os.path.exists(manifest_path):
            return
        try:
            with open(manifest_path) as f:
                manifest = json.load(f)
        except Exception as exc:  # torn/corrupt manifest: refuse the bank
            self._refuse(f"unreadable manifest ({exc}); starting empty")
            return
        if manifest.get("fingerprint") != self._fingerprint:
            self._refuse(
                "fingerprint mismatch "
                f"(bank {manifest.get('fingerprint')} vs "
                f"process {self._fingerprint}); refusing every entry"
            )
            return
        import jax
        from jax.experimental import serialize_executable

        devices_by_id = {d.id: d for d in jax.devices()}
        for sig, record in (manifest.get("entries") or {}).items():
            entry_path = os.path.join(self.path, record.get("file", ""))
            try:
                with open(entry_path, "rb") as f:
                    raw = f.read()
            except OSError as exc:
                self._refuse(f"entry {sig} unreadable ({exc})")
                continue
            if hashlib.sha256(raw).hexdigest() != record.get("sha256"):
                self._refuse(
                    f"entry {sig} digest mismatch — stale or torn payload, "
                    "refused like a corrupt snapshot shard"
                )
                continue
            try:
                payload = pickle.loads(raw)
                # load onto the devices the entry was compiled for: the
                # default is every device of the backend, which a program
                # compiled for a sub-mesh (or one device of several)
                # cannot execute on
                loaded = serialize_executable.deserialize_and_load(
                    payload["payload"],
                    payload["in_tree"],
                    payload["out_tree"],
                    execution_devices=[
                        devices_by_id[i] for i in record["devices"]
                    ],
                )
            except Exception as exc:
                self._refuse(f"entry {sig} failed to deserialize ({exc})")
                continue
            self._execs[sig] = _Entry(loaded, payload.get("extras"), "load")
            self._manifest_entries[sig] = record
            inc_counter("jit.bankLoads")
            tracing.event("bank.load", kernel=record.get("kernel"))
        self.load_ms = (time.perf_counter() - start) * 1000.0
        record_time("bank.load", self.load_ms / 1000.0)
        set_gauge("bank.entries", len(self._execs))

    def _refuse(self, why: str) -> None:
        inc_counter("bank.refused")
        if why not in self._warned:
            self._warned.add(why)
            logger.warning(
                "program bank %s: %s — falling back to trace+compile",
                self.path,
                why,
            )

    # -- lookup / backfill ---------------------------------------------------
    def lookup(self, sig: str) -> Optional[_Entry]:
        entry = self._execs.get(sig)
        if entry is not None:
            inc_counter("bank.hits")
        else:
            inc_counter("bank.misses")
        return entry

    def offer(
        self,
        sig: str,
        descriptor: Dict[str, Any],
        compiled,
        extras: Optional[dict] = None,
    ) -> None:
        """Back-fill one freshly AOT-compiled executable: serialize it,
        commit the entry + manifest atomically, and keep the live
        Compiled for in-process reuse. Serialization failure demotes the
        entry to in-process-only (warn once per kernel)."""
        with self._lock:
            self._execs[sig] = _Entry(compiled, extras, "backfill")
            inc_counter("bank.backfills")
            set_gauge("bank.entries", len(self._execs))
            try:
                from jax.experimental import serialize_executable

                payload, in_tree, out_tree = serialize_executable.serialize(
                    compiled
                )
                device_ids = [
                    d.id for d in compiled.runtime_executable().local_devices()
                ]
                raw = pickle.dumps(
                    {
                        "payload": payload,
                        "in_tree": in_tree,
                        "out_tree": out_tree,
                        "extras": extras,
                        "descriptor": descriptor,
                    }
                )
            except Exception as exc:
                key = ("serialize", descriptor.get("kernel"))
                if key not in self._warned:
                    self._warned.add(key)
                    logger.warning(
                        "program bank: kernel %s not serializable (%s) — "
                        "kept in-process only",
                        descriptor.get("kernel"),
                        exc,
                    )
                return
            self._persist(sig, descriptor, raw, device_ids)

    def _persist(
        self,
        sig: str,
        descriptor: Dict[str, Any],
        raw: bytes,
        device_ids: List[int],
    ) -> None:
        from .ckpt.coordinator import atomic_commit

        fname = sig + ENTRY_SUFFIX
        atomic_commit(
            os.path.join(self.path, fname),
            lambda tmp: _write_bytes(tmp, raw),
            site="bank.entry",
        )
        self._manifest_entries[sig] = {
            "file": fname,
            "sha256": hashlib.sha256(raw).hexdigest(),
            "kernel": descriptor.get("kernel"),
            "devices": device_ids,
        }
        manifest = {
            "fingerprint": self._fingerprint,
            "entries": self._manifest_entries,
        }
        atomic_commit(
            os.path.join(self.path, MANIFEST),
            lambda tmp: _write_bytes(
                tmp, json.dumps(manifest, sort_keys=True, indent=1).encode()
            ),
            site="bank.manifest",
        )

    # -- population ----------------------------------------------------------
    def populate(
        self, programs: Iterable[Tuple[Callable, Tuple, Dict[str, Any]]]
    ) -> int:
        """Drive each declared ``(callable, args, kwargs)`` program once
        so the lazyjit/segment funnels back-fill the bank ahead of
        traffic. Returns the number of programs touched."""
        n = 0
        for fn, args, kwargs in programs:
            fn(*args, **(kwargs or {}))
            n += 1
        return n

    def stats(self) -> Dict[str, float]:
        return {
            "entries": float(len(self._execs)),
            "loadMs": self.load_ms,
        }


def _write_bytes(path: str, raw: bytes) -> None:
    with open(path, "wb") as f:
        f.write(raw)


# ---------------------------------------------------------------------------
# the active-bank singleton (config.program_bank_dir)
# ---------------------------------------------------------------------------

_active: Dict[str, Any] = {"path": None, "bank": None}
_active_lock = threading.Lock()


def active_bank() -> Optional[ProgramBank]:
    """The process's ProgramBank for `config.program_bank_dir`, warm-
    loaded on first use; None when the bank is off (the default — every
    kernel then behaves exactly as before this module existed)."""
    path = config.program_bank_dir
    if path is None:
        return None
    with _active_lock:
        if _active["path"] != path or _active["bank"] is None:
            _active["bank"] = ProgramBank(path)
            _active["path"] = path
        return _active["bank"]


def reset_active_bank() -> None:
    """Drop the singleton (config.program_bank_mode scope transitions and
    tests); the next active_bank() warm-loads afresh."""
    with _active_lock:
        _active["path"] = None
        _active["bank"] = None


# ---------------------------------------------------------------------------
# the banked-call funnel (used by utils/lazyjit.py and pipeline.py)
# ---------------------------------------------------------------------------

def banked_call(
    bank: ProgramBank,
    kernel_id: str,
    traced_fn: Callable,
    args: Tuple,
    kwargs: Dict[str, Any],
    jit_kwargs: Dict[str, Any],
    extras_fn: Optional[Callable[[], dict]] = None,
    on_extras: Optional[Callable[[Optional[dict]], None]] = None,
):
    """Execute one kernel call through the bank.

    Returns ``(handled, result)`` — ``handled=False`` means the call is
    not bankable (caller runs its classic jit path). A hit calls the
    warm-loaded executable with the dynamic operands only (no trace, no
    compile); a miss AOT-compiles via ``lower().compile()`` (the trace
    runs ``traced_fn``'s body, so trace accounting and trace-time side
    effects such as FusedSegment guard capture still happen) and
    back-fills the bank, persisting ``extras_fn()`` alongside so future
    hits can replay trace-time state via ``on_extras``.
    """
    import jax

    if any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    ):
        # called under an enclosing trace (e.g. a lazy_jit kernel inside
        # a FusedSegment body): a compiled executable cannot consume
        # tracers — fall through so the inner call inlines into the
        # outer program, which is itself banked at the outer funnel
        inc_counter("bank.nestedTrace")
        return False, None
    sig_desc = signature(kernel_id, args, kwargs, jit_kwargs)
    if sig_desc is None:
        inc_counter("bank.unbankable")
        return False, None
    sig, descriptor = sig_desc
    dyn_args, dyn_kwargs, _ = split_static(args, kwargs, jit_kwargs)
    from .obs import tracing

    entry = bank.lookup(sig)
    if entry is not None:
        if on_extras is not None:
            on_extras(entry.extras)
        tracing.event("bank.hit", kernel=kernel_id, category="cache")
        return True, entry.fn(*dyn_args, **dyn_kwargs)
    start = time.perf_counter()
    with tracing.span("bank.compile", kernel=kernel_id, category="compile"):
        compiled = (
            jax.jit(traced_fn, **jit_kwargs).lower(*args, **kwargs).compile()
        )
    record_time("bank.compile", time.perf_counter() - start)
    extras = extras_fn() if extras_fn is not None else None
    bank.offer(sig, descriptor, compiled, extras=extras)
    if on_extras is not None:
        on_extras(extras)
    return True, compiled(*dyn_args, **dyn_kwargs)
