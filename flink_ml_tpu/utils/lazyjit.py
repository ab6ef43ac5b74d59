"""Lazily-compiled module-level jit kernels.

jax.jit called inside a function body creates a NEW wrapper per call, so
every call retraces and recompiles (seconds each). These helpers give the two needed shapes — a singleton kernel and
a kernel family keyed by a static value — as one-liners, replacing the
hand-rolled `global _X_JIT` caches that were spreading per module.

Runtime accounting: each wrapper creation bumps the `jit.kernels` counter,
and the first one installs the obs jax.monitoring hooks, so every actual
XLA backend compile (including shape-driven recompiles of an existing
wrapper) lands in `jit.compiles`/`jit.compile` and — when tracing is on —
as a `category=compile` span (obs/tracing.py). Every kernel body is
additionally wrapped so each *trace* ticks `jit.traces` (the body only
runs at trace time), giving the serving no-compile SLA an exact
trace count to assert against.

This module is also the AOT program bank's integration funnel
(compilebank.py, docs/performance.md §12): when `config.program_bank_dir`
is set, every call consults the bank before tracing — a hit calls a
warm-loaded serialized executable (no trace, no compile); a miss
AOT-compiles and back-fills the bank. With the bank off (the default)
behavior is byte-for-byte today's path.

`keyed_jit` factory caches are LRU-bounded at `config.kernel_cache_size`
entries (`jit.kernelCacheEvict` counter + `jit.kernelCacheSize` gauge);
an evicted key re-traces on its next touch with identical results.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Optional, Tuple


def _account_new_kernel() -> None:
    from ..obs import tracing
    from . import metrics

    metrics.inc_counter("jit.kernels")
    tracing.install_jax_hooks()  # jax is imported by the caller's next line


def _kernel_id(fn: Callable, key: Tuple = ()) -> Optional[str]:
    """Process-restart-stable bank identity for a kernel, or None when a
    factory key has no stable token (that family skips the bank)."""
    base = f"{getattr(fn, '__module__', '?')}.{getattr(fn, '__qualname__', getattr(fn, '__name__', '?'))}"
    if not key:
        return base
    from .. import compilebank

    tokens = [compilebank.static_token(k) for k in key]
    if any(t is None for t in tokens):
        return None
    return base + "[" + ",".join(tokens) + "]"


def _traced(fn: Callable) -> Callable:
    """Wrap a kernel body so each trace ticks `jit.traces`: the wrapper
    body only executes while jax is tracing, never on a cache hit."""
    import functools

    from . import metrics

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        metrics.inc_counter("jit.traces")
        return fn(*args, **kwargs)

    return traced


def _bank_consult(kernel_id: Optional[str], traced, args, kwargs, jit_kwargs):
    """(handled, result) through the program bank; (False, None) when the
    bank is off or the call is not bankable."""
    if kernel_id is None:
        return False, None
    from .. import compilebank

    bank = compilebank.active_bank()
    if bank is None:
        return False, None
    return compilebank.banked_call(
        bank, kernel_id, traced, args, kwargs, jit_kwargs
    )


def lazy_jit(fn: Callable, **jit_kwargs) -> Callable:
    """A callable that jits `fn` on first use and reuses the wrapper."""
    box = []

    def call(*args, **kwargs):
        if not box:
            import jax

            _account_new_kernel()
            traced = _traced(fn)
            box.append((traced, jax.jit(traced, **jit_kwargs)))
        traced, jitted = box[0]
        handled, result = _bank_consult(
            _kernel_id(fn), traced, args, kwargs, jit_kwargs
        )
        if handled:
            return result
        return jitted(*args, **kwargs)

    call.__name__ = getattr(fn, "__name__", "lazy_jit")
    return call


def keyed_jit(make_fn: Callable, **jit_kwargs) -> Callable:
    """A factory cache: `keyed_jit(make)(key)` jits `make(key)` once per
    distinct key (for kernels whose body depends on a static value)."""
    cache: "OrderedDict[Tuple, Callable]" = OrderedDict()

    def get(*key):
        fn = cache.get(key)
        if fn is not None:
            cache.move_to_end(key)
            return fn
        import jax

        from .. import config
        from . import metrics

        _account_new_kernel()
        traced = _traced(make_fn(*key))
        jitted = jax.jit(traced, **jit_kwargs)
        kernel_id = _kernel_id(make_fn, key)

        def call(*args, **kwargs):
            handled, result = _bank_consult(
                kernel_id, traced, args, kwargs, jit_kwargs
            )
            if handled:
                return result
            return jitted(*args, **kwargs)

        call.__name__ = getattr(make_fn, "__name__", "keyed_jit")
        cache[key] = call
        limit = max(1, int(config.kernel_cache_size))
        while len(cache) > limit:
            cache.popitem(last=False)
            metrics.inc_counter("jit.kernelCacheEvict")
        metrics.set_gauge("jit.kernelCacheSize", float(len(cache)))
        return call

    return get
