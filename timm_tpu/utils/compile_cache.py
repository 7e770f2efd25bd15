"""Persistent XLA compilation cache + compile-cost reporting utilities.

JAX's persistent compilation cache makes compiled executables durable across
processes: a second cold process re-loading the same program pays only a disk
read instead of a full XLA compile. The directory is part of the cache key, so
it must not move between runs:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps the cache
    there and this module sets no directory;
  * otherwise the cache lives in one fixed, git-ignored directory inside the
    checkout (``CHECKOUT_CACHE_DIR``, resolved from this file's location, not
    the working directory).

One subtlety this module handles: JAX latches its "is the cache enabled?"
decision at the FIRST compilation of the process. Setting the directory after
any jit has run silently does nothing. ``configure_compile_cache`` therefore
resets the cache state after configuring so late configuration still takes
effect.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    '.jax_cache')


def configure_compile_cache(
        min_entry_size_bytes: int = 0,
        min_compile_time_secs: float = 0.5,
) -> str:
    """Switch JAX's persistent compilation cache on for this process.

    Call at process start (the entry scripts, the serve engine and the tier-1
    conftest do) so every compile in the process is eligible. Returns the
    directory in use. Safe to call more than once and after jits have already
    run (the cache-enabled latch is reset).
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
        jax.config.update('jax_compilation_cache_dir', CHECKOUT_CACHE_DIR)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', min_entry_size_bytes)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', min_compile_time_secs)
    # un-latch the once-per-process enabled check so configuration after an
    # early jit (imports) still takes effect
    compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir


_BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


@contextlib.contextmanager
def collect_cache_events():
    """Count JAX's compile events within the block into a dict.

    Every program JAX builds — compiled afresh or read back from the
    persistent cache — records ``/jax/core/compile/backend_compile_duration``
    once, so its count is the number of compilations, cache or no cache. With
    the persistent cache on, one served from disk also records
    ``/jax/compilation_cache/cache_hits`` and one written to disk
    ``.../cache_misses`` (a compile under the persistence thresholds is
    neither). Each collector registers its own listeners, so nested
    measurements (engine prewarm inside drill inside test) each see their own
    counts."""
    import jax

    counts: Dict[str, int] = {}

    def on_event(event, **kwargs):
        if '/compilation_cache/' in event:
            counts[event] = counts.get(event, 0) + 1

    def on_duration(event, duration_secs, **kwargs):
        if event == _BACKEND_COMPILE_EVENT:
            counts[event] = counts.get(event, 0) + 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)


def cache_event_total(counts: Dict[str, int], suffix: str) -> int:
    """Sum event counts whose key ends with ``suffix`` (e.g. 'cache_hits')."""
    return sum(v for k, v in counts.items() if k.endswith(suffix))


def iter_jaxpr_eqns(jaxpr):
    """Every equation of a (closed) jaxpr, nested sub-jaxprs (scan/while/cond
    bodies, remat) included."""
    for eqn in getattr(jaxpr, 'jaxpr', jaxpr).eqns:
        yield eqn
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(item, 'jaxpr'):
                    yield from iter_jaxpr_eqns(item)


def count_jaxpr_eqns(jaxpr) -> int:
    """Total equation count of a (closed) jaxpr including nested sub-jaxprs.
    The proxy for trace/lowering cost: a Python block loop contributes
    O(depth) equations, a scanned stack O(1)."""
    return sum(1 for _ in iter_jaxpr_eqns(jaxpr))
