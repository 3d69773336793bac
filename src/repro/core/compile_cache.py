"""Persistent compilation cache + compile-count instrumentation.

The sweep engines' planner programs cost seconds to tens of seconds to
compile and milliseconds to run; at 10^5-point scale the only tolerable
cold start is one that *loads* executables instead of rebuilding them.
:func:`enable_compile_cache` turns jax's persistent compilation cache on,
with the size/time thresholds zeroed so every planner program is cached.
One rule places it: ``JAX_COMPILATION_CACHE_DIR`` when that is set (and
then no code sets another directory), otherwise the fixed ``.jax_cache/``
at the checkout root, resolved from this file and not from the working
directory.  Entry points (``chip_smoke.py``, ``launch/serve.py``, the
sweep CLI, ``sweep_bench``'s scale cell) enable it before their first
compile; library calls such as ``Session.run_sweep`` leave it alone.
Tests place it by setting the environment variable.  Combined with the
bucketing policy (:mod:`.bucketing` — stable shapes => byte-identical
jaxprs => identical cache keys), a re-run of any sweep on a warm
directory skips XLA entirely.

:class:`CompileCounter` counts what actually happened, via
``jax.monitoring`` events:

* ``backend_compiles`` — executable builds the backend was asked for
  (``/jax/core/compile/backend_compile_duration``; fires on real compiles
  AND on persistent-cache loads),
* ``cache_misses`` / ``cache_hits`` — persistent-cache outcomes (these
  events only fire when the cache is enabled).

``compiles`` is the authoritative "XLA really ran" count: backend builds
the persistent cache did not serve, so benches and tests assert on one
number.  :meth:`CompileCounter.see` is the one place that reads the
events; ``serving/spans`` counts the program's compiles through it.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.monitoring
from jax._src import compilation_cache as _compilation_cache

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/core/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Enable jax's persistent compilation cache; returns its directory.

    The directory is ``$JAX_COMPILATION_CACHE_DIR``, read at call time, or
    :data:`DEFAULT_CACHE_DIR` when that is unset.  Idempotent; creates the
    directory.  Thresholds are zeroed so even fast-compiling programs
    persist (the default 1s floor would skip the small shape buckets that
    dominate smoke grids).
    """
    path = os.environ.get(_ENV_VAR) or str(DEFAULT_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    changed = jax.config.jax_compilation_cache_dir != path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax initializes the file cache lazily at the first compile; a compile
    # before this call pins it *disabled* (config updates alone never
    # re-initialize).  Reset so the next compile re-reads the config — the
    # on-disk contents are untouched.
    if changed or getattr(_compilation_cache, "_cache", None) is None:
        _compilation_cache.reset_cache()
    return path


# jax.monitoring event -> the CompileCounter field it counts
_EVENT_FIELDS = {
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}


@dataclass
class CompileCounter:
    """Context manager counting compiles/cache traffic within its scope.

    Outside ``with`` it is a plain tally: :meth:`see` counts events that a
    listener of the caller's own hands it."""

    backend_compiles: int = 0
    cache_misses: int = 0
    cache_hits: int = 0
    cache_requests: int = 0
    # Executables XLA actually built: backend builds the persistent cache
    # did not serve.  A build fires its backend event with or without the
    # cache, on a disk load too; a load's cache-hit event comes first.  The
    # request event is NOT a liveness signal (jax emits it with the cache
    # disabled).
    compiles: int = 0
    _served: bool = field(default=False, repr=False)
    _handles: list = field(default_factory=list, repr=False)

    def see(self, event: str) -> bool:
        """Count one ``jax.monitoring`` event; True when it marks a compile."""
        name = _EVENT_FIELDS.get(event)
        if name is None:
            return False
        setattr(self, name, getattr(self, name) + 1)
        if name == "cache_hits":
            self._served = True
        elif name == "backend_compiles":
            served, self._served = self._served, False
            if not served:
                self.compiles += 1
                return True
        return False

    def __enter__(self) -> "CompileCounter":
        def on_event(event: str, **kw) -> None:
            self.see(event)

        def on_duration(event: str, duration: float, **kw) -> None:
            self.see(event)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._handles = [on_event, on_duration]
        return self

    def __exit__(self, *exc) -> None:
        on_event, on_duration = self._handles
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)
        self._handles = []
