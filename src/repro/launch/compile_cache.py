"""Persistent XLA compilation cache for the entry points.

A full-width program takes seconds to compile, and serving compiles one per
pow-2 jit key (DESIGN.md §9), so a cold start pays for dozens.  JAX's
persistent cache keeps the compiled programs on disk, keyed by path among
other things, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  overrides it.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

The entry points call :func:`configure_compile_cache`; tests never do.
:class:`CompileStats` counts compilations and cache hits through
``jax.monitoring`` so a run can report its set-up cost.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    # cache every program, not only those that took over a second: the
    # serving programs' compile time varies with the host
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


class CompileStats:
    """Counts XLA compilations (cache hits included), their seconds, and
    persistent-cache hits from the moment it is created.  ``jax.monitoring``
    listeners live as long as the process, so create one per process."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @property
    def fresh_compiles(self) -> int:
        """Compilations the persistent cache did not serve."""
        return self.compiles - self.cache_hits

    def snapshot(self) -> dict:
        return {"compiles": self.compiles,
                "fresh_compiles": self.fresh_compiles,
                "cache_hits": self.cache_hits,
                "compile_seconds": self.compile_seconds}
