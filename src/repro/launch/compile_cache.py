"""JAX's persistent compilation cache, placed from outside the program.

``use_compile_cache()`` is called once at start-up by every entry point
(``chip_smoke.py``, ``repro.launch.hypergraph``,
``repro.launch.serve_hypergraph``, ``benchmarks/run.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else
  is configured in code;
* otherwise: the fixed directory ``<repo root>/.jax_cache`` (gitignored).
  The path is part of the cache key, so it is never temporary, per-pid
  or time-derived — a second run in the same checkout hits it.

``cache_events()`` counts JAX's own cache hits and misses for the run,
so a caller can show that the second run hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts = {"hits": 0, "misses": 0}
_listening = False


def repo_root() -> Path:
    """The checkout this package runs from (nearest ``pyproject.toml``
    above it), else the working directory."""
    here = Path(__file__).resolve()
    for cand in here.parents:
        if (cand / "pyproject.toml").exists():
            return cand
    return Path.cwd()


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    global _listening
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(repo_root() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def cache_events() -> dict:
    """Persistent-cache hits and misses seen since ``use_compile_cache``."""
    return dict(_counts)
