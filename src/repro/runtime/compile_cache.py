"""Where JAX's persistent compilation cache lives.

One rule for every script that compiles real work (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/*.py``, ``repro.launch.serve|train``):

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
    sets another directory;
  * otherwise, on an accelerator: ``<checkout>/.jax_cache``, a fixed path
    (the directory is part of the cache key, so a path made from a temp
    name, a pid or the time would never hit), listed in ``.gitignore``;
  * otherwise, on the CPU backend (tests and rehearsals): no cache. CPU
    compiles are cheap, and XLA:CPU warns at length on every cache load.

Cache hits and misses are counted from JAX's monitoring events, so a run
can report whether its programs came from the cache.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path
from typing import Optional

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/runtime/``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts: collections.Counter = collections.Counter()
_listening = False


def _on_event(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name:
        _counts[name] += 1


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on at its one place; returns the directory
    (None when it stays off). Call before the first compilation.
    Idempotent."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def cache_stats() -> dict:
    """Persistent-cache hits and misses seen since the cache was enabled."""
    return {"hits": _counts["hits"], "misses": _counts["misses"]}
