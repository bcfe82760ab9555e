"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``launch/serve.py``,
``launch/train.py`` and ``chip_smoke.py`` before their first compile —
never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and nothing else is set. Otherwise the cache lives at
the fixed path ``<checkout>/.jax_cache`` (the path is part of what makes a
second run hit, so it never carries a temporary name, a pid or a time).

The returned :class:`CompileLog` counts, from JAX's own monitoring events,
the backend compiles of this process, their seconds (a cache hit counts
the read), the executables read from the cache and those written to it.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path

import jax
from jax import monitoring

#: the checkout root: src/repro/launch/compile_cache.py → three levels up
CHECKOUT = Path(__file__).resolve().parents[3]

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"  # recorded per write


@dataclass
class CompileLog:
    cache_dir: str
    compiles: int = 0
    compile_s: float = 0.0
    cache_hits: int = 0
    cache_writes: int = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1
        elif event == _CACHE_WRITE:
            self.cache_writes += 1

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def summary(self) -> str:
        return (
            f"{self.compiles} compiles in {self.compile_s:.1f} s, "
            f"{self.cache_hits} read from the cache, {self.cache_writes} "
            f"written to it ({self.cache_dir})"
        )


def enable_compile_cache() -> CompileLog:
    """Turn the persistent compilation cache on for this process and start
    counting compiles. Call once, from an entry point, before compiling."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    log = CompileLog(cache_dir)
    monitoring.register_event_listener(log._on_event)
    monitoring.register_event_duration_secs_listener(log._on_duration)
    return log
