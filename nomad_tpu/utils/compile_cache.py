"""The persistent XLA compilation cache, and a counter of compiles.

A cold solver start pays seconds of XLA compiles per pow2 `(gp, kp)`
shape bucket that are byte-identical across restarts of the same
binary on the same topology.  JAX's persistent compilation cache makes
warm restarts skip them — the failover-relevant cost for a scheduler
that must resume placing within a heartbeat.

This module is the ONLY place that touches `jax_compilation_cache_dir`.
The cache is on by default for `Server`, `bench.py`, `chip_smoke.py`
and the tests, and its directory resolves in this order:

  1. `JAX_COMPILATION_CACHE_DIR` in the environment: JAX reads that
     variable itself, so nothing is set in code — whoever runs the
     program (an operator, a test driver) places the cache;
  2. the agent config's `server.compile_cache_dir`, when given;
  3. `<checkout>/.jax_cache` — fixed (the path is part of the cache
     key, so a directory that moves never hits) and git-ignored.

JAX decides once, at its first compile, whether the cache is in use:
call `enable_compile_cache` before the first jitted call.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_enabled_dir: Optional[str] = None


def enable_compile_cache(config_dir: Optional[str] = None) -> str:
    """Enable JAX's persistent compilation cache and return the
    directory in effect (resolution order in the module docstring).
    Idempotent; a later call without `config_dir` keeps the directory
    an earlier call configured."""
    global _enabled_dir
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    cache_dir = config_dir or _enabled_dir or DEFAULT_DIR
    if cache_dir != _enabled_dir:
        os.makedirs(cache_dir, exist_ok=True)
        import jax
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _enabled_dir = cache_dir
    return cache_dir


def cache_entries() -> int:
    """Number of compiled programs persisted in the cache directory —
    diffing before/after a startup gives the MISS count for the bench
    report (entries that were already there were warm hits)."""
    cache_dir = os.environ.get(ENV_VAR) or _enabled_dir
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for e in os.scandir(cache_dir) if e.is_file())


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Counts what JAX compiles in this process, from JAX's own
    monitoring events.  `requests` is every executable JAX asked the
    backend for, `cache_hits` those the persistent cache answered,
    `compiles` the remainder — programs actually compiled — and
    `wall_s` the host-clock time spent inside all of them (set-up time,
    never part of a steady-state figure).  JAX offers no way to remove
    one listener, so the watch is installed once per process and
    readers diff `snapshot()`s around the window they care about."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._requests = 0
        self._hits = 0
        self._wall_s = 0.0

    def install(self) -> "CompileWatch":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self._requests += 1
                self._wall_s += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self._hits += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"requests": self._requests,
                    "cache_hits": self._hits,
                    "compiles": self._requests - self._hits,
                    "wall_s": round(self._wall_s, 3)}

    @staticmethod
    def diff(before: Dict[str, float], after: Dict[str, float]
             ) -> Dict[str, float]:
        return {k: round(after[k] - before[k], 3) for k in after}
