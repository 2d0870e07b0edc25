"""Process set-up for runs on the accelerator: JAX's persistent compilation
cache, and a count of the compiles a run paid for.

Kernel shapes follow the data (chunk counts, stream widths, candidate
counts), so a cold run compiles many programs.  Call
:func:`enable_compile_cache` before the first compile so that a later run on
the same machine loads them instead.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "compile_counts", "CACHE_ENV",
           "DEFAULT_CACHE_DIR"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout: the path is part of what a cache entry matches
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_counts = {"executables": 0, "cache_hits": 0}
_listening = False


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set; otherwise the cache is ``.jax_cache/`` at the
    root of the checkout.  Every compile is cached, however short: the
    kernels compile in about a second each, under JAX's default threshold.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _counts["executables"] += 1


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _counts["cache_hits"] += 1


def compile_counts() -> dict:
    """``{"compiles", "cache_hits"}`` since the first call: programs the
    backend compiled, and programs loaded from the persistent cache
    instead."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return {"compiles": _counts["executables"] - _counts["cache_hits"],
            "cache_hits": _counts["cache_hits"]}
