"""JAX persistent compilation cache switch.

Lives here — not in `repro.core.jax_engine`, whose import flips the
global x64 flag — so f32 callers (kernel microbenches, model tests) can
enable caching without inheriting the engine's dtype world.

Scope it deliberately: this JAX build miscompiles *deserialized*
executables for donated-buffer training steps (resuming training from
a cache hit yields garbage parameters — see tests/conftest.py), so
only enable it for workloads whose executables are known to round-trip
(the scheduling engine's are re-verified against the Python engine by
``benchmarks/run.py --smoke`` on every cached run).

The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise in ``.jax_cache`` at the root of the checkout. The path is
part of each entry's key, so it is fixed rather than per-user or
per-process: a cache that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def cache_dir() -> str:
    """Where `enable_compilation_cache` keeps compiled executables."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache (see `cache_dir`).

    The scheduling engine jit-specialises per (kernel, capacity,
    queue_cap, ...) tuple and each specialisation costs seconds of XLA
    compile time; tests and benchmarks re-pay it every process start.
    Caching compiled executables on disk makes repeat runs start hot.
    Safe to call more than once.
    """
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def disable_compilation_cache() -> None:
    """Turn the persistent cache back off (see module docstring).

    Clearing the config alone is not enough once the cache object has
    been lazily initialized — later compiles keep hitting it — so the
    initialized cache is reset too."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
