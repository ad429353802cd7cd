"""Process set-up shared by the entry points: compile cache and device facts.

``chip_smoke.py``, ``python -m repro.launch.serve_dtwn`` and
``examples/marl_allocation.py`` call :func:`setup_compile_cache` before
their first compile, and print :func:`device_info` so every run names the
device it ran on.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache — a fixed path, so the next run finds what this
# one wrote (a path derived from a pid, a temp name or the time would start
# empty every run). Listed in .gitignore.
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``.jax_cache/`` in the
    checkout. Call before anything touches a device (``jax.devices()``
    included): JAX fixes its cache when the backend starts."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_info() -> dict:
    """The device every result of this process ran on, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
