"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``).

A cold TPU process compiles every kernel and program it runs; the
persistent cache lets the next process from the same checkout load them
instead. The directory is part of what makes a cache hit possible, so it
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<repo>/.jax_cache`` — never a
temporary name, a pid or a timestamp.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in
    use. Call once per process, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
