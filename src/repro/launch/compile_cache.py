"""Persistent XLA compilation cache at a fixed place.

``enable()`` is called once at start-up by the entry points that drive the
chip (``chip_smoke.py``, ``launch/train_distributed.py``,
``launch/serve_zeroshot.py``). The cache key includes the directory, so
the directory never depends on a temporary name, a process id or the time:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing is
  set in code;
- otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
