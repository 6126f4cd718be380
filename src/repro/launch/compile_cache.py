"""Persistent compilation cache placement, shared by every entry point
that compiles for a device (``launch/serve.py``, ``chip_smoke.py``,
``benchmarks/run.py``).

JAX keys cache entries on the directory they live in, so the directory
must be fixed: ``<repo>/.jax_cache``, never a temporary name, a process
id or a time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and this module sets nothing.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: the repository root (this file is ``<repo>/src/repro/launch/...``)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``<repo>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it.  Returns the
    directory this call set, or None when the environment decides."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
