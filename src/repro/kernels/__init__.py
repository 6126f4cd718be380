"""Pallas TPU kernels (``<name>/kernel.py`` + ``ops.py`` + ``ref.py``).

Interpret mode is decided in exactly one place, :func:`interpret_mode`:
the Pallas interpreter runs only where JAX's default backend is the CPU.
No kernel entry point takes an ``interpret`` flag, so no caller can
forget one and run the interpreter on a TPU.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether a ``pallas_call`` runs in interpret mode: iff JAX's
    default backend is the CPU.  Compile-only checks that lower for a
    described TPU from a CPU host substitute ``lambda: False``."""
    return jax.default_backend() == "cpu"
