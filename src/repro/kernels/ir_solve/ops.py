"""Jitted wrapper: full IR-drop solve via the fused-sweep kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.timing import PAPER
from repro.kernels.ir_solve.kernel import jacobi_sweeps


def solve(g_dev, v_in, r_wire: float = PAPER.r_wire,
          r_access: float | None = None, n_iter: int = 2000,
          sweeps_per_call: int = 16, omega: float = 1.0):
    """Drop-in for core/ir_drop.jacobi_planar built on the Pallas kernel.

    Returns (i_out, v_row, v_col)."""
    if r_access is None:
        r_access = PAPER.r_on_transistor
    n, m = g_dev.shape
    g = 1.0 / (1.0 / jnp.maximum(g_dev, 1e-12) + r_access)
    g = g.astype(jnp.float32)
    g_w = 1.0 / r_wire
    v_row = jnp.broadcast_to(v_in[:, None], (n, m)).astype(jnp.float32)
    v_col = jnp.zeros((n, m), jnp.float32)
    vin_col = v_in[:, None].astype(jnp.float32)
    for _ in range(max(1, n_iter // sweeps_per_call)):
        v_row, v_col = jacobi_sweeps(g, vin_col, v_row, v_col,
                                     g_w=float(g_w), omega=omega,
                                     sweeps=sweeps_per_call)
    i_out = g_w * v_col[n - 1, :]
    return i_out, v_row, v_col
