"""Pallas TPU kernel: fused Jacobi sweeps for the crossbar IR-drop network.

Large-array fidelity studies (core/ir_drop.jacobi_planar at 256x256+) are
bandwidth-bound: each jnp sweep re-reads v_row/v_col/g from HBM.  This
kernel keeps the whole tile resident in VMEM and runs ``sweeps_per_call``
damped-Jacobi iterations per grid step — a classic stencil-in-fast-memory
pattern (HBM traffic / sweep drops by the fusion factor).

One grid cell owns the full (n, m) problem (crossbar tiles are <= 512x512
by construction — engine tiles are VMEM-sized): v_row, v_col, g and v_in
all live in VMEM; the sweep loop is unrolled at trace time.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _kernel(g_ref, vin_ref, vrow_ref, vcol_ref, orow_ref, ocol_ref, *,
            g_w: float, omega: float, sweeps: int):
    g = g_ref[...]
    v_in = vin_ref[...]
    v_row = vrow_ref[...]
    v_col = vcol_ref[...]
    n, m = g.shape
    east_g = jnp.concatenate([jnp.full((n, m - 1), g_w, g.dtype),
                              jnp.zeros((n, 1), g.dtype)], axis=1)
    north_g = jnp.concatenate([jnp.zeros((1, m), g.dtype),
                               jnp.full((n - 1, m), g_w, g.dtype)], axis=0)
    den_r = g_w + east_g + g
    for _ in range(sweeps):
        west = jnp.concatenate([v_in, v_row[:, :-1]], axis=1)
        east_v = jnp.concatenate([v_row[:, 1:],
                                  jnp.zeros((n, 1), g.dtype)], axis=1)
        num_r = g_w * west + east_g * east_v + g * v_col
        v_row = v_row + omega * (num_r / den_r - v_row)

        north_v = jnp.concatenate([jnp.zeros((1, m), g.dtype),
                                   v_col[:-1, :]], axis=0)
        south_v = jnp.concatenate([v_col[1:, :],
                                   jnp.zeros((1, m), g.dtype)], axis=0)
        num_c = north_g * north_v + g_w * south_v + g * v_row
        den_c = north_g + g_w + g
        v_col = v_col + omega * (num_c / den_c - v_col)
    orow_ref[...] = v_row
    ocol_ref[...] = v_col


@functools.partial(jax.jit, static_argnames=("g_w", "omega", "sweeps"))
def jacobi_sweeps(g, v_in, v_row, v_col, *, g_w: float, omega: float = 1.0,
                  sweeps: int = 8):
    """Run ``sweeps`` fused Jacobi iterations.  g/(v_row/v_col): (n, m);
    v_in: (n, 1) column vector of source voltages."""
    n, m = g.shape
    return pl.pallas_call(
        functools.partial(_kernel, g_w=g_w, omega=omega, sweeps=sweeps),
        out_shape=(jax.ShapeDtypeStruct((n, m), g.dtype),
                   jax.ShapeDtypeStruct((n, m), g.dtype)),
        in_specs=[pl.BlockSpec((n, m), lambda: (0, 0)),
                  pl.BlockSpec((n, 1), lambda: (0, 0)),
                  pl.BlockSpec((n, m), lambda: (0, 0)),
                  pl.BlockSpec((n, m), lambda: (0, 0))],
        out_specs=(pl.BlockSpec((n, m), lambda: (0, 0)),
                   pl.BlockSpec((n, m), lambda: (0, 0))),
        compiler_params=pltpu.CompilerParams(),
        interpret=kernels.interpret_mode(),
        name="jacobi_sweeps",
    )(g, v_in, v_row, v_col)
