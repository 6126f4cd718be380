"""ir_solve Pallas kernel vs the jnp oracle and the exact nodal solver."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import ir_drop as ird
from repro.core.timing import PAPER
from repro.kernels.ir_solve.kernel import jacobi_sweeps
from repro.kernels.ir_solve.ops import solve
from repro.kernels.ir_solve.ref import jacobi_sweep_ref


# smallest point unmarked so the PR fast lane keeps an ir_solve assertion
@pytest.mark.parametrize("n,m,sweeps", [
    (8, 8, 1),
    pytest.param(8, 8, 4, marks=pytest.mark.slow),
    pytest.param(12, 6, 8, marks=pytest.mark.slow)])
def test_kernel_matches_ref_sweeps(n, m, sweeps):
    key = jax.random.PRNGKey(n * m)
    g = jax.random.uniform(key, (n, m), minval=PAPER.g_reset,
                           maxval=PAPER.g_set).astype(jnp.float32)
    v_in = jnp.full((n,), PAPER.v_read, jnp.float32)
    g_w = 1.0 / PAPER.r_wire
    vr = jnp.broadcast_to(v_in[:, None], (n, m)).astype(jnp.float32)
    vc = jnp.zeros((n, m), jnp.float32)
    kr, kc = jacobi_sweeps(g, v_in[:, None], vr, vc, g_w=float(g_w),
                           sweeps=sweeps)
    rr, rc = vr, vc
    for _ in range(sweeps):
        rr, rc = jacobi_sweep_ref(rr, rc, g, v_in, g_w, 1.0)
    assert jnp.allclose(kr, rr, rtol=1e-5, atol=1e-7)
    assert jnp.allclose(kc, rc, rtol=1e-5, atol=1e-7)


@pytest.mark.slow  # 3000 interpret-mode Jacobi iterations (CI full lane)
def test_solve_matches_direct_nodal():
    g = jnp.full((12, 8), PAPER.g_set)
    v = jnp.full((12,), PAPER.v_write)
    i_k, _, _ = solve(g, v, n_iter=3000, sweeps_per_call=50)
    i_d, _, _ = ird.solve_planar(g, v)
    assert float(jnp.max(jnp.abs(i_k - i_d) / i_d)) < 2e-3
