"""Per-kernel allclose tests vs the pure-jnp oracles, swept over shapes,
dtypes and quantization configs (interpret mode on CPU)."""
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as eng
from repro.core import quant
from repro.core.quant import QuantConfig
from repro.kernels.crossbar_mac import ops as cb_ops
from repro.kernels.crossbar_mac.kernel import crossbar_mac
from repro.kernels.crossbar_mac.ref import crossbar_mac_ref
from repro.kernels.deepnet_stream.kernel import deepnet_stream
from repro.kernels.deepnet_stream.ops import stream_linear
from repro.kernels.deepnet_stream.ref import deepnet_stream_ref

jax.config.update("jax_enable_x64", False)


def _codes(key, shape, base):
    return jax.random.randint(key, shape, 0, base).astype(jnp.int8)


_slow = pytest.mark.slow  # interpret-mode sweeps: CI full lane only; the
# smallest point of each sweep stays unmarked so the PR fast lane keeps a
# kernel-correctness assertion

SWEEP = [
    # (B, K, N, S, in_bits, adc_bits, bits_per_cell, rows_per_adc)
    (4, 32, 16, 1, 4, 6, 1, 16),   # smallest: runs in the fast lane
    pytest.param(8, 64, 32, 3, 8, 8, 1, 32, marks=_slow),
    pytest.param(8, 128, 32, 4, 8, 12, 1, 64, marks=_slow),
    pytest.param(2, 48, 8, 2, 6, 10, 2, 16, marks=_slow),  # multi-bit cells
    pytest.param(16, 64, 64, 2, 8, 8, 2, 32, marks=_slow),
]


@pytest.mark.parametrize("b,k,n,s,ib,ab,bpc,rpa", SWEEP)
def test_crossbar_mac_matches_ref(b, k, n, s, ib, ab, bpc, rpa):
    key = jax.random.PRNGKey(b * 1000 + k)
    k1, k2, k3 = jax.random.split(key, 3)
    lo, hi = -(2 ** (ib - 1)), 2 ** (ib - 1)
    x_int = jax.random.randint(k1, (b, k), lo, hi).astype(jnp.int32)
    base = 2 ** bpc
    pos = _codes(k2, (s, k, n), base)
    neg = _codes(k3, (s, k, n), base)
    kw = dict(in_bits=ib, adc_bits=ab, bits_per_cell=bpc, rows_per_adc=rpa)
    ref = crossbar_mac_ref(x_int, pos, neg, **kw)
    out = crossbar_mac(x_int, pos, neg, block_b=min(b, 8),
                       block_n=min(n, 32), **kw)
    # tolerance: one ADC LSB accumulated per row-group and slice
    lsb = rpa * (base - 1) / (2.0 ** ab - 1.0)
    tol = lsb * (k // rpa) * s * 4 + 1e-3
    assert jnp.max(jnp.abs(out - ref)) <= tol


@pytest.mark.parametrize("dtype", [
    jnp.float32, pytest.param(jnp.bfloat16, marks=_slow)])
@pytest.mark.parametrize("b,k,n", [
    (8, 64, 32), pytest.param(4, 96, 16, marks=_slow)])
def test_deepnet_stream_matches_ref(b, k, n, dtype):
    key = jax.random.PRNGKey(k + n)
    k1, k2 = jax.random.split(key)
    x_int = jax.random.randint(k1, (b, k), -128, 128).astype(jnp.int32)
    w = (jax.random.normal(k2, (k, n)) * 0.4).astype(dtype)
    q = QuantConfig(w_bits=4, in_bits=8, adc_bits=10)
    ws = quant.weight_scales(w.astype(jnp.float32), q)
    kw = dict(w_bits=4, in_bits=8, adc_bits=10, bits_per_cell=1,
              rows_per_adc=32)
    ref = deepnet_stream_ref(x_int, w.astype(jnp.float32), ws, **kw)
    out = deepnet_stream(x_int, w.astype(jnp.float32), ws.astype(jnp.float32),
                         block_b=min(b, 8), block_n=min(n, 32), **kw)
    assert jnp.max(jnp.abs(out - ref)) <= 0.05


def test_engine_kernel_path_matches_reference_path():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (96, 80)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 96))
    for mode in ["expansion", "deepnet"]:
        qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=10)
        cfg_r = eng.EngineConfig(tile_rows=32, tile_cols=64, mode=mode,
                                 quant=qc)
        cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=64, mode=mode,
                                 quant=qc, use_kernel=True)
        pw = eng.program(w, cfg_r)
        y_r = eng.matmul(x, pw, cfg_r)
        y_k = eng.matmul(x, pw, cfg_k)
        assert jnp.allclose(y_r, y_k, atol=1e-4), mode


# ---------------------------------------------------------------------------
# Deep-net overlap reads: write-plane leakage as a traced kernel operand
# ---------------------------------------------------------------------------

# leakage in units of the ADC LSB: steady state, below one code (the
# paper's "negligible common-mode" regime, Fig. 3c), and well above it
# (where the ADC visibly digitizes the offset — parity must still hold)
_LEAK_LSB = [0.0, 0.4, 3.5]

_LEAK_SWEEP = [
    # (in_bits, adc_bits, bits_per_cell)
    (4, 6, 1),                                     # fast lane
    pytest.param(8, 8, 1, marks=_slow),
    pytest.param(10, 12, 1, marks=_slow),
    pytest.param(6, 5, 2, marks=_slow),            # coarse ADC, multi-bit
]


@pytest.mark.parametrize("leak_lsb", _LEAK_LSB)
@pytest.mark.parametrize("ib,ab,bpc", _LEAK_SWEEP)
def test_crossbar_mac_leak_parity_vs_ref(leak_lsb, ib, ab, bpc):
    """Kernel with a pre-ADC leak operand == oracle with the same leak."""
    b, k, n, s, rpa = 4, 64, 32, 2, 32
    key = jax.random.PRNGKey(ib * 100 + ab)
    k1, k2, k3 = jax.random.split(key, 3)
    lo, hi = -(2 ** (ib - 1)), 2 ** (ib - 1)
    x_int = jax.random.randint(k1, (b, k), lo, hi).astype(jnp.int32)
    base = 2 ** bpc
    pos = _codes(k2, (s, k, n), base)
    neg = _codes(k3, (s, k, n), base)
    lsb = rpa * (base - 1) / (2.0 ** ab - 1.0)
    leak = leak_lsb * lsb
    kw = dict(in_bits=ib, adc_bits=ab, bits_per_cell=bpc, rows_per_adc=rpa)
    ref = crossbar_mac_ref(x_int, pos, neg, leak_codes=leak, **kw)
    out = crossbar_mac(x_int, pos, neg, leak, block_b=min(b, 8),
                       block_n=min(n, 32), **kw)
    tol = lsb * (k // rpa) * s * 4 + 1e-3
    assert jnp.max(jnp.abs(out - ref)) <= tol


@pytest.mark.parametrize("mode", ["expansion", "deepnet"])
def test_engine_kernel_path_serves_nonzero_leak(mode):
    """use_kernel traffic stays on the Pallas path at leak != 0 (the
    overlap window is the hot path — no silent reference fallback) and
    matches matmul_reference at the same leak."""
    qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=10)
    cfg_r = eng.EngineConfig(tile_rows=32, tile_cols=64, mode=mode, quant=qc)
    cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=64, mode=mode,
                             quant=qc, use_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 80)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 128))
    pw = eng.program(w, cfg_r)
    lsb = cfg_r.rows_per_adc / (2.0 ** qc.adc_bits - 1.0)
    for leak in [0.0, 0.4 * lsb, 3.5 * lsb]:
        before = dict(eng.path_calls)
        y_k = eng.matmul(x, pw, cfg_k, leak_codes=leak)
        assert eng.path_calls["kernel"] == before["kernel"] + 1
        assert eng.path_calls["reference"] == before["reference"]
        y_r = eng.matmul_reference(x, pw, cfg_r, leak_codes=leak)
        assert jnp.allclose(y_k, y_r, atol=1e-4), (mode, leak)


def test_leak_zero_is_bitwise_identical_python_or_traced():
    """leak = 0.0 (the default, python float, or a device scalar) keeps the
    kernel output bit-identical — the operand plumbing costs nothing in
    steady state."""
    qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=10)
    cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=64, mode="deepnet",
                             quant=qc, use_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(11), (96, 48)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(13), (8, 96))
    pw = eng.program(w, cfg_k)
    y_default = eng.matmul(x, pw, cfg_k)
    y_py = eng.matmul(x, pw, cfg_k, leak_codes=0.0)
    y_traced = eng.matmul(x, pw, cfg_k, leak_codes=jnp.float32(0.0))
    assert jnp.array_equal(y_default, y_py)
    assert jnp.array_equal(y_default, y_traced)


def test_leak_value_changes_do_not_retrace():
    """The leak operand is traced, so one jitted closure serves every
    leak value — the serving tier flips it per decode step for free."""
    qc = QuantConfig(w_bits=4, in_bits=6, adc_bits=8)
    cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                             quant=qc, use_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(17), (64, 32)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(19), (8, 64))
    pw = eng.program(w, cfg_k)
    traces = []

    @jax.jit
    def f(leak):
        traces.append(1)                 # host-side: bumps per trace only
        return eng.matmul(x, pw, cfg_k, leak_codes=leak)

    y0 = f(jnp.float32(0.0))
    y1 = f(jnp.float32(2.5))
    assert len(traces) == 1
    assert not jnp.array_equal(y0, y1)   # 2.5 codes > one 8-bit ADC LSB


def test_odd_row_tile_fallback_warns_once_and_matches_reference():
    """Expansion mode with an odd row-tile count: conversions fall back to
    per-plane groups at the MODE'S full scale (matching the reference),
    and the grouping change is warned exactly once per geometry.  The
    coarse-ADC config makes a wrong full scale visible: digitizing
    against r*(base-1) instead of 2r*(base-1) would show up as O(1)
    output error, not ulps."""
    cb_ops._FALLBACK_WARNED.clear()
    qc = QuantConfig(w_bits=4, in_bits=6, adc_bits=5, bits_per_cell=2)
    cfg_r = eng.EngineConfig(tile_rows=32, tile_cols=64, mode="expansion",
                             quant=qc)
    cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=64, mode="expansion",
                             quant=qc, use_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 80)) * 0.3  # t = 3
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 96))
    pw = eng.program(w, cfg_r)
    with pytest.warns(UserWarning, match="cannot pair"):
        y_k = eng.matmul(x, pw, cfg_k)
    y_r = eng.matmul(x, pw, cfg_r)
    assert jnp.allclose(y_k, y_r, atol=1e-4)
    # same geometry again: warned already, stays quiet
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng.matmul(x, pw, cfg_k)
    assert not [w_ for w_ in rec if "cannot pair" in str(w_.message)]


def test_stream_linear_matches_engine_linear():
    x = jax.random.normal(jax.random.PRNGKey(8), (16, 96))
    w = jax.random.normal(jax.random.PRNGKey(9), (96, 80)) * 0.3
    cfg = eng.EngineConfig(tile_rows=32, tile_cols=64, mode="deepnet",
                           quant=QuantConfig(w_bits=4, in_bits=8,
                                             adc_bits=10))
    assert jnp.allclose(stream_linear(x, w, cfg), eng.linear(x, w, cfg),
                        atol=1e-4)


def test_kernel_nonaligned_shapes_via_ops():
    """ops.py must pad/unpad odd shapes correctly."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 70))
    w = jax.random.normal(jax.random.PRNGKey(2), (70, 33)) * 0.5
    qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=12)
    cfg_k = eng.EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                             quant=qc, use_kernel=True)
    cfg_r = eng.EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                             quant=qc)
    pw = eng.program(w, cfg_r)
    y_k = eng.matmul(x, pw, cfg_k)
    y_r = eng.matmul(x, pw, cfg_r)
    assert y_k.shape == (5, 33)
    assert jnp.allclose(y_k, y_r, atol=1e-4)
