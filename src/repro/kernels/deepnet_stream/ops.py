"""Jitted wrapper for the deep-net streaming matmul.

``stream_linear(x, w, cfg)`` is the deployment-shaped entry point: float
activations and float weights in, float activations out, with the program
step fused into the read pass (no programmed planes in HBM).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import quant
from repro.kernels.deepnet_stream.kernel import deepnet_stream


def _pad_axis(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def stream_linear(x, w, cfg):
    """x (..., K) float, w (K, N) float, cfg: EngineConfig -> (..., N)."""
    q = cfg.quant
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])
    x_int, x_scale = quant.quantize_inputs(xb, q)
    w_scale = quant.weight_scales(w, q)
    if not q.per_channel:
        w_scale = jnp.full((1, w.shape[1]), w_scale)

    rows = cfg.rows_per_adc
    k, n = w.shape
    x_int = _pad_axis(x_int.astype(jnp.int32), rows, axis=-1)
    w_p = _pad_axis(w.astype(jnp.float32), rows, axis=0)
    # widen the streamed weight tile to four row groups per fetch (the
    # double-buffered plane-read window: Pallas prefetches the next
    # block_k-deep tile while the current one's conversions run).  The
    # zero rows padding adds contribute exact zeros through quantize ->
    # MAC -> ADC, so the widened layout stays value-identical.
    block_k = 4 * rows if x_int.shape[1] > rows else rows
    x_int = _pad_axis(x_int, block_k, axis=-1)
    w_p = _pad_axis(w_p, block_k, axis=0)

    block_b = min(128, max(8, x_int.shape[0]))
    block_n = min(128, n)
    x_pad = _pad_axis(x_int, block_b, axis=0)
    w_p = _pad_axis(w_p, block_n, axis=1)
    s_pad = _pad_axis(w_scale.astype(jnp.float32), block_n, axis=1)
    # padded scale columns must be nonzero (div-by-zero in the kernel)
    s_pad = jnp.where(s_pad == 0.0, 1.0, s_pad)

    y = deepnet_stream(
        x_pad, w_p, s_pad, w_bits=q.w_bits, in_bits=q.in_bits,
        adc_bits=q.adc_bits, bits_per_cell=q.bits_per_cell,
        rows_per_adc=rows, block_b=block_b, block_n=block_n,
        block_k=block_k)

    y = y[: xb.shape[0], : n] * x_scale * w_scale[..., :n]
    return y.reshape(*lead, n)
