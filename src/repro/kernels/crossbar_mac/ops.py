"""Jitted wrapper: engine-facing entry point for the crossbar MAC kernel.

Handles quantization, padding to kernel-friendly shapes, scale application
and un-padding, so ``engine.matmul(..., use_kernel=True)`` is a drop-in for
the jnp reference path — including deep-net overlap reads, where the write
plane's common-mode leakage arrives as a traced ``leak_codes`` scalar
(changing its value between decode steps never re-lowers the kernel).

Expansion-fused reads (per-weight mode policy, ``executor.mode_report``)
ride this same lane: the executor dispatches them through its cached
expansion-mode cfg, so ``cfg.rows_per_adc`` doubles the pre-ADC grouping
and the fused pair's planes convert as one analog sum — with
``leak_codes`` pinned to the Python constant 0.0 at trace time, since a
fused pair never hosts an in-flight write.  Mixed-mode models therefore
lower one kernel variant per mode, not per swap-window state.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from repro.core import quant
from repro.kernels.crossbar_mac.kernel import crossbar_mac

# grouping-fallback warnings already emitted, keyed by tile geometry —
# warn once per geometry, not once per traced matmul
_FALLBACK_WARNED = set()


def _pad_axis(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def crossbar_matmul(x, pw, cfg, leak_codes=0.0):
    """x (..., K) float, pw: ProgrammedLinear, cfg: EngineConfig -> (..., N).

    ``leak_codes`` (python float or traced 0-d array) is the in-flight
    shadow write's common-mode pre-ADC offset, fused into the kernel's ADC
    stage exactly as ``engine.matmul_reference`` applies it.
    """
    q = cfg.quant
    lead = x.shape[:-1]
    xb = x.reshape(-1, x.shape[-1])
    x_int, x_scale = quant.quantize_inputs(xb, q)

    s, t, r, n_pad = pw.pos.shape
    pos = pw.pos.reshape(s, t * r, n_pad)
    neg = pw.neg.reshape(s, t * r, n_pad)
    x_int = _pad_axis(x_int.astype(jnp.int32), t * r, axis=-1)

    rows_per_adc = cfg.rows_per_adc
    full_scale_rows = cfg.rows_per_adc
    if (t * r) % rows_per_adc != 0:
        # odd number of row tiles in expansion mode: the pairwise analog
        # sum has no partner tile, so conversions fall back to per-plane
        # groups.  The ADC itself keeps the mode's full scale
        # (full_scale_rows) — matching the reference path, which digitizes
        # un-paired tiles against the expansion-mode range.
        rows_per_adc = r
        key = (cfg.mode, t, r)
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            warnings.warn(
                f"crossbar_mac: {t} row tiles of {r} rows cannot pair for "
                f"{cfg.mode}-mode analog summation ({t * r} rows % "
                f"{cfg.rows_per_adc} rows/ADC != 0); falling back to "
                f"per-plane conversions ({r} rows/ADC at the mode's "
                f"{full_scale_rows}-row full scale). ADC grouping differs "
                f"from the even-tile layout — pad K to a multiple of "
                f"{cfg.rows_per_adc} rows to avoid this.",
                stacklevel=3)

    block_b = min(128, max(8, x_int.shape[0]))
    block_n = min(128, n_pad)
    x_pad = _pad_axis(x_int, block_b, axis=0)
    pos = _pad_axis(pos, block_n, axis=-1)
    neg = _pad_axis(neg, block_n, axis=-1)

    y = crossbar_mac(
        x_pad, pos, neg, leak_codes,
        in_bits=q.in_bits, adc_bits=q.adc_bits,
        bits_per_cell=q.bits_per_cell, rows_per_adc=rows_per_adc,
        full_scale_rows=full_scale_rows,
        block_b=block_b, block_n=min(block_n, pos.shape[-1]))

    y = y[: xb.shape[0], :n_pad]
    y = y * x_scale * pw.w_scale[..., :n_pad]
    return y[:, : pw.n].reshape(*lead, pw.n)
