"""Pallas TPU kernel: bit-sliced CrossStack crossbar MAC.

One grid step materializes a (block_b x block_n) output tile's contribution
from one analog row group (``rows_per_adc`` rows — 2*tile_rows in expansion
mode, tile_rows in deep-net mode).  Inside the body:

  * the DAC happens in-register: the int32 inputs are expanded to
    two's-complement bit planes with shifts/masks (8x less input traffic
    than shipping pre-expanded pulse trains from HBM),
  * per (input bit, cell slice): one MXU matmul (bits x codes, exact in
    f32), followed by the saturating ADC — the per-conversion nonlinearity
    is fused in VMEM; nothing round-trips to HBM,
  * the signed shift-add recombine accumulates into the output block, which
    is revisited across the row-group grid axis (standard accumulate-over-K
    pattern; the K axis is marked "arbitrary").

Deep-net overlap reads (paper Fig. 3c): while the twin plane of a stacked
pair is being programmed, its OFF access transistors leak a common-mode
current into the shared columns.  That term rides into BOTH differential
conversions as a pre-ADC code offset — so it is a *scalar operand*, not a
compile-time constant: ``leak`` arrives as a (1, 1) f32 ref in SMEM and is
added to each analog accumulator before the ADC, exactly where
``engine._adc_codes(acc + leak_codes)`` applies it in the reference.
Passing it as a traced operand means one compiled kernel serves leak = 0
(steady state) and leak != 0 (an active hot-swap window) without
re-lowering — the serving tier flips the value per decode step.

ADC full scale is set by the *mode's* conversion group
(``full_scale_rows``), which may exceed ``rows_per_adc`` when an odd
row-tile count forces per-plane conversions in expansion mode (see
ops.py): the converter hardware keeps its range; only the analog group
shrinks.

VMEM budget per step (f32 words):
  x: block_b * rows  +  pos/neg: 2 * S * rows * block_n  +  out: block_b * block_n
With the default block_b = block_n = 128, rows = 256, S <= 4 this is
~1.2 MB << 16 MB v5e VMEM, leaving room for the automatic double buffering
that overlaps the next row-group's DMA with the current matmuls (the
deep-net read/write overlap, at the kernel level).

The MXU contraction dim is ``rows`` (a multiple of 128 in production
configs) and the output tile is 128-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _adc(acc, adc_bits: int, full_scale: float):
    # NB: divide (not reciprocal-multiply) so rounding at half-LSB points is
    # bit-identical to ref.py and the engine reference path.
    levels = 2.0 ** adc_bits - 1.0
    lsb = full_scale / levels
    return jnp.clip(jnp.round(acc / lsb), 0.0, levels) * lsb


def _kernel(leak_ref, x_ref, pos_ref, neg_ref, out_ref, *, in_bits: int,
            adc_bits: int, bits_per_cell: int, rows_per_adc: int,
            full_scale_rows: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = 2 ** bits_per_cell
    full_scale = float(full_scale_rows * (base - 1))
    leak = leak_ref[0, 0]                                 # common-mode code
    x = x_ref[...].astype(jnp.int32)                      # (B, R)
    u = x & ((1 << in_bits) - 1)                          # two's complement

    acc = jnp.zeros_like(out_ref)
    for p in range(in_bits):
        bitw = float(2 ** p) if p < in_bits - 1 else -float(2 ** p)
        xb = ((u >> p) & 1).astype(jnp.float32)           # in-register DAC
        for s in range(pos_ref.shape[0]):
            slcw = float(base ** s)
            ap = jax.lax.dot(xb, pos_ref[s].astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            an = jax.lax.dot(xb, neg_ref[s].astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            d = (_adc(ap + leak, adc_bits, full_scale)
                 - _adc(an + leak, adc_bits, full_scale))
            acc = acc + (bitw * slcw) * d
    out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=(
    "in_bits", "adc_bits", "bits_per_cell", "rows_per_adc",
    "full_scale_rows", "block_b", "block_n"))
def crossbar_mac(x_int, pos, neg, leak_codes=0.0, *, in_bits: int,
                 adc_bits: int, bits_per_cell: int, rows_per_adc: int,
                 full_scale_rows: Optional[int] = None,
                 block_b: int = 128, block_n: int = 128):
    """x_int (B, K) int32, pos/neg (S, K, N) int8 -> (B, N) f32 code units.

    ``leak_codes`` is the write-plane common-mode leakage in pre-ADC code
    units — a *traced* scalar (python float or 0-d array): changing its
    value does not re-lower the kernel.  ``full_scale_rows`` sets the ADC
    full scale independently of the contraction group (defaults to
    ``rows_per_adc``; ops.py passes the mode's group when an odd row-tile
    count forces smaller conversions).  K must be a multiple of
    rows_per_adc; B of block_b; N of block_n (ops.py pads).
    """
    b, k = x_int.shape
    s, k2, n = pos.shape
    assert k == k2 and k % rows_per_adc == 0
    if full_scale_rows is None:
        full_scale_rows = rows_per_adc
    grid = (b // block_b, n // block_n, k // rows_per_adc)
    leak = jnp.asarray(leak_codes, jnp.float32).reshape(1, 1)

    return pl.pallas_call(
        functools.partial(_kernel, in_bits=in_bits, adc_bits=adc_bits,
                          bits_per_cell=bits_per_cell,
                          rows_per_adc=rows_per_adc,
                          full_scale_rows=full_scale_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, t: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, rows_per_adc), lambda i, j, t: (i, t)),
            pl.BlockSpec((s, rows_per_adc, block_n),
                         lambda i, j, t: (0, t, j)),
            pl.BlockSpec((s, rows_per_adc, block_n),
                         lambda i, j, t: (0, t, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernels.interpret_mode(),
    )(leak, x_int, pos, neg)
