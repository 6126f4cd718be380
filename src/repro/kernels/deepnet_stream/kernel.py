"""Pallas TPU kernel: deep-net-mode streaming crossbar matmul.

The paper's deep-net mode programs one plane *while* the other is read, so
weight programming never stalls the data path (§III-B).  On TPU the same
schedule appears at the memory hierarchy: this kernel streams the *float*
weights tile-by-tile from HBM and performs the "program" step (quantize ->
differential cell codes) in VMEM, fused immediately with the "read" step
(bit-serial MAC + ADC).  Pallas' automatic block double-buffering prefetches
tile t+1's weights during tile t's matmuls — the write of the next tile
rides under the read of the current one, exactly the paper's
read-subsumed-in-write budget (pipeline.streaming_speedup gives the napkin
model).  ``block_k`` widens each streamed tile to several row groups
(default four via ops.py): one DMA covers block_k // rows_per_adc
conversions, so the prefetch has a longer read to hide under, while the
per-group ``out_ref += acc`` order keeps the output bitwise identical to
the narrow layout.

Napkin math (why fuse): the unfused path ships 2*S int8 code planes per
weight (pos+neg), i.e. 2*S bytes/weight of HBM traffic; streaming the bf16
master weight ships 2 bytes/weight and programs on the fly.  For the
default S = 4 slices that is a 4x cut of the dominant HBM term, and the
quantize/slice arithmetic (a handful of VPU ops per weight) hides under the
S * in_bits MXU matmuls per tile.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _adc(acc, adc_bits: int, full_scale: float):
    levels = 2.0 ** adc_bits - 1.0
    lsb = full_scale / levels
    return jnp.clip(jnp.round(acc / lsb), 0.0, levels) * lsb


def _kernel(x_ref, w_ref, scale_ref, out_ref, *, w_bits: int, in_bits: int,
            adc_bits: int, bits_per_cell: int, rows_per_adc: int,
            groups_per_block: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = 2 ** bits_per_cell
    n_slices = -(-w_bits // bits_per_cell)
    full_scale = float(rows_per_adc * (base - 1))
    qmax = 2.0 ** w_bits - 1.0
    r = rows_per_adc

    # the streamed tile covers groups_per_block row groups: Pallas
    # prefetches tile t+1 (one HBM->VMEM DMA of block_k rows) while the
    # body walks tile t's groups — the wider the tile, the longer the
    # plane read rides under the next plane's write (fetch)
    w_tile = w_ref[...].astype(jnp.float32)               # (block_k, N)
    x_tile = x_ref[...].astype(jnp.int32)                 # (B, block_k)

    for gi in range(groups_per_block):
        # ---- "program" phase: quantize this row group to cell codes ----
        w = w_tile[gi * r:(gi + 1) * r]                   # (R, N)
        w_int = jnp.clip(jnp.round(w / scale_ref[...]), -qmax, qmax)
        wp = jnp.maximum(w_int, 0.0)
        wn = jnp.maximum(-w_int, 0.0)

        # ---- "read" phase: bit-serial MAC with per-conversion ADC ------
        x = x_tile[:, gi * r:(gi + 1) * r]
        u = (x + (1 << in_bits)) % (1 << in_bits)

        acc = jnp.zeros_like(out_ref)
        for p in range(in_bits):
            bitw = float(2 ** p) if p < in_bits - 1 else -float(2 ** p)
            xb = ((u >> p) & 1).astype(jnp.float32)
            rp, rn = wp, wn
            for s in range(n_slices):
                slcw = float(base ** s)
                pos_s = rp - jnp.floor(rp / base) * base  # digit s
                neg_s = rn - jnp.floor(rn / base) * base
                rp = jnp.floor(rp / base)
                rn = jnp.floor(rn / base)
                ap = jax.lax.dot(xb, pos_s,
                                 preferred_element_type=jnp.float32)
                an = jax.lax.dot(xb, neg_s,
                                 preferred_element_type=jnp.float32)
                d = (_adc(ap, adc_bits, full_scale)
                     - _adc(an, adc_bits, full_scale))
                acc = acc + (bitw * slcw) * d
        # per-GROUP += in row-group order: the accumulation association
        # is identical to the block_k == rows_per_adc layout, so widening
        # the streamed tile never moves a bit of the output
        out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=(
    "w_bits", "in_bits", "adc_bits", "bits_per_cell", "rows_per_adc",
    "block_b", "block_n", "block_k"))
def deepnet_stream(x_int, w, w_scale, *, w_bits: int, in_bits: int,
                   adc_bits: int, bits_per_cell: int, rows_per_adc: int,
                   block_b: int = 128, block_n: int = 128,
                   block_k: int = 0):
    """x_int (B, K) int32, w (K, N) float, w_scale (1, N) -> (B, N) f32.

    ``block_k`` (0 = ``rows_per_adc``) is the streamed weight-tile depth:
    a multiple of ``rows_per_adc`` dividing K.  Each grid step along the
    K axis fetches one (block_k, block_n) tile and walks its row groups
    in order — bitwise identical to the one-group-per-step layout, but
    the prefetch window (the "write" that hides under the "read") spans
    ``block_k // rows_per_adc`` conversions instead of one.
    """
    b, k = x_int.shape
    k2, n = w.shape
    bk = block_k or rows_per_adc
    assert k == k2 and bk % rows_per_adc == 0 and k % bk == 0, (
        k, k2, rows_per_adc, bk)
    grid = (b // block_b, n // block_n, k // bk)

    return pl.pallas_call(
        functools.partial(_kernel, w_bits=w_bits, in_bits=in_bits,
                          adc_bits=adc_bits, bits_per_cell=bits_per_cell,
                          rows_per_adc=rows_per_adc,
                          groups_per_block=bk // rows_per_adc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, block_n), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, block_n), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernels.interpret_mode(),
        name="deepnet_stream",
    )(x_int, w, w_scale)
