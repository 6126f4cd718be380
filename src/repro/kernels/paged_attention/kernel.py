"""Pallas kernels: paged ragged decode attention, two lanes.

**Scratch lane** (``paged_attention_kernel``, the small-window fast path
and the bitwise oracle) — grid = (batch,): each step serves ONE sequence
row.  Inside the body:

  * the row's page table (a (1, P_seq) int32 operand) drives a staticly
    unrolled gather — each logical page is a dynamic-index load from the
    physical page pool into a VMEM scratch buffer, materializing the
    row's logical K/V view (depth = P_seq * page_size == max_len),
  * grouped SDPA over that view with the EXACT dense-path math: the same
    einsum contraction strings as ``models/layers._sdpa`` (f32
    accumulation via ``preferred_element_type``), the same -1e30 causal
    and length mask constants, the same softmax — so the kernel is
    bit-exact vs both ``ref.py`` and the dense cache path at equal
    contents (the interpret-mode CI pins this),
  * per-row scalars ``kv_len`` (valid depth) and ``q_offset`` (absolute
    position of the window's first query) arrive as (1, 1) SMEM operands
    — rows sit at different depths under continuous batching, and the
    causal offset must not be a trace constant.

The gather is READ-ONLY, so aliased page tables (two rows sharing
physical prefix pages under the scheduler's refcounted prefix sharing)
are in-contract and bit-exact vs materialized private copies; the
scheduler's copy-on-write keeps *writes* off shared pages before this
kernel ever runs (docs/KERNELS.md).

VMEM budget per step (one row): the gathered K+V views dominate at
2 * max_len * kv_heads * head_dim elements — peak scratch grows
LINEARLY with the window (``scratch_lane_vmem_bytes``), which is what
caps this lane at short windows.

**Streamed lane** (``paged_attention_streamed``, the long-context path)
— a block-streamed online-softmax (flash-style) kernel.  Grid =
(n_page_blocks,) with the whole batch folded into each block step; the
page table, fill lengths and query offsets arrive as *scalar-prefetch*
operands (``pltpu.PrefetchScalarGridSpec``; on TPU the table is in SMEM before
the first DMA issues).  Each step gathers ONE page block of K/V into a
two-slot VMEM scratch ring — block j+1 prefetches into the other slot
while block j is attended — and folds it into running max /
denominator / accumulator scratch carried across grid steps.

The O(block_pages) claim is about the *scratch* (the ring + the f32
online-softmax stats, ``streamed_lane_vmem_bytes``): it is constant in
the window length, which is what lets ``block_pages`` cap the working
set the attention math touches per step.  It is NOT yet the kernel's
total VMEM residency on a real TPU lowering: the current ``in_specs``
map the whole K/V pools as single full-array blocks (the body gathers
with ``k_ref[...][page_ids]``), which the CPU interpreter streams
lazily but a Mosaic lowering would make resident —
``streamed_lane_resident_bytes`` accounts that honestly (scratch +
2×pool), and the paged bench records both numbers.  Finishing the TPU
port means replacing the one-shot gather with per-block DMA out of
HBM-resident pools (``pltpu.make_async_copy`` indexed by the
prefetched table, or per-page index maps through the scalar-prefetch
operands); the slot arithmetic, the scratch layout and the numerics
below do not change — see docs/KERNELS.md "Porting notes".

Numerics contract per lane: the scratch lane is bitwise vs ref.py and
the dense ``_sdpa`` (the paged≡dense stream oracle).  The streamed lane
reassociates the softmax reduction (one block at a time), so bitwise
equality with the one-shot order is unattainable *by construction* —
and empirically even a jitted same-order jnp replica of the block
recursion drifts 1–2 ulp vs the in-kernel execution (XLA fuses the
multiply-adds differently inside the Pallas interpreter than in a plain
jit graph).  Its contract is therefore **bounded-ulp + argmax-stable**:
``|streamed − scratch| <= ~1e-6`` relative at fp32 and the argmax over
the head dim never moves (tests/test_paged_streamed.py pins both, plus
its own block-order oracle ``ref.paged_attention_streamed_ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _smem_scalar(index_map):
    """BlockSpec of one (1, 1) per-row scalar, held in SMEM."""
    return pl.BlockSpec((1, 1), index_map, memory_space=pltpu.SMEM)


def _kernel(pt_ref, len_ref, off_ref, q_ref, k_ref, v_ref, out_ref,
            ks_ref, vs_ref, *, page_size: int, pages_per_seq: int,
            causal: bool):
    ps = page_size
    # gather: logical page i of this row lives at physical page pt[i]
    # (0 = the null page — unallocated entries read zeros that the
    # length mask below excludes exactly)
    for i in range(pages_per_seq):
        pg = pt_ref[0, i]
        ks_ref[pl.ds(i * ps, ps)] = k_ref[pl.ds(pg, 1)].reshape(
            ps, *k_ref.shape[2:])
        vs_ref[pl.ds(i * ps, ps)] = v_ref[pl.ds(pg, 1)].reshape(
            ps, *v_ref.shape[2:])
    kk = ks_ref[...]                       # (depth, kv, hd)
    vv = vs_ref[...]
    q = q_ref[0]                           # (sq, hq, hd)
    if kk.dtype != q.dtype:   # low-precision (fp8) cache: upcast in-dot
        kk = kk.astype(q.dtype)
        vv = vv.astype(q.dtype)
    sq, hq, hd = q.shape
    depth, kv = kk.shape[0], kk.shape[1]
    g = hq // kv
    qg = q.reshape(sq, kv, g, hd)
    scale = hd ** -0.5
    # identical contraction to _sdpa's "bskgh,btkh->bkgst" at B = 1
    logits = jnp.einsum("skgh,tkh->kgst", qg, kk,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = off_ref[0, 0] + jnp.arange(sq)
        mask = qpos[:, None] >= jnp.arange(depth)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    valid = jnp.arange(depth) < len_ref[0, 0]
    logits = jnp.where(valid[None, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("kgst,tkh->skgh", w.astype(vv.dtype), vv)
    out_ref[0] = out.reshape(sq, hq, hd)


@functools.partial(jax.jit, static_argnames=("causal",))
def paged_attention_kernel(q, k_pages, v_pages, page_table, kv_len,
                           q_offset, *, causal: bool = True):
    """q (B, sq, hq, hd); k/v pages (P+1, ps, kv, hd); page_table
    (B, P_seq) int32; kv_len/q_offset (B,) int32 -> (B, sq, hq, hd).

    ``kv_len`` and ``q_offset`` are traced operands: rows at different
    cache depths share ONE lowered kernel.
    """
    b, sq, hq, hd = q.shape
    p1, ps, kv, _ = k_pages.shape
    p_seq = page_table.shape[1]
    depth = p_seq * ps
    grid = (b,)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, pages_per_seq=p_seq,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, p_seq), lambda i: (i, 0)),
            _smem_scalar(lambda i: (i, 0)),
            _smem_scalar(lambda i: (i, 0)),
            pl.BlockSpec((1, sq, hq, hd), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((p1, ps, kv, hd), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((p1, ps, kv, hd), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, sq, hq, hd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((depth, kv, hd), k_pages.dtype),
            pltpu.VMEM((depth, kv, hd), v_pages.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=kernels.interpret_mode(),
        name="paged_attention_kernel",
    )(page_table,
      kv_len.astype(jnp.int32).reshape(b, 1),
      q_offset.astype(jnp.int32).reshape(b, 1),
      q, k_pages, v_pages)


# -- streamed lane: block-streamed online softmax ---------------------------


def _stream_body(pt_ref, len_ref, off_ref, q_ref, k_ref, v_ref, out_ref,
                 ks_ref, vs_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                 block_pages: int, n_blocks: int, causal: bool):
    """One grid step = one page block for the WHOLE batch.

    Scratch refs carried across steps: ``ks/vs`` — the (2, B, block_tok,
    kv, hd) double-buffer ring; ``m/l`` — running max / denominator
    (B, kv, g, sq) in f32; ``acc`` — the unnormalized output accumulator
    (B, kv, g, sq, hd) in f32.  Step j attends the block prefetched at
    step j−1 (slot j % 2) while prefetching block j+1 into the other
    slot; the final step divides ``acc / l`` and writes the output.
    """
    j = pl.program_id(0)
    ps, bp = page_size, block_pages
    bt = bp * ps                                   # tokens per block
    b = q_ref.shape[0]

    def gather(jb, slot):
        # one-shot gather: B*bp page ids -> one XLA gather of the pool
        # (the unrolled per-page dynamic slices of the scratch lane cost
        # O(pages) kernel ops; this is O(1) ops per block)
        ptj = pt_ref[:, pl.ds(jb * bp, bp)].reshape(-1)
        kk = k_ref[...][ptj].reshape(b, bt, *k_ref.shape[2:])
        vv = v_ref[...][ptj].reshape(b, bt, *v_ref.shape[2:])
        ks_ref[pl.ds(slot, 1)] = kk[None]
        vs_ref[pl.ds(slot, 1)] = vv[None]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        gather(0, 0)                               # prime slot 0

    @pl.when(j + 1 < n_blocks)
    def _prefetch():                               # double-buffer: next
        gather(j + 1, (j + 1) % 2)                 # block -> other slot

    cur = j % 2
    kk = ks_ref[pl.ds(cur, 1)][0]                  # (B, bt, kv, hd)
    vv = vs_ref[pl.ds(cur, 1)][0]
    q = q_ref[...]
    if kk.dtype != q.dtype:   # low-precision (fp8) cache: upcast in-dot
        kk = kk.astype(q.dtype)
        vv = vv.astype(q.dtype)
    _, sq, hq, hd = q.shape
    kv = kk.shape[2]
    g = hq // kv
    qg = q.reshape(b, sq, kv, g, hd)
    scale = hd ** -0.5
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, kk,
                        preferred_element_type=jnp.float32) * scale
    tpos = j * bt + jnp.arange(bt)                 # absolute KV positions
    if causal:
        qpos = off_ref[:, 0][:, None] + jnp.arange(sq)[None]
        mask = qpos[:, :, None] >= tpos[None, None, :]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    valid = tpos[None, :] < len_ref[:, 0][:, None]
    logits = jnp.where(valid[:, None, None, None], logits, -1e30)
    # online softmax: rescale the running sums by exp(m_old - m_new)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, logits.max(axis=-1))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "bkgst,btkh->bkgsh", p, vv.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        out = acc_ref[...] / l_ref[...][..., None]
        out_ref[...] = out.transpose(0, 3, 1, 2, 4).reshape(
            b, sq, hq, hd).astype(out_ref.dtype)


def resolve_block_pages(pages_per_seq: int, block_pages: int) -> int:
    """Largest divisor of ``pages_per_seq`` that is <= ``block_pages``
    (the grid needs equal blocks; the serving tier's pages_per_seq is a
    power of two in practice, so this is usually ``block_pages`` itself)."""
    bp = max(1, min(block_pages, pages_per_seq))
    while pages_per_seq % bp:
        bp -= 1
    return bp


@functools.partial(jax.jit, static_argnames=("causal", "block_pages"))
def paged_attention_streamed(q, k_pages, v_pages, page_table, kv_len,
                             q_offset, *, causal: bool = True,
                             block_pages: int = 16):
    """Streamed-lane entry point; same signature/contract surface as
    ``paged_attention_kernel`` plus ``block_pages`` (pages per streamed
    block; clamped to a divisor of the table width).
    """
    b, sq, hq, hd = q.shape
    p1, ps, kv, _ = k_pages.shape
    p_seq = page_table.shape[1]
    bp = resolve_block_pages(p_seq, block_pages)
    n_blocks = p_seq // bp
    g = hq // kv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((b, sq, hq, hd), lambda j, *_: (0, 0, 0, 0)),
            # the K/V pools ride as single whole-array blocks: fine for
            # the interpreter (lazy gather), but VMEM-resident under a
            # real Mosaic lowering — streamed_lane_resident_bytes counts
            # them; the TPU port swaps these for per-block DMA (module
            # docstring)
            pl.BlockSpec((p1, ps, kv, hd), lambda j, *_: (0, 0, 0, 0)),
            pl.BlockSpec((p1, ps, kv, hd), lambda j, *_: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, sq, hq, hd), lambda j, *_: (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, b, bp * ps, kv, hd), k_pages.dtype),
            pltpu.VMEM((2, b, bp * ps, kv, hd), v_pages.dtype),
            pltpu.VMEM((b, kv, g, sq), jnp.float32),
            pltpu.VMEM((b, kv, g, sq), jnp.float32),
            pltpu.VMEM((b, kv, g, sq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_stream_body, page_size=ps, block_pages=bp,
                          n_blocks=n_blocks, causal=causal),
        out_shape=jax.ShapeDtypeStruct((b, sq, hq, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=kernels.interpret_mode(),
        name="paged_attention_streamed",
    )(page_table.astype(jnp.int32),
      kv_len.astype(jnp.int32).reshape(b, 1),
      q_offset.astype(jnp.int32).reshape(b, 1),
      q, k_pages, v_pages)


# -- peak-scratch accounting (the bench records these) ----------------------


def scratch_lane_vmem_bytes(pages_per_seq: int, page_size: int, kv: int,
                            hd: int, kv_dtype) -> int:
    """Peak VMEM scratch of the gather-then-SDPA lane: the K+V logical
    views, LINEAR in the window length."""
    itemsize = jnp.dtype(kv_dtype).itemsize
    return 2 * pages_per_seq * page_size * kv * hd * itemsize


def streamed_lane_vmem_bytes(b: int, sq: int, hq: int, kv: int, hd: int,
                             pages_per_seq: int, page_size: int,
                             block_pages: int, kv_dtype) -> int:
    """VMEM *scratch* of the streamed lane: the two-slot K/V block ring
    plus the f32 running max/denominator/accumulator — a function of
    ``block_pages``, NOT of the window length.  This is the working set
    the per-step attention math touches; it is not the lowering's total
    residency (see :func:`streamed_lane_resident_bytes`)."""
    bp = resolve_block_pages(pages_per_seq, block_pages)
    itemsize = jnp.dtype(kv_dtype).itemsize
    g = hq // kv
    ring = 2 * 2 * b * bp * page_size * kv * hd * itemsize
    stats = (2 * b * kv * g * sq + b * kv * g * sq * hd) * 4
    return ring + stats


def streamed_lane_resident_bytes(b: int, sq: int, hq: int, kv: int,
                                 hd: int, pages_per_seq: int,
                                 page_size: int, block_pages: int,
                                 n_pool_pages: int, kv_dtype) -> int:
    """Total VMEM the CURRENT lowering would hold resident on real TPU:
    the scratch above plus the two whole K/V pools that the full-array
    ``in_specs`` pin per grid step (``n_pool_pages`` includes the null
    page).  Honest accounting for the interpret-mode-only gap the TPU
    port closes — once the gather becomes per-block HBM DMA this
    collapses to :func:`streamed_lane_vmem_bytes`."""
    itemsize = jnp.dtype(kv_dtype).itemsize
    pools = 2 * n_pool_pages * page_size * kv * hd * itemsize
    return streamed_lane_vmem_bytes(b, sq, hq, kv, hd, pages_per_seq,
                                    page_size, block_pages,
                                    kv_dtype) + pools
