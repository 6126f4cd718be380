"""Model-zoo building blocks: norms, RoPE/M-RoPE, GQA attention (qk-norm,
KV cache, chunked/causal), dense MLPs (SwiGLU / squared-ReLU / GELU) and
token-dropping MoE with group-local sort-based dispatch.

Everything is a pure function over explicit param dicts.  Each ``init_*``
returns ``(params, specs)`` where specs mirror params with tuples of
*logical* axis names consumed by distributed/sharding.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.executor import crossbar_linear
from repro.distributed.sharding import logical_constraint as lc

Params = Dict[str, Any]


# -- initializers -------------------------------------------------------------

def _normal(key, shape, scale, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, spec=("embed", "mlp"),
               scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return _normal(key, (d_in, d_out), scale), spec


# -- norms --------------------------------------------------------------------

def rmsnorm_init(d: int, spec=(None,)):
    return jnp.ones((d,), jnp.float32), spec


def rmsnorm(x, w, eps: float = 1e-6):
    # statistics in f32; the normalized tensor is cast back BEFORE the
    # weight multiply so the op feeding any downstream sharding constraint
    # is a bf16 multiply — otherwise XLA hoists SP all-gathers above the
    # final convert and moves the activation in f32 (2x bytes; §Perf H1).
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(dtype)
    return normed * w.astype(dtype)


def layernorm_init(d: int):
    return {"w": jnp.ones((d,), jnp.float32),
            "b": jnp.zeros((d,), jnp.float32)}, {"w": (None,), "b": (None,)}


def layernorm(x, p, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]).astype(dtype)


# -- rotary embeddings ---------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e6):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e6):
    """x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv      # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, theta: float = 1e6, sections=None):
    """Qwen2-VL multimodal RoPE: positions_thw (B, S, 3) = (t, h, w) ids.

    The head_dim/2 frequency slots are split into (temporal, height, width)
    sections; each section rotates by its own position stream.  Text tokens
    carry t == h == w, reducing to standard RoPE.  Default split follows
    Qwen2-VL's 1:1.5:1.5 ratio ((16, 24, 24) at head_dim = 128).
    """
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                # (D/2,)
    n = d // 2
    if sections is None:
        t = n // 4
        h = (n - t) // 2
        sections = (t, h, n - t - h)
    assert sum(sections) == n, (sections, n)
    sec_ids = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                         total_repeat_length=n)               # (D/2,)
    pos = jnp.take_along_axis(
        positions_thw.astype(jnp.float32),
        jnp.broadcast_to(sec_ids[None, None, :],
                         positions_thw.shape[:2] + (n,)).astype(jnp.int32),
        axis=-1)                                              # (B, S, D/2)
    ang = pos * inv
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- attention -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 1e6
    kv_repeat: int = 1          # Megatron-style KV replication for TP > n_kv
    causal: bool = True
    mrope: bool = False
    q_chunk: int = 0            # 0 = unchunked; else chunk the query axis
    chunk_unroll: bool = True   # unroll the q-chunk loop (see DESIGN §5)
    paged_kernel: bool = False  # paged decode via the Pallas kernel
    paged_stream_pages: int = 0  # stream the paged kernel (online-softmax
    # block lane) when the page table is >= this many pages; 0 = always
    # the gather-scratch lane (the bitwise small-window fast path)
    paged_block_pages: int = 16  # pages per streamed block (VMEM knob)

    @property
    def kv_eff(self) -> int:
        return self.n_kv * self.kv_repeat


def attn_init(key, cfg: AttnConfig):
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    p: Params = {
        "wq": _normal(ks[0], (d, cfg.n_heads, hd), d ** -0.5),
        "wk": _normal(ks[1], (d, cfg.kv_eff, hd), d ** -0.5),
        "wv": _normal(ks[2], (d, cfg.kv_eff, hd), d ** -0.5),
        "wo": _normal(ks[3], (cfg.n_heads, hd, d),
                      (cfg.n_heads * hd) ** -0.5),
    }
    s: Params = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"], s["q_norm"] = rmsnorm_init(hd)
        p["k_norm"], s["k_norm"] = rmsnorm_init(hd)
    return p, s


def _qkv_proj(x, w, name):
    """q/k/v projection, routable onto resident crossbar tiles."""
    return crossbar_linear(
        x, w, name,
        digital=lambda: jnp.einsum("bsd,dhk->bshk", x, w.astype(x.dtype)))


def _project_qkv(p, cfg: AttnConfig, x, positions):
    q = _qkv_proj(x, p["wq"], "wq")
    k = _qkv_proj(x, p["wk"], "wk")
    v = _qkv_proj(x, p["wv"], "wv")
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    elif positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = lc(q, ("batch", None, "act_heads", None))
    k = lc(k, ("batch", None, "act_kv_heads", None))
    v = lc(v, ("batch", None, "act_kv_heads", None))
    return q, k, v


def _sdpa(q, k, v, cfg: AttnConfig, q_offset, kv_len=None,
          cross: bool = False):
    """Grouped scaled-dot-product attention on (B, S, H, D) tensors.

    q_offset: absolute position of q[.., 0] for causal masking — a
              scalar, or (B,) when slots sit at different depths
              (continuous batching admits prompts of different lengths).
    kv_len:   (B,) valid KV lengths (decode), or None for full.
    """
    b, sq, hq, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = hq // kv
    if k.dtype != q.dtype:   # low-precision (fp8) cache: upcast fuses
        k = k.astype(q.dtype)  # into the dot, no materialized copy
        v = v.astype(q.dtype)
    qg = q.reshape(b, sq, kv, g, hd)
    scale = hd ** -0.5
    # bf16 operands, f32 ACCUMULATION: never materialize an f32 copy of the
    # (potentially huge) K tensor — MXU-style mixed precision.
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cfg.causal and not cross:
        qpos = (jnp.asarray(q_offset).reshape(-1, 1)
                + jnp.arange(sq)[None])                   # (B or 1, sq)
        mask = qpos[:, :, None] >= jnp.arange(sk)[None, None, :]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    if kv_len is not None:
        valid = jnp.arange(sk)[None, :] < kv_len[:, None]
        logits = jnp.where(valid[:, None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v)
    return out.reshape(b, sq, hq, hd)


def _chunked_sdpa(q, k, v, cfg: AttnConfig, kv_len=None,
                  cross: bool = False, q_offset=0):
    """Q-axis-chunked SDPA: bounds the scores working set to
    (B, H, q_chunk, S_k) — applies to self, cross, AND cache-prefill
    attention (a 32k x 32k unchunked score tensor is tens of GiB)."""
    b, sq = q.shape[:2]
    if not (cfg.q_chunk and sq > cfg.q_chunk and sq % cfg.q_chunk == 0):
        return _sdpa(q, k, v, cfg, q_offset, kv_len=kv_len, cross=cross)
    nq = sq // cfg.q_chunk
    qs = q.reshape(b, nq, cfg.q_chunk, *q.shape[2:])

    def one(i, qi):
        return _sdpa(qi, k, v, cfg, q_offset + i * cfg.q_chunk,
                     kv_len=kv_len, cross=cross)

    if cfg.chunk_unroll:
        outs = [one(i, qs[:, i]) for i in range(nq)]
        out = jnp.stack(outs, axis=1)
    else:
        def body(_, iq):
            i, qi = iq
            return 0.0, one(i, qi)

        _, outs = jax.lax.scan(body, 0.0,
                               (jnp.arange(nq), qs.swapaxes(0, 1)))
        out = outs.swapaxes(0, 1)
    return out.reshape(b, sq, *q.shape[2:])


def _paged_attend(q, k, v, cfg: AttnConfig, cache):
    """Paged decode: route this window's K/V writes through the page
    table, then attend over the gathered logical view.  ``pt`` maps each
    row's logical pages to physical pages of the pool arrays (leading
    axis n_pages + 1); physical page 0 is the reserved null page —
    unallocated entries point at it, so out-of-range writes land there
    (the dense path's dropped-OOB-scatter semantics) and gathers through
    it read only masked positions.  Returns (out, new_cache)."""
    b, sq = q.shape[:2]
    pos = cache["len"]                                    # (B,)
    pt = cache["pt"]                                      # (B, P_seq)
    ps = cache["k"].shape[1]
    depth = pt.shape[1] * ps                              # == max_len
    s_idx = pos[:, None] + jnp.arange(sq)[None]           # (B, sq)
    inb = s_idx < depth
    lpage = jnp.minimum(s_idx // ps, pt.shape[1] - 1)
    phys = jnp.where(inb, jnp.take_along_axis(pt, lpage, axis=1), 0)
    slot = jnp.where(inb, s_idx % ps, 0)
    ck = cache["k"].at[phys, slot].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[phys, slot].set(v.astype(cache["v"].dtype))
    new_len = cache["len"] + sq
    if cfg.paged_kernel:
        from repro.kernels.paged_attention import paged_attention
        out = paged_attention(q, ck, cv, pt, new_len, pos,
                              causal=cfg.causal,
                              stream_min_pages=cfg.paged_stream_pages,
                              block_pages=cfg.paged_block_pages)
    else:
        # gather width is exactly max_len (page_size | max_len), so the
        # SDPA below sees the same einsum shapes as the dense branch —
        # the bit-exactness contract with that path
        gk = ck[pt].reshape(b, depth, *ck.shape[2:])
        gv = cv[pt].reshape(b, depth, *cv.shape[2:])
        out = _chunked_sdpa(q, gk, gv, cfg, kv_len=new_len, q_offset=pos)
    return out, {"k": ck, "v": cv, "len": new_len, "pt": pt}


def attention(p, cfg: AttnConfig, x, positions, cache=None,
              cross_kv=None, kv_len=None):
    """Returns (y, new_cache).

    cache: None (training / prefill-no-cache) or dict with
      k, v: (B, S_max, kv_eff, hd) and "len": (B,) int32 fill marker.
    cross_kv: (k, v) precomputed for encoder-decoder cross attention.
    """
    sq = x.shape[1]
    if cross_kv is not None:
        q = _qkv_proj(x, p["wq"], "wq")
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"])
        k, v = cross_kv
    else:
        q, k, v = _project_qkv(p, cfg, x, positions)
    # the scope covers the K/V writes and gathers, scores, softmax and
    # PV; the projections on either side stay outside it
    with jax.named_scope("attention"):
        if cross_kv is not None:
            out = _chunked_sdpa(q, k, v, cfg, kv_len=kv_len, cross=True)
            new_cache = cache
        elif cache is None:
            out = _chunked_sdpa(q, k, v, cfg)
            new_cache = None
        elif "pt" in cache:
            out, new_cache = _paged_attend(q, k, v, cfg, cache)
        else:
            # decode: append this step's K/V at each row's own fill
            # position — slots admitted with different prompt lengths
            # sit at different depths, so the write index and causal
            # offset are per-row
            pos = cache["len"]                            # (B,)
            b_idx = jnp.arange(x.shape[0])[:, None]
            s_idx = pos[:, None] + jnp.arange(sq)[None]
            ck = cache["k"].at[b_idx, s_idx].set(
                k.astype(cache["k"].dtype))
            cv = cache["v"].at[b_idx, s_idx].set(
                v.astype(cache["v"].dtype))
            new_len = cache["len"] + sq
            out = _chunked_sdpa(q, ck, cv, cfg, kv_len=new_len,
                                q_offset=pos)
            new_cache = {"k": ck, "v": cv, "len": new_len}
    # explicit bf16 dot output: the TP partial-sum all-reduce then moves
    # bf16, not the f32 accumulator JAX requests by default (§Perf H1)
    y = crossbar_linear(
        out, p["wo"], "wo",
        digital=lambda: jnp.einsum("bshk,hkd->bsd", out,
                                   p["wo"].astype(x.dtype),
                                   preferred_element_type=x.dtype))
    return lc(y, ("batch", "seq", "act_embed")), new_cache


def init_cache(cfg: AttnConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.kv_eff, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def cache_specs(cfg: AttnConfig):
    return {"k": ("batch", "kv_seq", "act_kv_heads", None),
            "v": ("batch", "kv_seq", "act_kv_heads", None),
            "len": ("batch",)}


def paged_init_cache(cfg: AttnConfig, batch: int, max_len: int,
                     n_pages: int, page_size: int, dtype=jnp.bfloat16):
    """Paged KV cache for one layer: a physical page pool plus per-row
    page tables.  Page 0 is the reserved null page (see serve/kv_pool.py);
    ``page_size`` must divide ``max_len`` so a full gather through the
    table is exactly ``max_len`` deep (the dense-path bit-exactness
    contract)."""
    if max_len % page_size:
        raise ValueError(f"page_size {page_size} must divide max_len "
                         f"{max_len}")
    shape = (n_pages + 1, page_size, cfg.kv_eff, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((batch,), jnp.int32),
            "pt": jnp.zeros((batch, max_len // page_size), jnp.int32)}


def paged_cache_specs(cfg: AttnConfig):
    # the page axis is unsharded: pages are lane-local working state
    return {"k": (None, None, "act_kv_heads", None),
            "v": (None, None, "act_kv_heads", None),
            "len": ("batch",), "pt": ("batch", None)}


@jax.jit
def paged_copy_page(layers, src, dst):
    """Duplicate physical page ``src`` into ``dst`` across every layer's
    K/V pool — the device half of copy-on-write (serve/kv_pool.py
    privatizes a shared page before a row's own tokens overwrite it).
    ``layers`` is the scheduler's stacked per-layer dict: ``k``/``v``
    are ``(n_layers, n_pages + 1, page_size, kv_eff, head_dim)``.
    ``src``/``dst`` are traced scalars, so ONE compiled copy serves
    every page pair (no per-page-id retrace); page tables and fill
    markers pass through untouched — the pool owns them."""
    out = dict(layers)
    for key in ("k", "v"):
        page = jax.lax.dynamic_index_in_dim(layers[key], src, axis=1,
                                            keepdims=True)
        out[key] = jax.lax.dynamic_update_slice_in_dim(
            layers[key], page, dst, axis=1)
    return out


# -- MLPs ----------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        p = {"wi": _normal(ks[0], (d_model, d_ff), d_model ** -0.5),
             "wg": _normal(ks[1], (d_model, d_ff), d_model ** -0.5),
             "wo": _normal(ks[2], (d_ff, d_model), d_ff ** -0.5)}
        s = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"),
             "wo": ("mlp", "embed")}
    else:
        p = {"wi": _normal(ks[0], (d_model, d_ff), d_model ** -0.5),
             "wo": _normal(ks[2], (d_ff, d_model), d_ff ** -0.5)}
        s = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    return p, s


@jax.named_scope("mlp")
def mlp(p, x, act: str):
    h = crossbar_linear(x, p["wi"], "wi",
                        digital=lambda: x @ p["wi"].astype(x.dtype))
    if act == "swiglu":
        g = crossbar_linear(x, p["wg"], "wg",
                            digital=lambda: x @ p["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    elif act == "relu2":                  # nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(h))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(act)
    h = lc(h, ("batch", None, "act_mlp"))

    def _wo_digital():
        from repro.distributed.sharding import tp_bf16_matmul
        y = tp_bf16_matmul(h, p["wo"].astype(x.dtype))  # opt-in (§Perf)
        if y is None:
            y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype),
                           preferred_element_type=x.dtype)
        return y

    y = crossbar_linear(h, p["wo"], "wo", digital=_wo_digital)
    return lc(y, ("batch", "seq", "act_embed"))


# -- Mixture of Experts ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    act: str = "swiglu"


def moe_init(key, d_model: int, d_ff: int, cfg: MoEConfig):
    ks = jax.random.split(key, 4)
    e = cfg.n_experts
    p = {"router": _normal(ks[0], (d_model, e), d_model ** -0.5),
         "wi": _normal(ks[1], (e, d_model, d_ff), d_model ** -0.5),
         "wo": _normal(ks[3], (e, d_ff, d_model), d_ff ** -0.5)}
    s = {"router": ("embed", None),
         "wi": ("experts", "embed", "expert_mlp"),
         "wo": ("experts", "expert_mlp", "embed")}
    if cfg.act == "swiglu":
        p["wg"] = _normal(ks[2], (e, d_model, d_ff), d_model ** -0.5)
        s["wg"] = ("experts", "embed", "expert_mlp")
    return p, s


def moe_block(p, x, cfg: MoEConfig):
    """Token-dropping top-k MoE with group-local sort-based dispatch.

    x: (B, S, d).  Groups are the (sharded) batch rows, so the argsort and
    scatter stay shard-local under pjit — no cross-device token exchange in
    the baseline layout (experts are TP-sharded on d_ff; see DESIGN.md for
    the all-to-all EP variant).  Capacity per group/expert:
      C = ceil(S * top_k * capacity_factor / n_experts).
    Tokens over capacity are dropped (standard dropping MoE); the residual
    stream carries them unchanged.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = max(1, int(-(-s * k * cfg.capacity_factor // e)))

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)                 # (B, S, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    # flatten assignments within each group
    ids = top_e.reshape(b, s * k)                          # (B, S*k)
    gts = top_g.reshape(b, s * k)
    order = jnp.argsort(ids, axis=-1)                      # group-local sort
    ids_s = jnp.take_along_axis(ids, order, axis=-1)
    gts_s = jnp.take_along_axis(gts, order, axis=-1)
    tok_s = order // k                                     # source token

    # position within expert via running count over the sorted list
    same = (ids_s[:, :, None] == jnp.arange(e)[None, None, :])
    pos_all = jnp.cumsum(same, axis=1) - 1                 # (B, S*k, E)
    pos = jnp.take_along_axis(pos_all, ids_s[:, :, None],
                              axis=-1)[..., 0]             # (B, S*k)
    keep = pos < c
    dest = ids_s * c + jnp.minimum(pos, c - 1)             # (B, S*k)

    xs = jnp.take_along_axis(x, tok_s[..., None], axis=1)  # (B, S*k, d)
    xs = jnp.where(keep[..., None], xs, 0.0)
    buf = jnp.zeros((b, e * c, d), x.dtype)
    buf = jax.vmap(lambda bf, dst, val: bf.at[dst].add(val))(buf, dest, xs)
    buf = buf.reshape(b, e, c, d)
    buf = lc(buf, ("batch", None, None, "act_embed"))

    h = jnp.einsum("becd,edf->becf", buf, p["wi"].astype(x.dtype))
    if cfg.act == "swiglu":
        g = jnp.einsum("becd,edf->becf", buf, p["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    h = lc(h, ("batch", None, None, "act_mlp"))
    out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(x.dtype),
                     preferred_element_type=x.dtype)  # bf16 TP all-reduce
    out = out.reshape(b, e * c, d)

    # gather back to sorted slots, weight by gates, unsort via scatter-add
    ys = jnp.take_along_axis(out, dest[..., None], axis=1)
    ys = ys * (gts_s * keep)[..., None].astype(x.dtype)
    y = jnp.zeros((b, s, d), x.dtype)
    y = jax.vmap(lambda acc, t, val: acc.at[t].add(val))(y, tok_s, ys)
    return lc(y, ("batch", "seq", "act_embed")), gates


def moe_aux_loss(gates: jax.Array, top_e: Optional[jax.Array] = None
                 ) -> jax.Array:
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    e = gates.shape[-1]
    p_e = gates.mean(axis=tuple(range(gates.ndim - 1)))
    hard = jax.nn.one_hot(jnp.argmax(gates, -1), e)
    f_e = hard.mean(axis=tuple(range(hard.ndim - 1)))
    return e * jnp.sum(f_e * p_e)
