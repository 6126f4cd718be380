"""Unified model builder: one ModelConfig + build_model() for all ten
assigned architectures (dense / MoE / VLM / enc-dec / RWKV6 / Zamba2).

``build_model(cfg)`` returns a ``Model`` with a functional API:
  init(key) -> params                      param_specs -> logical axes
  loss_fn(params, batch) -> (loss, metrics)             [train shapes]
  prefill(params, batch, cache) -> (logits, cache)      [prefill shapes]
  decode_step(params, tokens, cache) -> (logits, cache) [decode shapes]
  init_cache(batch, max_len) / cache_specs()
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import executor as xbar
from repro.core.engine import EngineConfig
from repro.core.executor import CrossbarExecutor
from repro.distributed.sharding import logical_constraint as lc
from repro.models import layers as L
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.layers import AttnConfig, MoEConfig


def _pad_vocab(v: int, mult: int = 256) -> int:
    return -(-v // mult) * mult


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | vlm | encdec | rwkv6 | zamba2
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv: int = 0
    d_ff: int = 0
    vocab: int = 32000
    head_dim: int = 128
    act: str = "swiglu"
    qk_norm: bool = False
    norm: str = "rms"
    rope_theta: float = 1e6
    kv_repeat: int = 1             # Megatron KV replication for TP > n_kv
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity: float = 1.25
    ssm_state: int = 64
    ssm_head_dim: int = 64
    attn_every: int = 6            # zamba2 shared-attention period
    q_chunk: int = 0               # chunked attention (0 = off)
    chunk_unroll: bool = True
    lin_chunk: int = 16            # GLA chunk for rwkv/mamba
    remat: str = "none"            # none | full | dots
    scan_layers: bool = True
    dtype: Any = jnp.bfloat16      # activation/compute dtype
    kv_dtype: Any = None           # KV-cache storage dtype (None = dtype);
    # fp8 halves decode's dominant memory term — the CrossStack low-bit-cell
    # argument applied to the cache (§Perf)
    tie_embeddings: bool = False
    paged_kernel: bool = False     # paged decode via the Pallas kernel
    paged_stream_pages: int = 0    # streamed-lane threshold in pages
    # (>= this many table pages -> online-softmax block streaming; 0 =
    # always the bitwise gather-scratch lane); see kernels/paged_attention
    paged_block_pages: int = 16    # pages per streamed block
    backend: str = "digital"       # "digital" | "crossbar" (weight-resident)
    xbar: EngineConfig = EngineConfig(mode="deepnet")  # crossbar-backend cfg

    @property
    def padded_vocab(self) -> int:
        return _pad_vocab(self.vocab)

    @property
    def attn(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            head_dim=self.head_dim, qk_norm=self.qk_norm,
            rope_theta=self.rope_theta, kv_repeat=self.kv_repeat,
            mrope=(self.family == "vlm"), q_chunk=self.q_chunk,
            chunk_unroll=self.chunk_unroll,
            paged_kernel=self.paged_kernel,
            paged_stream_pages=self.paged_stream_pages,
            paged_block_pages=self.paged_block_pages)

    @property
    def moe(self) -> Optional[MoEConfig]:
        if self.moe_experts == 0:
            return None
        return MoEConfig(self.moe_experts, self.moe_top_k,
                         self.moe_capacity, self.act)

    @property
    def block_cfg(self) -> T.BlockConfig:
        return T.BlockConfig(attn=self.attn, d_ff=self.d_ff, act=self.act,
                             moe=self.moe, norm=self.norm,
                             cross_attn=(self.family == "encdec"))

    @property
    def rwkv(self) -> R.RWKVConfig:
        return R.RWKVConfig(d_model=self.d_model, n_layers=self.n_layers,
                            head_dim=self.ssm_head_dim, vocab=self.vocab,
                            ffn_mult=self.d_ff / self.d_model,
                            chunk=self.lin_chunk,
                            chunk_unroll=self.chunk_unroll)

    @property
    def mamba(self) -> S.Mamba2Config:
        return S.Mamba2Config(d_model=self.d_model, d_state=self.ssm_state,
                              head_dim=self.ssm_head_dim,
                              chunk=self.lin_chunk,
                              chunk_unroll=self.chunk_unroll)

    # zamba2 layout: n_super super-blocks of (shared attn + attn_every
    # mamba layers) + trailing mamba layers
    @property
    def zamba_layout(self) -> Tuple[int, int]:
        n_super = self.n_layers // self.attn_every
        trailing = self.n_layers - n_super * self.attn_every
        return n_super, trailing


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    init: Any
    param_specs: Any
    loss_fn: Any
    prefill: Any
    decode_step: Any
    init_cache: Any
    cache_specs: Any
    executor: Optional[CrossbarExecutor] = None  # crossbar backend only
    # paged-KV serving (transformer decoder families; None elsewhere)
    init_paged_cache: Any = None
    paged_cache_specs: Any = None
    # copy-on-write page duplication for the prefix-sharing scheduler
    copy_paged_page: Any = None


# ---------------------------------------------------------------------------
# transformer families (dense / moe / vlm / encdec)
# ---------------------------------------------------------------------------

def _build_transformer(cfg: ModelConfig) -> Model:
    bc = cfg.block_cfg
    enc_bc = dataclasses.replace(
        bc, cross_attn=False,
        attn=dataclasses.replace(bc.attn, causal=False))
    pv = cfg.padded_vocab
    executor = (CrossbarExecutor(cfg.xbar) if cfg.backend == "crossbar"
                else None)
    # crossbar tiles are addressed by layer NAME, so the layer loop must be
    # unrolled (Python ints, not a scanned carry index)
    scan_layers = cfg.scan_layers and executor is None

    def init(key):
        ks = jax.random.split(key, 4)
        p: Dict[str, Any] = {}
        p["embed"], _ = T.embed_init(ks[0], pv, cfg.d_model)
        p["blocks"], _ = T.stack_init(ks[1], bc, cfg.n_layers)
        p["ln_f"], _ = L.rmsnorm_init(cfg.d_model)
        if not cfg.tie_embeddings:
            p["head"] = jax.random.normal(
                ks[2], (cfg.d_model, pv)) * cfg.d_model ** -0.5
        if cfg.family == "encdec":
            p["enc_blocks"], _ = T.stack_init(ks[3], enc_bc, cfg.n_layers)
            p["enc_ln_f"], _ = L.rmsnorm_init(cfg.d_model)
        return p

    def param_specs():
        p: Dict[str, Any] = {}
        p["embed"] = {"tok": ("vocab", "embed")}
        p["blocks"] = T.stack_specs(bc)
        p["ln_f"] = (None,)
        if not cfg.tie_embeddings:
            p["head"] = ("embed", "vocab")
        if cfg.family == "encdec":
            p["enc_blocks"] = T.stack_specs(enc_bc)
            p["enc_ln_f"] = (None,)
        return p

    def _positions(batch, sq, offset=None):
        if cfg.family == "vlm":
            return batch["positions_thw"]
        pos = jnp.arange(sq)[None]
        if offset is not None:
            pos = pos + offset[:, None]
        return jnp.broadcast_to(pos, (batch["tokens"].shape[0], sq))

    def _trunk(p, x, positions, caches=None, cross_kv=None, cross_len=None):
        with xbar.scope("blocks"):
            return T.stack_apply(p["blocks"], bc, x, positions,
                                 caches=caches, cross_kv=cross_kv,
                                 cross_len=cross_len, remat=cfg.remat,
                                 scan=scan_layers)

    def _encode(p, batch):
        enc = batch["enc_emb"].astype(cfg.dtype)
        pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                               enc.shape[:2])
        with xbar.scope("enc_blocks"):
            h, _, _ = T.stack_apply(p["enc_blocks"], enc_bc, enc, pos,
                                    remat=cfg.remat, scan=scan_layers)
        return L.rmsnorm(h, p["enc_ln_f"])

    def _cross_kv(p, enc_out):
        """Per-layer cross-attention K/V from encoder output (stacked)."""

        def one(pl):
            k = jnp.einsum("bsd,dhk->bshk", enc_out,
                           pl["xattn"]["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bsd,dhk->bshk", enc_out,
                           pl["xattn"]["wv"].astype(enc_out.dtype))
            return k, v

        return jax.lax.map(one, p["blocks"])

    def _logits(p, x):
        x = lc(x, ("batch", "seq_act", "act_embed"))  # SP gather point
        if not cfg.tie_embeddings:
            logits = xbar.crossbar_linear(
                x, p["head"], "head",
                digital=lambda: T.unembed(p["embed"], x, head=p["head"]))
            return lc(logits, ("batch", None, "vocab"))
        return T.unembed(p["embed"], x)

    def _embed_inputs(p, batch):
        x = T.embed(p["embed"], batch["tokens"]).astype(cfg.dtype)
        if cfg.family == "vlm" and "vis_emb" in batch:
            x = jnp.concatenate([batch["vis_emb"].astype(cfg.dtype), x],
                                axis=1)
        return x

    def loss_fn(params, batch):
        x = _embed_inputs(params, batch)
        sq = x.shape[1]
        cross_kv = cross_len = None
        if cfg.family == "encdec":
            enc_out = _encode(params, batch)
            cross_kv = _cross_kv(params, enc_out)
        pos = _positions(batch, sq)
        h, _, aux = _trunk(params, x, pos, cross_kv=cross_kv)
        h = L.rmsnorm(h, params["ln_f"])
        if cfg.family == "vlm" and "vis_emb" in batch:
            h = h[:, batch["vis_emb"].shape[1]:]
        logits = _logits(params, h)
        loss = T.xent_loss(logits, batch["labels"],
                           batch.get("loss_mask"), vocab=cfg.vocab)
        if cfg.moe is not None:
            loss = loss + 0.01 * aux / cfg.n_layers
        return loss, {"loss": loss, "aux": aux}

    def init_cache(batch: int, max_len: int, src_len: int = 0):
        one = L.init_cache(bc.attn, batch, max_len,
                           dtype=cfg.kv_dtype or cfg.dtype)
        caches = {k: jnp.zeros((cfg.n_layers,) + v.shape, v.dtype)
                  for k, v in one.items()}
        out = {"layers": caches}
        if cfg.family == "encdec" and src_len:
            kv = jnp.zeros((cfg.n_layers, batch, src_len,
                            bc.attn.kv_eff, bc.attn.head_dim), cfg.dtype)
            # dict, NOT tuple: a tuple-of-tuples would read as one spec
            # leaf in cache_specs and silently replicate 10s of GiB
            out["cross_kv"] = {"k": kv, "v": kv}
        return out

    def cache_specs():
        cs = L.cache_specs(bc.attn)
        out = {"layers": jax.tree.map(
            lambda names: ("layers",) + names, cs,
            is_leaf=lambda x: type(x) is tuple)}
        if cfg.family == "encdec":
            kv_spec = ("layers", "batch", "kv_seq", "act_kv_heads", None)
            out["cross_kv"] = {"k": kv_spec, "v": kv_spec}
        return out

    def init_paged_cache(batch: int, max_len: int, n_pages: int,
                         page_size: int):
        """Paged KV cache: physical page pools + per-row page tables,
        stacked across layers like ``init_cache``.  The table is
        replicated per layer so the one cache pytree flows through
        ``stack_apply`` (scan and unrolled) unchanged."""
        if cfg.family == "encdec":
            raise ValueError("paged KV serving targets decoder-only "
                             "families (no cross-attention cache)")
        one = L.paged_init_cache(bc.attn, batch, max_len, n_pages,
                                 page_size, dtype=cfg.kv_dtype or cfg.dtype)
        caches = {k: jnp.zeros((cfg.n_layers,) + v.shape, v.dtype)
                  for k, v in one.items()}
        return {"layers": caches}

    def paged_cache_specs():
        cs = L.paged_cache_specs(bc.attn)
        return {"layers": jax.tree.map(
            lambda names: ("layers",) + names, cs,
            is_leaf=lambda x: type(x) is tuple)}

    def copy_paged_page(cache, src, dst):
        """Copy-on-write plumbing: duplicate physical KV page ``src``
        into ``dst`` in every layer (page tables untouched — the pool
        retargets them host-side).  One jitted copy serves every page
        pair; see ``layers.paged_copy_page``."""
        return dict(cache, layers=L.paged_copy_page(
            cache["layers"], jnp.int32(src), jnp.int32(dst)))

    def prefill(params, batch, cache):
        """Prefill the KV cache with a full prompt; returns last logits.

        Uses the cache-aware attention path (dynamic_update_slice at
        position 0 + length-masked SDPA) so prefill and decode share one
        code path."""
        x = _embed_inputs(params, batch)
        sq = x.shape[1]
        pos = _positions(batch, sq)
        cross_kv = None
        if cfg.family == "encdec":
            enc_out = _encode(params, batch)
            cross_kv = _cross_kv(params, enc_out)
        h, new_layers, _ = _trunk(params, x, pos, caches=cache["layers"],
                                  cross_kv=cross_kv)
        with jax.named_scope("head"):
            h = L.rmsnorm(h, params["ln_f"])
            logits = _logits(params, h[:, -1:])
        cache = dict(cache, layers=new_layers)
        if cfg.family == "encdec":
            cache["cross_kv"] = {"k": cross_kv[0].astype(cfg.dtype),
                                 "v": cross_kv[1].astype(cfg.dtype)}
        return logits, cache

    def decode_step(params, tokens, cache, last=None):
        """Logits ``(B, S, V)`` for every fed position; with ``last``
        (``(B,)`` int positions) only row ``b``'s position ``last[b]``
        reaches the final norm and the head, and the logits are
        ``(B, 1, V)``."""
        x = T.embed(params["embed"], tokens).astype(cfg.dtype)
        offset = cache["layers"]["len"][0]
        sq = tokens.shape[1]
        if cfg.family == "vlm":
            pos1 = offset[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
            pos = jnp.broadcast_to(pos1[..., None], pos1.shape + (3,))
        else:
            pos = offset[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
        ckv = cache.get("cross_kv")
        cross_kv = (ckv["k"], ckv["v"]) if ckv is not None else None
        h, new_layers, _ = _trunk(params, x, pos, caches=cache["layers"],
                                  cross_kv=cross_kv)
        with jax.named_scope("head"):
            if last is not None:
                h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            h = L.rmsnorm(h, params["ln_f"])
            logits = _logits(params, h)
        return logits, dict(cache, layers=new_layers)

    def _on_crossbar(fn):
        """Inference entry points read the resident tiles.

        Programming happens on the first *eager* call (or explicitly via
        ``model.executor.program_params``); under jit the tiles must
        already be resident.  The training path (``loss_fn``) stays
        digital — program-at-load is a deployment-side contract.
        """
        if executor is None:
            return fn

        def wrapped(params, *args, **kwargs):
            executor.ensure_programmed(params)
            with executor.activate():
                return fn(params, *args, **kwargs)

        return wrapped

    return Model(cfg, init, param_specs, loss_fn, _on_crossbar(prefill),
                 _on_crossbar(decode_step), init_cache, cache_specs,
                 executor=executor, init_paged_cache=init_paged_cache,
                 paged_cache_specs=paged_cache_specs,
                 copy_paged_page=copy_paged_page)


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

def _build_rwkv(cfg: ModelConfig) -> Model:
    rc = cfg.rwkv
    pv = cfg.padded_vocab

    def init(key):
        ks = jax.random.split(key, 3)
        blocks = [R.block_init(k, rc)[0]
                  for k in jax.random.split(ks[1], cfg.n_layers)]
        return {
            "embed": {"tok": jax.random.normal(ks[0], (pv, cfg.d_model))
                      * 0.02},
            "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
            "ln_f": jnp.ones((cfg.d_model,)),
            "head": jax.random.normal(ks[2], (cfg.d_model, pv))
            * cfg.d_model ** -0.5,
        }

    def param_specs():
        bs = jax.tree.map(lambda n: ("layers",) + n, R.block_specs(rc),
                          is_leaf=lambda x: type(x) is tuple)
        return {"embed": {"tok": ("vocab", "embed")}, "blocks": bs,
                "ln_f": (None,), "head": ("embed", "vocab")}

    def _run(params, x, states, decode):
        if not cfg.scan_layers:   # unrolled (dry-run cost probes)
            one = T._remat(
                lambda p_l, xc, st_l: R.block(p_l, rc, xc, st_l,
                                              decode=decode), cfg.remat)
            outs = []
            for l in range(cfg.n_layers):
                p_l = jax.tree.map(lambda a: a[l], params["blocks"])
                st_l = jax.tree.map(lambda a: a[l], states)
                x, new_st = one(p_l, x, st_l)
                outs.append(new_st)
            return x, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        def body(carry, pl):
            xc = carry
            p_l, st_l = pl
            xo, new_st = R.block(p_l, rc, xc, st_l, decode=decode)
            return xo, new_st

        body = T._remat(body, cfg.remat)
        x, new_states = jax.lax.scan(body, x, (params["blocks"], states))
        return x, new_states

    def init_cache(batch: int, max_len: int = 0):
        one = R.init_state(rc, batch)
        return {"layers": jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)}

    def cache_specs():
        cs = R.state_specs(rc)
        return {"layers": jax.tree.map(
            lambda n: ("layers",) + n, cs,
            is_leaf=lambda x: type(x) is tuple)}

    def loss_fn(params, batch):
        x = T.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        states = init_cache(x.shape[0])["layers"]
        h, _ = _run(params, x, states, decode=False)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h, head=params["head"])
        loss = T.xent_loss(logits, batch["labels"],
                           batch.get("loss_mask"), vocab=cfg.vocab)
        return loss, {"loss": loss}

    def prefill(params, batch, cache):
        x = T.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        h, new_states = _run(params, x, cache["layers"], decode=False)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h[:, -1:], head=params["head"])
        return logits, {"layers": new_states}

    def decode_step(params, tokens, cache):
        x = T.embed(params["embed"], tokens).astype(cfg.dtype)
        h, new_states = _run(params, x, cache["layers"], decode=True)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h, head=params["head"])
        return logits, {"layers": new_states}

    return Model(cfg, init, param_specs, loss_fn, prefill, decode_step,
                 init_cache, cache_specs)


# ---------------------------------------------------------------------------
# Zamba2 hybrid
# ---------------------------------------------------------------------------

def _build_zamba(cfg: ModelConfig) -> Model:
    mc = cfg.mamba
    bc = dataclasses.replace(cfg.block_cfg, moe=None)
    n_super, trailing = cfg.zamba_layout
    pv = cfg.padded_vocab

    def init(key):
        ks = jax.random.split(key, 5)
        inner = []
        for k_s in jax.random.split(ks[1], n_super):
            blocks = [S.mamba2_block_init(k, mc)[0]
                      for k in jax.random.split(k_s, cfg.attn_every)]
            inner.append(jax.tree.map(lambda *xs: jnp.stack(xs), *blocks))
        tail = [S.mamba2_block_init(k, mc)[0]
                for k in jax.random.split(ks[2], max(trailing, 1))]
        return {
            "embed": {"tok": jax.random.normal(ks[0], (pv, cfg.d_model))
                      * 0.02},
            "shared_attn": T.block_init(ks[3], bc)[0],  # ONE shared block
            "supers": jax.tree.map(lambda *xs: jnp.stack(xs), *inner),
            "tail": jax.tree.map(lambda *xs: jnp.stack(xs), *tail),
            "ln_f": jnp.ones((cfg.d_model,)),
            "head": jax.random.normal(ks[4], (cfg.d_model, pv))
            * cfg.d_model ** -0.5,
        }

    def param_specs():
        ms = S.mamba2_block_specs(mc)
        as_ = T.block_specs(bc)
        pre2 = jax.tree.map(lambda n: ("layers", "layers") + n, ms,
                            is_leaf=lambda x: type(x) is tuple)
        pre1 = jax.tree.map(lambda n: ("layers",) + n, ms,
                            is_leaf=lambda x: type(x) is tuple)
        return {"embed": {"tok": ("vocab", "embed")},
                "shared_attn": as_, "supers": pre2, "tail": pre1,
                "ln_f": (None,), "head": ("embed", "vocab")}

    def init_cache(batch: int, max_len: int):
        attn_cache = L.init_cache(bc.attn, batch, max_len,
                                  dtype=cfg.kv_dtype or cfg.dtype)
        m_state = S.mamba2_state(mc, batch)
        return {
            "attn": jax.tree.map(
                lambda a: jnp.zeros((n_super,) + a.shape, a.dtype),
                attn_cache),
            "mamba": jax.tree.map(
                lambda a: jnp.zeros((n_super, cfg.attn_every) + a.shape,
                                    a.dtype), m_state),
            "tail": jax.tree.map(
                lambda a: jnp.zeros((max(trailing, 1),) + a.shape, a.dtype),
                m_state),
        }

    def cache_specs():
        acs = L.cache_specs(bc.attn)
        mcs = S.mamba2_state_specs(mc)
        return {
            "attn": jax.tree.map(lambda n: ("layers",) + n, acs,
                                 is_leaf=lambda x: type(x) is tuple),
            "mamba": jax.tree.map(lambda n: ("layers", "layers") + n, mcs,
                                  is_leaf=lambda x: type(x) is tuple),
            "tail": jax.tree.map(lambda n: ("layers",) + n, mcs,
                                 is_leaf=lambda x: type(x) is tuple),
        }

    def _run(params, x, cache, positions, decode):
        """Scan over super-blocks: shared attn (per-app cache) + mamba x6.

        Attention KV caches ride in the scan carry (in-place updates; at
        long_500k they are the dominant buffers); mamba states are small
        and flow as xs/ys."""

        def super_body(carry, per):
            xc, attn_caches, idx = carry
            p_super, m_states = per
            attn_cache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0,
                                                       keepdims=False),
                attn_caches)
            xo, new_attn, _ = T.block(params["shared_attn"], bc, xc,
                                      positions, cache=attn_cache)
            attn_caches = jax.tree.map(
                lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                    c, nc.astype(c.dtype), idx, 0), attn_caches, new_attn)

            def inner_body(c2, per2):
                p_m, st = per2
                y, new_st = S.mamba2_block(p_m, mc, c2, st, decode=decode)
                return y, new_st

            if not cfg.scan_layers:   # unroll inner loop too (cost probes)
                new_sts = []
                for m_i in range(cfg.attn_every):
                    per2 = jax.tree.map(lambda a: a[m_i],
                                        (p_super, m_states))
                    xo, new_st = inner_body(xo, per2)
                    new_sts.append(new_st)
                new_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_sts)
            else:
                xo, new_m = jax.lax.scan(inner_body, xo,
                                         (p_super, m_states))
            return (xo, attn_caches, idx + 1), new_m

        super_body = T._remat(super_body, cfg.remat)
        if not cfg.scan_layers:   # unrolled (dry-run cost probes)
            carry = (x, cache["attn"], jnp.int32(0))
            new_ms = []
            for s_i in range(n_super):
                per = jax.tree.map(lambda a: a[s_i],
                                   (params["supers"], cache["mamba"]))
                carry, nm = super_body(carry, per)
                new_ms.append(nm)
            x, new_attn, _ = carry
            new_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_ms)
        else:
            (x, new_attn, _), new_m = jax.lax.scan(
                super_body, (x, cache["attn"], jnp.int32(0)),
                (params["supers"], cache["mamba"]))

        def tail_body(c2, per2):
            p_m, st = per2
            y, new_st = S.mamba2_block(p_m, mc, c2, st, decode=decode)
            return y, new_st

        if trailing > 0:
            x, new_tail = jax.lax.scan(tail_body, x,
                                       (params["tail"], cache["tail"]))
        else:
            new_tail = cache["tail"]
        return x, {"attn": new_attn, "mamba": new_m, "tail": new_tail}

    def loss_fn(params, batch):
        x = T.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        b, sq = batch["tokens"].shape
        cache = init_cache(b, sq)
        pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
        h, _ = _run(params, x, cache, pos, decode=False)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h, head=params["head"])
        loss = T.xent_loss(logits, batch["labels"],
                           batch.get("loss_mask"), vocab=cfg.vocab)
        return loss, {"loss": loss}

    def prefill(params, batch, cache):
        x = T.embed(params["embed"], batch["tokens"]).astype(cfg.dtype)
        b, sq = batch["tokens"].shape
        pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
        h, new_cache = _run(params, x, cache, pos, decode=False)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h[:, -1:], head=params["head"])
        return logits, new_cache

    def decode_step(params, tokens, cache):
        x = T.embed(params["embed"], tokens).astype(cfg.dtype)
        offset = cache["attn"]["len"][0, 0]
        pos = jnp.broadcast_to(offset[None, None], tokens.shape)
        h, new_cache = _run(params, x, cache, pos, decode=True)
        h = L.rmsnorm(h, params["ln_f"])
        logits = T.unembed(params["embed"], h, head=params["head"])
        return logits, new_cache

    return Model(cfg, init, param_specs, loss_fn, prefill, decode_step,
                 init_cache, cache_specs)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.backend not in ("digital", "crossbar"):
        raise ValueError(f"unknown backend {cfg.backend!r}")
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        return _build_transformer(cfg)
    if cfg.backend == "crossbar":
        raise ValueError(
            f"backend='crossbar' supports transformer families only, "
            f"not {cfg.family!r}")
    if cfg.family == "rwkv6":
        return _build_rwkv(cfg)
    if cfg.family == "zamba2":
        return _build_zamba(cfg)
    raise ValueError(f"unknown family {cfg.family}")
