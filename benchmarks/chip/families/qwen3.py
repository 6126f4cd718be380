"""Qwen3 (dense decoder: RMSNorm, per-head q/k RMSNorm, split-half RoPE,
grouped-query causal attention, SwiGLU MLP, a tied or untied head).

The program stores each of the published KV heads ``kv_repeat`` times
(``serving.kv_repeat``); the weights draw the published heads and repeat
them, so the arithmetic is the published GQA, and ``published`` takes
one copy of each back for the reference.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spec
from reference import HIGHEST, linear
from weights import key_for

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=384,
            num_hidden_layers=2)

KV_BYTES = 2        # bf16 K/V, as the configuration serves them
WEIGHT_BYTES = 2    # bf16: the least a digital step can read per weight


# -- the program's configuration -----------------------------------------------

def program_config(conf):
    """The program's ``ModelConfig`` for a Qwen3 configuration file."""
    from repro.models.model import ModelConfig

    if conf["model_type"] != "qwen3" or conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only qwen3 with silu is wired")
    if conf["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-6")
    serving = conf["serving"]
    kw = dict(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        act="swiglu", qk_norm=True, rope_theta=float(conf["rope_theta"]),
        kv_repeat=serving["kv_repeat"],
        tie_embeddings=conf["tie_word_embeddings"],
        backend=serving["backend"])
    if serving["backend"] == "crossbar":
        kw["xbar"] = spec.crossbar_engine(conf)
    return ModelConfig(**kw)


# -- weights -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, shape):
    d, ff, h, kv, hd, rep, vocab, pv, n_layers, tied = shape
    ks = iter(jax.random.split(key, 16))

    def normal(shp, std):
        w = jax.random.normal(next(ks), shp, jnp.float32) * std
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    def gain(shp):
        return normal(shp, 0.1) + 1.0

    lyr = (n_layers,)
    wk, wv = (jnp.repeat(normal(lyr + (d, kv, hd), d ** -0.5), rep, axis=2)
              for _ in range(2))
    keep = (jnp.arange(pv) < vocab).astype(jnp.float32)
    p = {
        "embed": {"tok": normal((pv, d), d ** -0.5) * keep[:, None]},
        "blocks": {
            "ln1": gain(lyr + (d,)),
            "attn": {"wq": normal(lyr + (d, h, hd), d ** -0.5),
                     "wk": wk, "wv": wv,
                     "wo": normal(lyr + (h, hd, d), (h * hd) ** -0.5),
                     "q_norm": gain(lyr + (hd,)),
                     "k_norm": gain(lyr + (hd,))},
            "ln2": gain(lyr + (d,)),
            "mlp": {"wi": normal(lyr + (d, ff), d ** -0.5),
                    "wg": normal(lyr + (d, ff), d ** -0.5),
                    "wo": normal(lyr + (ff, d), ff ** -0.5)},
        },
        "ln_f": gain((d,)),
    }
    if not tied:
        p["head"] = normal((d, pv), d ** -0.5) * keep[None, :]
    return p


def make_params(conf, seed: int):
    """The program's parameters for ``conf``, drawn from ``seed``."""
    cfg = program_config(conf)      # the program's padded vocabulary
    shape = (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv, cfg.head_dim,
             cfg.kv_repeat, cfg.vocab, cfg.padded_vocab, cfg.n_layers,
             cfg.tie_embeddings)
    return _make(key_for(seed), shape)


def published(params, conf):
    """The published view of ``params``: one copy of each KV head."""
    rep = conf["serving"]["kv_repeat"]
    attn = dict(params["blocks"]["attn"])
    for name in ("wk", "wv"):
        attn[name] = attn[name][:, :, ::rep]
    blocks = dict(params["blocks"], attn=attn)
    return dict(params, blocks=blocks)


# -- the plain forward pass ----------------------------------------------------

def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Split-half rotary embedding of x (B, S, H, D) at positions (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "arith"))
def block(x, lp, dims, arith):
    """One decoder layer over whole sequences x (B, S, d)."""
    h, kv, hd, eps, theta = dims
    b, s, d = x.shape
    pos = jnp.arange(s)
    a = lp["attn"]
    y = rmsnorm(x, lp["ln1"], eps)
    q = linear(y, a["wq"].reshape(d, h * hd), arith).reshape(b, s, h, hd)
    k = linear(y, a["wk"].reshape(d, kv * hd), arith).reshape(b, s, kv, hd)
    v = linear(y, a["wv"].reshape(d, kv * hd), arith).reshape(b, s, kv, hd)
    q = rope(rmsnorm(q, a["q_norm"], eps), pos, theta)
    k = rope(rmsnorm(k, a["k_norm"], eps), pos, theta)
    qg = q.reshape(b, s, kv, h // kv, hd)
    sc = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                    precision=HIGHEST) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v, precision=HIGHEST)
    x = x + linear(o.reshape(b, s, h * hd), a["wo"].reshape(h * hd, d),
                   arith)
    m = lp["mlp"]
    y = rmsnorm(x, lp["ln2"], eps)
    g = linear(y, m["wg"], arith)
    u = linear(y, m["wi"], arith)
    return x + linear(jax.nn.silu(g) * u, m["wo"], arith)


@functools.partial(jax.jit, static_argnames=("eps", "arith"))
def head(x, ln_f, w, eps, arith):
    return linear(rmsnorm(x, ln_f, eps), w, arith)


def logits_at(params, conf, seqs: Sequence[np.ndarray],
              want: Sequence[np.ndarray], seq_len: int, arith,
              group: int = 4, pad: int = 128,
              head_rows: int = 256) -> np.ndarray:
    """Reference logits (len(concat(want)), vocab) at positions ``want[i]``
    of sequences ``seqs[i]``.  Sequences go through the layers ``group``
    at a time, longest first, each group right-padded to its longest
    rounded up to ``pad`` (at most ``seq_len``); the head reads the
    wanted rows ``head_rows`` at a time.  So few shapes compile, and
    padding is never attended to (the mask is causal)."""
    dims = (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]))
    vocab = conf["vocab_size"]
    blocks = params["blocks"]
    w_head = (params["head"][:, :vocab] if "head" in params
              else params["embed"]["tok"][:vocab].T)
    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i]))
    per_seq: Dict[int, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        for g0 in range(0, len(order), group):
            chunk = order[g0:g0 + group]
            length = min(seq_len, -(-len(seqs[chunk[0]]) // pad) * pad)
            toks = np.zeros((group, length), np.int32)
            for r, i in enumerate(chunk):
                toks[r, :len(seqs[i])] = seqs[i]
            x = params["embed"]["tok"][jnp.asarray(toks)]
            for layer in range(conf["num_hidden_layers"]):
                lp = jax.tree.map(lambda a, l=layer: a[l], blocks)
                x = block(x, lp, dims, arith)
            idx = np.concatenate([r * length + np.asarray(want[i])
                                  for r, i in enumerate(chunk)])
            n = len(idx)
            idx = np.pad(idx, (0, -n % head_rows))
            rows = x.reshape(group * length, -1)[jnp.asarray(idx)]
            logits = np.concatenate([
                np.asarray(head(rows[r0:r0 + head_rows], params["ln_f"],
                                w_head, dims[3], arith))
                for r0 in range(0, len(idx), head_rows)])[:n]
            for i in chunk:
                per_seq[i], logits = (logits[:len(want[i])],
                                      logits[len(want[i]):])
    return np.concatenate([per_seq[i] for i in range(len(seqs))])


# -- work ----------------------------------------------------------------------

def linears(conf) -> Tuple[List[Tuple[str, int, int]], Tuple[int, int]]:
    """(name, K, N) of one layer's linears at published widths, and the
    head's (K, N)."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
             ("wi", d, ff), ("wg", d, ff), ("wo_mlp", ff, d)]
    return layer, (d, conf["vocab_size"])


def trunk_weights(conf) -> int:
    """Weights of every layer's linears: the layer's list, once per
    layer."""
    layer, _ = linears(conf)
    return conf["num_hidden_layers"] * sum(k * n for _, k, n in layer)


def head_weights(conf) -> int:
    _, (k, n) = linears(conf)
    return k * n


def step_flops(conf, positions: int, context: int, tokens: int) -> float:
    """Model FLOPs of one step: 2 per weight per useful position (head:
    per emitted token) plus QK^T and PV over each position's keys."""
    attn = 4 * conf["num_attention_heads"] * conf["head_dim"] * context
    return (2.0 * trunk_weights(conf) * positions
            + 2.0 * head_weights(conf) * tokens
            + conf["num_hidden_layers"] * attn)


def step_bytes(conf, positions: int, context: int) -> float:
    """Least HBM bytes of one step: every weight once at bf16, the KV of
    every attended key read and of every useful position written."""
    kv = (2 * conf["num_key_value_heads"] * conf["head_dim"] * KV_BYTES
          * conf["num_hidden_layers"])
    return (WEIGHT_BYTES * (trunk_weights(conf) + head_weights(conf))
            + kv * (context + positions))
