"""Seeded weights for a configuration, made on the device in one call.

The benchmark makes the weights, not the program, so that the plain
reference can read the same numbers without taking anything the program
made.  The tree has the layout of the program's ``model.init`` (the
system under test takes it as its parameters), in the published
arithmetic: 8 KV heads drawn and repeated ``kv_repeat`` times where the
program stores each head more than once.  Every value is a bf16 number
stored in float32 (the program's parameter type), so the serving step's
bf16 cast loses nothing.  The vocabulary's padding rows and columns are
zero.  The embedding has the linears' scale, ``hidden ** -0.5``, so a
tied head's logits have unit scale, as an untied head's do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32
    bits included), so two seeds never share a key."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


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


def make_params(cfg, seed: int):
    """Parameters of ``cfg`` (a ``ModelConfig``) drawn from ``seed``."""
    shape = (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv, cfg.head_dim,
             cfg.kv_repeat, cfg.vocab, cfg.padded_vocab, cfg.n_layers,
             cfg.tie_embeddings)
    return _make(key_for(seed), shape)


def published(params, cfg):
    """The published view of ``params``: one copy of each KV head."""
    attn = dict(params["blocks"]["attn"])
    for name in ("wk", "wv"):
        attn[name] = attn[name][:, :, ::cfg.kv_repeat]
    blocks = dict(params["blocks"], attn=attn)
    return dict(params, blocks=blocks)
