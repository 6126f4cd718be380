"""The comparison that decides ``correct``, and the arithmetic of the
plain reference's linear layers.

The forward pass is the configuration's family's (``spec.family``:
``logits_at``), written from the model's published description in
float32 with ``precision=HIGHEST``, over whole sequences with no cache
and no batching of rows at different depths, with every linear layer
through ``linear`` here.  It imports nothing of the program: it reads
the benchmark's own weights (``weights.published``) and computes its own
quantization where the configuration states one.

A configuration's arithmetic for its linear layers (``arith``):

* ``("float",)``: ``x @ W``.
* ``("int8",)`` and ``("fp8",)``: W8A8 with per-output-channel and
  per-row scales, in int8 or e4m3: the precisions one step below the
  digital configuration's bfloat16, its two controls.
* ``("xbar", rows_per_adc, w_bits, in_bits, adc_bits, bits_per_cell)``:
  the crossbar read as the configuration states it, written out plainly:
  per-column weight scales, ``w_bits`` magnitude bits split into
  differential cell slices of ``bits_per_cell`` bits, per-row input
  scales, two's-complement bit-serial pulses, one saturating
  ``adc_bits`` conversion per ``rows_per_adc`` rows per pulse per slice,
  then the signed shift-add.  The crossbar configuration's control reads
  it with 4 input bits (int4 for int8).

Two numbers are compared, both from the gap of a token at a position:
the amount by which its reference logit lies below the reference's best
logit there.  ``widest_gap`` is the largest gap of the sampled requests'
served tokens, ``mean_gap`` their mean.  A control is judged by the
same numbers, with the token its own logits put first at each position
of the same sequences in place of the served token.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spec

HIGHEST = jax.lax.Precision.HIGHEST


def arith_for(conf) -> Tuple:
    """The arithmetic the configuration states for its linears."""
    if conf["serving"]["backend"] != "crossbar":
        return ("float",)
    xb = conf["crossbar"]
    if xb["mode"] != "deepnet" or not xb["per_channel"]:
        raise ValueError("the reference reads deep-net, per-column "
                         "crossbars only")
    return ("xbar", xb["tile_rows"], xb["w_bits"], xb["in_bits"],
            xb["adc_bits"], xb["bits_per_cell"])


def controls_for(conf) -> Dict[str, Tuple]:
    """The precisions one step below the configuration's: each is a
    control, and each has to come out not correct."""
    a = arith_for(conf)
    if a[0] == "float":
        return {"int8": ("int8",), "fp8": ("fp8",)}
    return {"in_bits4": a[:3] + (4,) + a[4:]}


# -- linears -------------------------------------------------------------------

def _lin_float(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def _lin_int8(x, w):
    ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / 127.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     1e-8) / 127.0
    wq = jnp.clip(jnp.round(w / ws), -127, 127).astype(jnp.bfloat16)
    xq = jnp.clip(jnp.round(x / xs), -127, 127).astype(jnp.bfloat16)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    return acc * xs * ws


def _lin_fp8(x, w):
    ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / 448.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     1e-8) / 448.0
    wq = (w / ws).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    xq = (x / xs).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    return acc * xs * ws


def _lin_xbar(x, w, arith):
    _, rows, w_bits, in_bits, adc_bits, bpc = arith
    k, n = w.shape
    base = 2 ** bpc
    n_sl = -(-w_bits // bpc)
    qmax = 2.0 ** w_bits - 1.0
    ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qmax
    wi = jnp.clip(jnp.round(w / ws), -qmax, qmax)
    t = -(-k // rows)
    wi = jnp.pad(wi, ((0, t * rows - k), (0, 0)))
    xmax = 2.0 ** (in_bits - 1) - 1.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                     1e-8) / xmax
    xi = jnp.clip(jnp.round(x / xs), -xmax - 1, xmax).astype(jnp.int32)
    u = jnp.pad(xi % (1 << in_bits), ((0, 0), (0, t * rows - k)))
    m = x.shape[0]
    blk = min(max(8, 2 ** int(math.log2(max(1, 2 ** 27 // (t * n * 4))))),
              -(-m // 8) * 8)
    mp = -(-m // blk) * blk
    u = jnp.pad(u, ((0, mp - m), (0, 0))).reshape(mp // blk, blk, t, rows)
    levels = 2.0 ** adc_bits - 1.0
    lsb = rows * (base - 1) / levels
    pulse_w = jnp.asarray([2.0 ** a for a in range(in_bits - 1)]
                          + [-(2.0 ** (in_bits - 1))], jnp.float32)

    def adc(acc):
        return jnp.clip(jnp.round(acc / lsb), 0.0, levels) * lsb

    def one_slice(y, s):
        div = jnp.float32(base) ** s
        pos = (jnp.floor(jnp.maximum(wi, 0.0) / div) % base)
        neg = (jnp.floor(jnp.maximum(-wi, 0.0) / div) % base)
        pos = pos.astype(jnp.bfloat16).reshape(t, rows, n)
        neg = neg.astype(jnp.bfloat16).reshape(t, rows, n)

        def block(ub):
            def pulse(acc, a):
                bits = ((ub >> a) & 1).astype(jnp.bfloat16)
                ap = jnp.einsum("btr,trn->btn", bits, pos,
                                preferred_element_type=jnp.float32)
                an = jnp.einsum("btr,trn->btn", bits, neg,
                                preferred_element_type=jnp.float32)
                return acc + pulse_w[a] * (adc(ap) - adc(an)).sum(1), None
            acc, _ = jax.lax.scan(pulse, jnp.zeros((blk, n), jnp.float32),
                                  jnp.arange(in_bits))
            return acc

        part = jax.lax.map(block, u).reshape(mp, n)[:m]
        return y + div * part, None

    y, _ = jax.lax.scan(one_slice, jnp.zeros((m, n), jnp.float32),
                        jnp.arange(n_sl))
    return y * xs * ws


def linear(x, w, arith):
    """``x (..., K) @ w (K, N)`` in the arithmetic ``arith``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if arith[0] == "float":
        y = _lin_float(x2, w)
    elif arith[0] == "int8":
        y = _lin_int8(x2, w)
    elif arith[0] == "fp8":
        y = _lin_fp8(x2, w)
    else:
        y = _lin_xbar(x2, w, arith)
    return y.reshape(*lead, w.shape[1])


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of
    ``tokens`` (inf for an id outside the vocabulary)."""
    best = ref.max(-1)
    ok = (tokens >= 0) & (tokens < ref.shape[-1])
    got = ref[np.arange(len(tokens)), np.where(ok, tokens, 0)]
    return np.where(ok, best - got, np.inf)


def sample(finished, seed: int, min_tokens: int = 1024, min_reqs: int = 4,
           max_reqs: int = 24) -> List:
    """Finished requests to compare, drawn from ``seed``: the longest,
    then others in a seeded order until ``min_tokens`` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 3])
    by_len = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.out)))
    rest = [by_len[i] for i in rng.permutation(len(by_len) - 1) + 1]
    picked, n = [by_len[0]], len(by_len[0].out)
    for r in rest:
        if len(picked) >= max_reqs or (n >= min_tokens
                                       and len(picked) >= min_reqs):
            break
        picked.append(r)
        n += len(r.out)
    return picked


def numbers(ref: np.ndarray, tokens: np.ndarray) -> Dict[str, float]:
    """The numbers compared, for ``tokens`` at the reference's positions."""
    g = gaps(ref, tokens)
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean())}


def compare(params, conf, reqs, seq_len: int,
            controls: Sequence[str] = ()) -> Dict[str, Dict[str, float]]:
    """The numbers compared for the served tokens of ``reqs``
    (``Request``-like: ``prompt``, ``out``) under ``"served"``, and for
    each control named under its name."""
    seqs, want, served = [], [], []
    for r in reqs:
        p = np.asarray(r.prompt, np.int32)
        o = np.asarray(r.out, np.int32)
        seqs.append(np.concatenate([p, o[:-1]]))
        want.append(np.arange(len(p) - 1, len(p) + len(o) - 1))
        served.append(o)
    served = np.concatenate(served)
    logits_at = spec.family(conf).logits_at
    ref = logits_at(params, conf, seqs, want, seq_len, arith_for(conf))
    out = {"served": numbers(ref, served)}
    arith = controls_for(conf)
    for name in controls:
        ctl = logits_at(params, conf, seqs, want, seq_len, arith[name])
        out[name] = numbers(ref, ctl.argmax(-1))
    return out
