"""Paged continuous-batching scheduler: end-to-end acceptance tests.

The invariants this file pins are the PR's exit criteria:

  * paged decode is bit-exact with the dense-cache path at the
    scheduler level (same token streams, same request mix),
  * a ragged stream of random-length prompts admitted continuously is
    token-bit-exact vs the unpadded per-request greedy reference,
  * admissions NEVER stall an in-flight decode step,
  * one compiled closure per tenant with a zero retrace delta across a
    prompt-length mix spanning >= 4 of the old padded buckets,
  * page-pool backpressure queues (never drops) and conserves pages,
  * low-precision KV caches (bf16 / fp8) keep the logits within one
    storage-format ulp of the fp32 cache on BOTH the dense and paged
    paths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_config
from repro.models.model import build_model
from repro.serve.engine import BatchScheduler, Request, greedy_generate

# hypothesis drives the ragged-admission property when available; the
# parametrized fallback below keeps the property pinned without it
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _model(**overrides):
    cfg = get_config("qwen3_4b", smoke=True)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _prompts(cfg, plens, seed0=100):
    out = []
    for i, plen in enumerate(plens):
        k = jax.random.PRNGKey(seed0 + i)
        out.append(jax.random.randint(k, (plen,), 0,
                                      cfg.vocab - 1).astype(jnp.int32))
    return out


def _serve(sched, prompts, max_new, trickle=0):
    """Run a prompt list through a scheduler; ``trickle`` submits one
    request every N steps instead of all up front (continuous
    admission).  Returns {rid: out}."""
    pending = list(enumerate(prompts))
    if not trickle:
        for rid, p in pending:
            sched.submit(Request(rid=rid, prompt=p, max_new=max_new))
        pending = []
    done, steps = {}, 0
    while (len(done) < len(prompts)) and steps < 500:
        if pending and steps % max(trickle, 1) == 0:
            rid, p = pending.pop(0)
            sched.submit(Request(rid=rid, prompt=p, max_new=max_new))
        for r in sched.step():
            done[r.rid] = r.out
        steps += 1
    assert len(done) == len(prompts), f"stalled at {len(done)}"
    return done


# -- paged vs dense bit-exactness ---------------------------------------------

def test_paged_decode_bit_exact_with_dense_path_end_to_end():
    """The scheduler-level half of the acceptance gate (the kernel-level
    half lives in test_paged_attention.py): identical token streams
    from the paged pool and the dense per-slot cache."""
    cfg, m, params = _model()
    plens = (1, 3, 5, 9, 14, 23, 2, 30)
    prompts = _prompts(cfg, plens)
    streams = {}
    for kv in ("paged", "dense"):
        sched = BatchScheduler(m, params, n_slots=3, max_len=32, kv=kv,
                               page_size=8)
        streams[kv] = _serve(sched, prompts, max_new=5)
    assert streams["paged"] == streams["dense"]


def test_paged_kernel_serving_matches_gather_path():
    """cfg.paged_kernel=True routes lane attention through the Pallas
    kernel (interpret mode on CPU) — streams must not change."""
    cfg, m, params = _model()
    prompts = _prompts(cfg, (4, 11, 7))
    base = _serve(BatchScheduler(m, params, n_slots=3, max_len=32),
                  prompts, max_new=4)
    cfg_k, mk, params_k = _model(paged_kernel=True)
    kern = _serve(BatchScheduler(mk, params_k, n_slots=3, max_len=32),
                  prompts, max_new=4)
    assert base == kern


# -- ragged continuous admission vs unpadded reference ------------------------

def _assert_ragged_stream_exact(plens, max_new, trickle):
    obs.reset()
    cfg, m, params = _model()
    prompts = _prompts(cfg, plens, seed0=300)
    refs = {i: [int(t) for t in greedy_generate(
        m, params, {"tokens": p[None]}, max_new=max_new, max_len=64)[0]]
        for i, p in enumerate(prompts)}
    sched = BatchScheduler(m, params, n_slots=3, max_len=64)
    done = _serve(sched, prompts, max_new, trickle=trickle)
    assert done == refs
    reg = obs.registry()
    assert reg.total("serve_jit_traces_total",
                     closure="decode", tenant="A") == 1
    assert reg.total("serve_jit_retraces_total") == 0


# the fallback sweep: fixed draws from the same distribution the
# hypothesis path samples (prompt lengths spanning >= 4 of the old
# padded buckets: 8, 16, 32, 64)
@pytest.mark.parametrize("plens,max_new,trickle", [
    ((5, 13, 27, 50, 2), 4, 0),
    ((1, 8, 9, 33, 17, 60), 3, 2),
    ((62, 3, 31, 15, 7), 2, 1),
])
def test_ragged_admission_bit_exact_vs_unpadded_reference(
        plens, max_new, trickle):
    """Random-length prompts admitted continuously produce streams
    token-bit-exact vs the unpadded greedy reference, through ONE
    compiled closure with a zero retrace delta after warmup."""
    _assert_ragged_stream_exact(plens, max_new, trickle)


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=8, deadline=None)
    @given(plens=st.lists(st.integers(min_value=1, max_value=63),
                          min_size=1, max_size=6),
           max_new=st.integers(min_value=1, max_value=4),
           trickle=st.integers(min_value=0, max_value=3))
    def test_ragged_admission_property(plens, max_new, trickle):
        _assert_ragged_stream_exact(tuple(plens), max_new, trickle)


# -- admissions never stall decode --------------------------------------------

def test_admission_never_stalls_in_flight_decode():
    """While a long prompt chunk-prefills, an already-decoding request
    must emit exactly one token on EVERY step — no admission pause, no
    skipped decode step (the old bucket prefill ran a separate batched
    call that stalled the decode batch)."""
    cfg, m, params = _model()
    short, long_ = _prompts(cfg, (4, 60), seed0=400)
    sched = BatchScheduler(m, params, n_slots=2, max_len=64, chunk=4)
    sched.submit(Request(rid=0, prompt=short, max_new=30))
    sched.step()                          # rid 0 emits its first token
    req0 = sched._lanes["A"].slots[0]
    assert req0 is not None and len(req0.out) == 1
    sched.submit(Request(rid=1, prompt=long_, max_new=2))
    # rid 1 needs ceil(60/4) = 15 steps of chunked prefill; rid 0 must
    # gain exactly one token on every single one of them
    for step in range(15):
        before = len(req0.out)
        sched.step()
        assert len(req0.out) == before + 1, f"decode stalled at {step}"
    # and rid 1's stream is still the unpadded reference
    ref = [int(t) for t in greedy_generate(
        m, params, {"tokens": long_[None]}, max_new=2, max_len=64)[0]]
    done = {}
    steps = 0
    while 1 not in done and steps < 50:
        for r in sched.step():
            done[r.rid] = r.out
        steps += 1
    assert done[1] == ref


# -- page-pool backpressure ---------------------------------------------------

def test_page_backpressure_queues_without_dropping():
    """A pool too small for the whole queue admits what fits, holds the
    rest in FIFO, and completes everything — zero drops, conservation
    intact at every step."""
    cfg, m, params = _model()
    prompts = _prompts(cfg, (20, 20, 20, 20), seed0=500)
    # 4 slots but only enough pages for ~2 resident full-lifetime seqs
    sched = BatchScheduler(m, params, n_slots=4, max_len=32, page_size=8,
                           kv_pages=8)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p, max_new=10))
    pool = sched._lanes["A"].pool
    done, steps = [], 0
    max_resident = 0
    while len(done) < 4 and steps < 300:
        done += sched.step()
        assert pool.conservation_ok()
        max_resident = max(max_resident,
                           sum(s is not None
                               for s in sched._lanes["A"].slots))
        steps += 1
    assert len(done) == 4                  # nothing dropped
    assert max_resident == 2               # the budget really gated
    assert pool.pages_in_use == 0          # all reclaimed
    # streams unaffected by having waited
    refs = {i: [int(t) for t in greedy_generate(
        m, params, {"tokens": p[None]}, max_new=10, max_len=32)[0]]
        for i, p in enumerate(prompts)}
    assert {r.rid: r.out for r in done} == refs


def test_prompt_longer_than_max_len_still_rejected():
    cfg, m, params = _model()
    sched = BatchScheduler(m, params, n_slots=2, max_len=16, page_size=8)
    p = _prompts(cfg, (17,))[0]
    sched.submit(Request(rid=0, prompt=p, max_new=2))
    with pytest.raises(ValueError, match="exceeds"):
        sched.step()


# -- KV cache dtypes (dense AND paged) ----------------------------------------

_KV_DTYPES = [jnp.bfloat16]
if hasattr(jnp, "float8_e4m3fn"):
    _KV_DTYPES.append(jnp.float8_e4m3fn)


@pytest.mark.parametrize("kv", ["dense", "paged"])
@pytest.mark.parametrize("kv_dtype", _KV_DTYPES,
                         ids=lambda d: jnp.dtype(d).name)
def test_low_precision_kv_cache_decode_parity(kv, kv_dtype):
    """bf16/fp8 cache storage (the in-dot upcast branch in
    models/layers._sdpa) must track the fp32 cache's LOGITS on both
    storage layouts, teacher-forced on the same tokens so sampling
    cannot compound a near-tie into a different stream.

    Tolerance from the storage format: rounding to ``nmant`` mantissa
    bits moves each cached K and V element by at most half a unit in
    the last place, 2**-(nmant + 1) relative.  To first order the K
    error perturbs the attention logits and the V error the mixed
    values (the softmax weights sum to one), so the two add up to one
    ulp, 2**-nmant, of the logit scale: 2**-3 for e4m3's 3-bit
    mantissa, 2**-7 for bf16."""
    tokens = jnp.stack(_prompts(get_config("qwen3_4b", smoke=True),
                                (24, 24, 24), seed0=600))
    ref = _teacher_forced_logits(jnp.float32, kv, tokens)
    low = _teacher_forced_logits(kv_dtype, kv, tokens)
    tol = 2.0 ** -jnp.finfo(kv_dtype).nmant * float(jnp.abs(ref).max())
    err = float(jnp.abs(low - ref).max())
    assert err <= tol, (f"{jnp.dtype(kv_dtype).name} cache: max logit "
                        f"error {err:.4f} > {tol:.4f}")


def _teacher_forced_logits(kv_dtype, kv, tokens, chunk=8, max_len=32,
                           page_size=8):
    """Logits of every position of ``tokens`` (B, S), fed ``chunk``
    tokens per decode call through a dense or paged cache of
    ``kv_dtype`` (each row owns its own pages on the paged layout)."""
    cfg, m, params = _model(kv_dtype=kv_dtype)
    b, s = tokens.shape
    if kv == "dense":
        cache = m.init_cache(b, max_len)
    else:
        pps = max_len // page_size
        cache = m.init_paged_cache(b, max_len, b * pps, page_size)
        pt = 1 + jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
        layers = dict(cache["layers"])
        layers["pt"] = jnp.broadcast_to(pt, layers["pt"].shape)
        cache = dict(cache, layers=layers)
    out = []
    for i in range(0, s, chunk):
        logits, cache = m.decode_step(params, tokens[:, i:i + chunk], cache)
        out.append(logits.astype(jnp.float32))
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("kv_dtype", _KV_DTYPES + [jnp.float32],
                         ids=lambda d: jnp.dtype(d).name)
def test_cache_dtype_streams_identical_across_storage_layouts(kv_dtype):
    """At EQUAL cache dtype the paged pool and the dense cache hold the
    same numbers, so the streams must be bit-identical — including the
    fp8 upcast branch, which was previously untested."""
    cfg, m, params = _model(kv_dtype=kv_dtype)
    prompts = _prompts(cfg, (6, 13, 25), seed0=600)
    dense = _serve(BatchScheduler(m, params, n_slots=3, max_len=32,
                                  kv="dense"), prompts, max_new=8)
    paged = _serve(BatchScheduler(m, params, n_slots=3, max_len=32,
                                  kv="paged"), prompts, max_new=8)
    assert dense == paged


# -- constructor validation ---------------------------------------------------

def test_constructor_validation():
    cfg, m, params = _model()
    with pytest.raises(ValueError, match="kv must be"):
        BatchScheduler(m, params, n_slots=2, max_len=32, kv="sparse")
    with pytest.raises(ValueError, match="divide"):
        BatchScheduler(m, params, n_slots=2, max_len=30, page_size=8)
    with pytest.raises(ValueError, match="chunk"):
        BatchScheduler(m, params, n_slots=2, max_len=32, chunk=0)
