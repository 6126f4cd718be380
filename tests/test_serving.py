"""Serving runtime tests: generation loop + continuous-batching scheduler."""
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.engine import EngineConfig
from repro.core.quant import QuantConfig
from repro.models.model import ModelConfig, build_model
from repro.serve.engine import (BatchScheduler, Request, greedy_generate,
                                make_decode_step, make_prefill_step,
                                make_window_step)


def _model():
    cfg = get_config("qwen3_4b", smoke=True)
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def test_greedy_generate_shapes_and_determinism():
    cfg, m, params = _model()
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab - 1).astype(jnp.int32)
    out1 = greedy_generate(m, params, {"tokens": prompts}, max_new=6)
    out2 = greedy_generate(m, params, {"tokens": prompts}, max_new=6)
    assert out1.shape == (2, 6)
    assert jnp.array_equal(out1, out2)
    assert int(out1.max()) < cfg.padded_vocab


def test_prefill_then_decode_continues_greedy_path():
    cfg, m, params = _model()
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab - 1).astype(jnp.int32)
    cache = m.init_cache(2, 32)
    prefill = make_prefill_step(m)
    decode = make_decode_step(m)
    tok, cache = prefill(params, {"tokens": prompts}, cache)
    toks = [tok]
    for _ in range(3):
        tok, cache = decode(params, tok, cache)
        toks.append(tok)
    gen = jnp.concatenate(toks, axis=1)
    ref = greedy_generate(m, params, {"tokens": prompts}, max_new=4,
                          max_len=32)
    assert jnp.array_equal(gen, ref)


def test_scheduler_completes_all_requests():
    cfg, m, params = _model()
    sched = BatchScheduler(m, params, n_slots=2, max_len=32)
    for rid in range(4):
        p = jax.random.randint(jax.random.PRNGKey(rid), (6,), 0,
                               cfg.vocab - 1).astype(jnp.int32)
        sched.submit(Request(rid=rid, prompt=p, max_new=5))
    done, steps = [], 0
    while len(done) < 4 and steps < 200:
        done += sched.step()
        steps += 1
    assert len(done) == 4
    assert all(len(r.out) >= 5 for r in done)


def test_scheduler_matches_unbatched_decode():
    """A request served through slot admission must produce the same
    tokens as a dedicated batch-of-1 generation."""
    cfg, m, params = _model()
    p = jax.random.randint(jax.random.PRNGKey(9), (6,), 0,
                           cfg.vocab - 1).astype(jnp.int32)
    ref = greedy_generate(m, params, {"tokens": p[None]}, max_new=5,
                          max_len=32)[0]
    sched = BatchScheduler(m, params, n_slots=2, max_len=32)
    sched.submit(Request(rid=0, prompt=p, max_new=5))
    done = []
    while not done:
        done += sched.step()
    assert done[0].out[:5] == [int(t) for t in ref[:5]]


def test_admission_rejects_prompt_longer_than_max_len():
    """The last real prompt token's K/V lands at position len-1; a prompt
    of max_len+1 tokens would scatter it past the cache depth and JAX
    would silently drop the write — must be refused at admission."""
    cfg, m, params = _model()
    sched = BatchScheduler(m, params, n_slots=2, max_len=16)
    p = jax.random.randint(jax.random.PRNGKey(5), (17,), 0,
                           cfg.vocab - 1).astype(jnp.int32)
    sched.submit(Request(rid=0, prompt=p, max_new=2))
    with pytest.raises(ValueError, match="exceeds"):
        sched.step()


def test_max_new_token_counts_are_exact():
    """Regression: a request must emit EXACTLY max_new tokens — the
    first on the step that feeds its final prompt chunk, one per decode
    step after — so a request costs ceil(plen / chunk) + max_new - 1
    steps, never an extra decode step past its budget."""
    cfg, m, params = _model()
    p = jax.random.randint(jax.random.PRNGKey(3), (6,), 0,
                           cfg.vocab - 1).astype(jnp.int32)
    for max_new in (1, 2, 3):
        sched = BatchScheduler(m, params, n_slots=2, max_len=32, chunk=4)
        sched.submit(Request(rid=0, prompt=p, max_new=max_new))
        done, steps = [], 0
        while not done and steps < 20:
            done += sched.step()
            steps += 1
        assert len(done) == 1
        assert len(done[0].out) == max_new          # pinned, not >=
        assert done[0].done
        # plen=6 at chunk=4 prefills in 2 steps (the 2nd emits token 1)
        assert steps == 2 + (max_new - 1)


def test_completion_frees_slots_for_next_step_admission():
    """A request finishing on step N releases its slot (and pages)
    within that step, so a queue of short requests drains through 2
    slots at full occupancy: 3 one-chunk max_new=1 requests need
    exactly 2 steps, never a stall step between waves."""
    cfg, m, params = _model()
    sched = BatchScheduler(m, params, n_slots=2, max_len=32, chunk=4)
    for rid in range(3):
        p = jax.random.randint(jax.random.PRNGKey(rid), (4,), 0,
                               cfg.vocab - 1).astype(jnp.int32)
        sched.submit(Request(rid=rid, prompt=p, max_new=1))
    first = sched.step()
    assert sorted(r.rid for r in first) == [0, 1]
    second = sched.step()
    assert [r.rid for r in second] == [2]
    assert all(len(r.out) == 1 for r in first + second)


def test_mixed_prompt_lengths_share_one_closure_bit_exactly():
    """Tentpole invariant: ANY prompt-length mix is served by ONE
    compiled window closure — zero re-traces — and the chunked-prefill
    path is bit-exact with the unpadded per-request reference."""
    obs.reset()
    cfg, m, params = _model()
    sched = BatchScheduler(m, params, n_slots=2, max_len=32)
    refs = {}
    for rid, plen in enumerate((1, 3, 5, 8, 9)):
        p = jax.random.randint(jax.random.PRNGKey(40 + rid), (plen,), 0,
                               cfg.vocab - 1).astype(jnp.int32)
        refs[rid] = greedy_generate(m, params, {"tokens": p[None]},
                                    max_new=3, max_len=32)[0]
        sched.submit(Request(rid=rid, prompt=p, max_new=3))
    done, steps = [], 0
    while len(done) < 5 and steps < 50:
        done += sched.step()
        steps += 1
    reg = obs.registry()
    assert reg.total("serve_jit_traces_total",
                     closure="decode", tenant="A") == 1
    assert reg.total("serve_jit_retraces_total") == 0
    for r in done:
        assert r.out == [int(t) for t in refs[r.rid]]
    # a longer prompt (the old 16-wide bucket) reuses the SAME closure:
    # still one trace, still bit-exact
    p = jax.random.randint(jax.random.PRNGKey(60), (12,), 0,
                           cfg.vocab - 1).astype(jnp.int32)
    ref = greedy_generate(m, params, {"tokens": p[None]}, max_new=2,
                          max_len=32)[0]
    sched.submit(Request(rid=99, prompt=p, max_new=2))
    done = []
    while not done:
        done += sched.step()
    assert reg.total("serve_jit_traces_total",
                     closure="decode", tenant="A") == 1
    assert reg.total("serve_jit_retraces_total") == 0
    assert done[0].out == [int(t) for t in ref]


@pytest.mark.parametrize("backend", ["digital", "crossbar"])
def test_window_head_reads_last_fed_position_only(backend):
    """The window step runs the head on each slot's last fed position.
    Against the full-window path (head on every position, then the row
    at ``m - 1``, then argmax) on a mixed window — a row mid-prompt, a
    row finishing its prompt, a decoding row, an empty slot — the
    tokens and the cache are equal, and on the crossbar (compiled
    kernel, here interpreted) the selected rows' logits are bitwise
    equal."""
    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=1, d_model=32, n_heads=2,
        n_kv=2, head_dim=16, d_ff=64, vocab=128, backend=backend,
        dtype=jnp.float32, tie_embeddings=backend == "digital",
        xbar=EngineConfig(tile_rows=32, tile_cols=32, mode="deepnet",
                          use_kernel=True,
                          quant=QuantConfig(w_bits=4, in_bits=6,
                                            adc_bits=12)))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    ex = m.executor
    tiles = {}
    if ex is not None:
        ex.program_params(params)
        tiles = ex.resident_tiles("A")
    leak = jnp.float32(0.0)

    def scoped(fn):
        def run(params, tiles, *args):
            if ex is None:
                return fn(params, *args)
            with ex.read_tenant("A"), ex.leak_scope(leak), \
                    ex.tile_scope(tiles):
                return fn(params, *args)
        return jax.jit(run)

    @scoped
    def heads(params, tokens, cache, m_):
        idx = jnp.maximum(m_ - 1, 0)
        full, new = m.decode_step(params, tokens, cache)
        sel = jnp.take_along_axis(full, idx[:, None, None], axis=1)[:, 0]
        last, _ = m.decode_step(params, tokens, cache, last=idx)
        layers = dict(new["layers"], len=cache["layers"]["len"] + m_[None])
        return sel, last[:, 0], dict(new, layers=layers)

    step = make_window_step(m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 4), 0,
                              cfg.vocab - 1).astype(jnp.int32)
    cache = m.init_cache(4, 16)
    # the first window leaves rows 0 and 1 mid-prompt and row 2 at the
    # end of a 3-token prompt; the second mixes every kind of row
    _, cache = step(params, tiles, toks[0], cache,
                    jnp.array([4, 4, 3, 0], jnp.int32), leak)
    m2 = jnp.array([4, 2, 1, 0], jnp.int32)
    sel, last, want = heads(params, tiles, toks[1], cache, m2)
    got_tok, got = step(params, tiles, toks[1],
                        jax.tree.map(jnp.copy, cache), m2, leak)
    want_tok = jnp.argmax(sel.astype(jnp.float32), axis=-1)
    assert jnp.array_equal(got_tok, want_tok)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.array_equal(a, b)
    if backend == "crossbar":
        assert jnp.array_equal(last, sel)
    else:
        assert jnp.allclose(last, sel, rtol=1e-6, atol=1e-6)
