"""Streamed-lane paged attention CI: the bounded-ulp / argmax-stable
contract of the block-streamed online-softmax kernel, the two-lane
dispatch (counters, failures raise), and the
O(page_block) VMEM claim.

The scratch lane keeps its BITWISE tripod in test_paged_attention.py;
this file pins everything the streamed lane adds:

  * parity grid streamed-vs-scratch-vs-dense over kv dtypes
    {fp32, bf16, fp8} x window lengths straddling page-block boundaries
    (1, page_size, page_size*B +/- 1, >= 8 blocks), within the
    documented tolerance AND argmax-stable,
  * streamed kernel == its same-schedule jnp flash oracle (tight),
  * aliased/COW page tables are bitwise-invisible to the streamed lane,
  * dispatch counters: auto-lane thresholding, and a streamed-lane
    failure raises to the caller (no fallback lane, nothing counted),
  * streamed-lane VMEM scratch is constant in the window length while
    the scratch lane's grows linearly,
  * scheduler property: a long-prompt admission on the streamed lane
    causes ZERO retraces (runtime serve_jit_retraces_total check) and
    streamed dispatches only, with token streams matching the scratch
    lane.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.paged_attention import (
    paged_attention, paged_attention_streamed, paged_attention_streamed_ref,
    paged_path_calls, resolve_block_pages, scratch_lane_vmem_bytes,
    streamed_lane_resident_bytes, streamed_lane_vmem_bytes)
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import gather_pages
from repro.models.layers import AttnConfig, _chunked_sdpa

jax.config.update("jax_enable_x64", False)

_slow = pytest.mark.slow

# the documented streamed-lane contract: both lanes accumulate in f32,
# they differ only in reduction association (online vs one-shot
# softmax), so fp32 outputs agree to a few ulp and low-precision
# outputs to ~1 output-dtype ulp
_TOL = {
    jnp.float32: dict(atol=1e-6, rtol=1e-6),
    jnp.bfloat16: dict(atol=2e-2, rtol=2e-2),
}


def _assert_close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_TOL[dtype])


def _assert_argmax_stable(a, b):
    """The serving-level half of the contract: whatever downstream
    reduction picks a winner, both lanes pick the same one."""
    af = np.asarray(a, np.float32).reshape(a.shape[0], -1)
    bf = np.asarray(b, np.float32).reshape(b.shape[0], -1)
    assert (af.argmax(-1) == bf.argmax(-1)).all()


def _window_case(key, lens, *, sq=1, hq=4, kv=2, hd=8, ps=4, p_seq=16,
                 dtype=jnp.float32, kv_dtype=None):
    """One row per requested window length; each row owns a private
    contiguous page run, trailing table entries null (page 0)."""
    b = len(lens)
    kq, kk, kvk = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, hd)).astype(dtype)
    n_pages = b * p_seq + 1
    kp = jax.random.normal(kk, (n_pages, ps, kv, hd)).astype(kv_dtype
                                                             or dtype)
    vp = jax.random.normal(kvk, (n_pages, ps, kv, hd)).astype(kv_dtype
                                                              or dtype)
    pt = jnp.zeros((b, p_seq), jnp.int32)
    for r, depth in enumerate(lens):
        assert sq <= depth <= ps * p_seq
        npg = -(-depth // ps)
        pt = pt.at[r, :npg].set(jnp.arange(1 + r * p_seq,
                                           1 + r * p_seq + npg))
    kv_len = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, pt, kv_len, kv_len - sq


# window lengths straddling the page-block boundary at block_pages=2,
# page_size=4 (block = 8 tokens): 1, page_size, block -/+ 1, block, and
# the full 16-page window = 8 blocks
_BP = 2
_PS = 4
_WINDOWS = (1, _PS, _PS * _BP - 1, _PS * _BP, _PS * _BP + 1, _PS * 16)


@pytest.mark.parametrize("dtype,sq", [
    (jnp.float32, 1),
    pytest.param(jnp.bfloat16, 1, marks=_slow),
    pytest.param(jnp.float32, 4, marks=_slow),   # causal, multi-query rows
])
def test_streamed_parity_grid_vs_scratch_and_dense(dtype, sq):
    """The parity grid: every boundary-straddling window in one ragged
    batch, streamed within tolerance of BOTH the scratch lane and the
    dense-path SDPA, argmax-stable, and tight against its own
    same-schedule flash oracle."""
    windows = tuple(max(w, sq) for w in _WINDOWS)   # need sq <= window
    q, kp, vp, pt, kv_len, q_off = _window_case(
        jax.random.PRNGKey(3), windows, sq=sq, ps=_PS, dtype=dtype)
    streamed = paged_attention(q, kp, vp, pt, kv_len, q_off,
                               lane="streamed", block_pages=_BP)
    scratch = paged_attention(q, kp, vp, pt, kv_len, q_off, lane="scratch")
    oracle = paged_attention_streamed_ref(q, kp, vp, pt, kv_len, q_off,
                                          block_pages=_BP)
    assert streamed.dtype == dtype
    _assert_close(streamed, scratch, dtype)
    _assert_close(streamed, oracle, dtype)
    _assert_argmax_stable(streamed, scratch)
    if dtype is jnp.float32 and sq == 1:
        # One dense-arm compile is enough: scratch == dense is pinned
        # bitwise in test_paged_attention, so streamed ~= scratch covers
        # the dense path transitively for the slow params.
        cfg = AttnConfig(d_model=q.shape[2] * q.shape[3],
                         n_heads=q.shape[2], n_kv=kp.shape[2],
                         head_dim=q.shape[3])
        dense = _chunked_sdpa(q, gather_pages(kp, pt), gather_pages(vp, pt),
                              cfg, kv_len=kv_len, q_offset=q_off)
        _assert_close(streamed, dense, dtype)
        _assert_argmax_stable(streamed, dense)


def test_streamed_parity_fp8_kv_cache():
    """fp8 K/V pages upcast inside the dot on both lanes; the streamed
    output stays within one bf16 ulp of the scratch lane."""
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jax build")
    q, kp, vp, pt, kv_len, q_off = _window_case(
        jax.random.PRNGKey(5), _WINDOWS, ps=_PS, dtype=jnp.bfloat16,
        kv_dtype=jnp.float8_e4m3fn)
    streamed = paged_attention(q, kp, vp, pt, kv_len, q_off,
                               lane="streamed", block_pages=_BP)
    scratch = paged_attention(q, kp, vp, pt, kv_len, q_off, lane="scratch")
    assert streamed.dtype == q.dtype
    _assert_close(streamed, scratch, jnp.bfloat16)
    _assert_argmax_stable(streamed, scratch)


def test_streamed_aliased_page_tables_bitwise_vs_materialized():
    """Prefix sharing is read-only aliasing: the streamed gather cannot
    tell a shared physical page from a private copy, so aliased vs
    materialized tables agree BITWISE (same lane, same schedule)."""
    key = jax.random.PRNGKey(21)
    b, sq, hq, kv, hd, ps = 2, 1, 4, 2, 8, 4
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, hd)).astype(jnp.float32)
    kp = jax.random.normal(kk, (8, ps, kv, hd)).astype(jnp.float32)
    vp = jax.random.normal(kv_, (8, ps, kv, hd)).astype(jnp.float32)
    # both rows share pages 1,2 for their prefix, own tails 3/4
    pt_alias = jnp.asarray([[1, 2, 3, 0], [1, 2, 4, 0]], jnp.int32)
    kp_mat = kp.at[5].set(kp[1]).at[6].set(kp[2])
    vp_mat = vp.at[5].set(vp[1]).at[6].set(vp[2])
    pt_mat = jnp.asarray([[1, 2, 3, 0], [5, 6, 4, 0]], jnp.int32)
    kv_len = jnp.asarray([ps * 3, ps * 3 - 2], jnp.int32)
    q_off = kv_len - sq
    aliased = paged_attention_streamed(q, kp, vp, pt_alias, kv_len, q_off,
                                       block_pages=2)
    materialized = paged_attention_streamed(q, kp_mat, vp_mat, pt_mat,
                                            kv_len, q_off, block_pages=2)
    assert jnp.array_equal(aliased, materialized)


def test_resolve_block_pages_clamps_to_divisor():
    assert resolve_block_pages(16, 16) == 16
    assert resolve_block_pages(16, 5) == 4
    assert resolve_block_pages(9, 4) == 3
    assert resolve_block_pages(7, 16) == 7    # prime width: whole table
    assert resolve_block_pages(12, 8) == 6
    assert resolve_block_pages(1, 16) == 1


# -- dispatch: counters, auto lane, failures raise ----------------------------

def _tiny_case(seed=1):
    return _window_case(jax.random.PRNGKey(seed), (3, 14), ps=_PS,
                        p_seq=4, dtype=jnp.float32)


def test_auto_lane_thresholds_on_table_width():
    """lane="auto" picks streamed iff stream_min_pages is enabled and
    the table is at least that wide; every call lands in the dispatch
    counters."""
    obs.reset()
    q, kp, vp, pt, kv_len, q_off = _tiny_case()
    base = dict(paged_path_calls)
    paged_attention(q, kp, vp, pt, kv_len, q_off)            # default
    paged_attention(q, kp, vp, pt, kv_len, q_off,
                    stream_min_pages=8)                      # 4 < 8
    assert paged_path_calls["paged_scratch"] == base["paged_scratch"] + 2
    assert paged_path_calls["paged_streamed"] == base["paged_streamed"]
    paged_attention(q, kp, vp, pt, kv_len, q_off,
                    stream_min_pages=4, block_pages=2)       # 4 >= 4
    paged_attention(q, kp, vp, pt, kv_len, q_off, lane="streamed",
                    block_pages=2)
    assert paged_path_calls["paged_streamed"] == base["paged_streamed"] + 2
    with pytest.raises(ValueError, match="lane"):
        paged_attention(q, kp, vp, pt, kv_len, q_off, lane="warp")


def test_streamed_failure_warns_once_and_falls_back_to_scratch_kernel(
        monkeypatch):
    """Fails loudly: a streamed-lane failure propagates to the caller
    every time and counts no dispatch, so a lane that cannot lower on
    the chip stops the program instead of serving another lane."""
    obs.reset()
    q, kp, vp, pt, kv_len, q_off = _tiny_case(seed=2)

    def boom(*a, **k):
        raise RuntimeError("induced streamed-lane lowering failure")

    monkeypatch.setattr(paged_ops._kernel_mod, "paged_attention_streamed",
                        boom)
    base = dict(paged_path_calls)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="induced streamed-lane"):
            paged_attention(q, kp, vp, pt, kv_len, q_off, lane="streamed")
    assert dict(paged_path_calls) == base


# -- the O(page_block) VMEM claim ---------------------------------------------

def test_streamed_vmem_constant_while_scratch_grows_linearly():
    """The tentpole's point: the scratch lane's gather buffer is linear
    in the window; the streamed lane's ring + online-softmax stats do
    not depend on it at all.  The honest companion number: the CURRENT
    lowering maps the whole K/V pools as input blocks, so its total
    residency is scratch + 2x pool — accounted (and pinned) separately
    so the O(block) claim never silently overstates what a real-TPU
    lowering would hold."""
    geom = dict(page_size=8, kv=2, hd=64, kv_dtype=jnp.bfloat16)
    windows = (16, 32, 64, 128, 256)
    scratch = [scratch_lane_vmem_bytes(p, geom["page_size"], geom["kv"],
                                       geom["hd"], geom["kv_dtype"])
               for p in windows]
    streamed = [streamed_lane_vmem_bytes(4, 1, 8, geom["kv"], geom["hd"],
                                         p, geom["page_size"],
                                         16, geom["kv_dtype"])
                for p in windows]
    assert len(set(streamed)) == 1                # constant in the window
    for a, b, pa, pb in zip(scratch, scratch[1:], windows, windows[1:]):
        assert b * pa == a * pb                   # exactly linear
    assert streamed[0] < scratch[-1]              # and it actually pays off
    # resident = scratch + both pools, exactly; grows with the pool (one
    # full-depth row per window here), NOT constant — the accounting
    # must not launder pool residency into the O(block) claim
    itemsize = jnp.dtype(geom["kv_dtype"]).itemsize
    resident = []
    for p in windows:
        n_pool = 4 * p + 1
        r = streamed_lane_resident_bytes(4, 1, 8, geom["kv"], geom["hd"],
                                         p, geom["page_size"], 16,
                                         n_pool, geom["kv_dtype"])
        pools = 2 * n_pool * geom["page_size"] * geom["kv"] * geom["hd"] \
            * itemsize
        assert r == streamed[0] + pools
        resident.append(r)
    assert len(set(resident)) == len(windows)


# -- scheduler property: long-prompt admission, zero retraces -----------------

def test_streamed_lane_long_prompt_admission_zero_retraces():
    """Admitting a long prompt (chunked prefill) plus decode traffic on
    the streamed lane traces ONE decode closure, retraces NOTHING, never
    falls back — and emits the same token streams as the scratch lane."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve.engine import BatchScheduler, Request

    def serve(**overrides):
        cfg = dataclasses.replace(get_config("qwen3_4b", smoke=True),
                                  paged_kernel=True, **overrides)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        sched = BatchScheduler(m, params, n_slots=2, max_len=64,
                               page_size=8, chunk=8)
        prompts = [jax.random.randint(jax.random.PRNGKey(100 + i), (plen,),
                                      0, cfg.vocab - 1).astype(jnp.int32)
                   for i, plen in enumerate((58, 5))]
        for rid, p in enumerate(prompts):
            sched.submit(Request(rid=rid, prompt=p, max_new=4))
        done, steps = {}, 0
        while len(done) < 2 and steps < 60:
            for r in sched.step():
                done[r.rid] = r.out
            steps += 1
        assert len(done) == 2
        return done

    obs.reset()
    base = dict(paged_path_calls)
    streamed = serve(paged_stream_pages=4, paged_block_pages=2)
    reg = obs.registry()
    assert reg.total("serve_jit_retraces_total") == 0
    assert reg.total("serve_jit_traces_total", closure="decode",
                     tenant="A") == 1
    assert paged_path_calls["paged_streamed"] > base["paged_streamed"]
    assert paged_path_calls["paged_scratch"] == base["paged_scratch"]
    scratch = serve()                        # default config: scratch lane
    assert streamed == scratch               # argmax-stable end to end
