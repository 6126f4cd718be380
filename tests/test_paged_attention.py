"""Paged-attention kernel CI: interpret-mode bit-exactness vs the ref.py
oracle AND the dense-cache SDPA at equal logical contents — the contract
the paged serving tier rests on (see kernels/paged_attention/kernel.py).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.paged_attention.ref import gather_pages
from repro.models.layers import AttnConfig, _chunked_sdpa

jax.config.update("jax_enable_x64", False)

_slow = pytest.mark.slow  # interpret-mode sweeps: CI full lane only; one
# point of each sweep stays unmarked so the PR fast lane keeps a
# kernel-correctness assertion


def _case(key, b, sq, hq, kv, hd, ps, ppr, n_pages, dtype):
    """Random paged K/V contents with per-row ragged depths/tables."""
    kq, kk, kv_, kt, kl = jax.random.split(key, 5)
    q = jax.random.normal(kq, (b, sq, hq, hd)).astype(dtype)
    kp = jax.random.normal(kk, (n_pages + 1, ps, kv, hd)).astype(dtype)
    vp = jax.random.normal(kv_, (n_pages + 1, ps, kv, hd)).astype(dtype)
    # each row owns a distinct page run; trailing entries null (0)
    maxlen = ps * ppr
    pt = jnp.zeros((b, ppr), jnp.int32)
    nxt = 1
    lens = []
    for r in range(b):
        depth = int(jax.random.randint(jax.random.fold_in(kl, r), (),
                                       sq, maxlen + 1))
        npg = -(-depth // ps)
        pt = pt.at[r, :npg].set(jnp.arange(nxt, nxt + npg))
        nxt += npg
        lens.append(depth)
    assert nxt - 1 <= n_pages
    kv_len = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, pt, kv_len, kv_len - sq


SWEEP = [
    # (b, sq, hq, kv, hd, ps, ppr)
    (2, 4, 4, 2, 8, 8, 3),        # smallest: runs in the fast lane
    pytest.param(3, 1, 8, 8, 16, 4, 4, marks=_slow),   # MHA decode, sq=1
    pytest.param(4, 6, 6, 2, 8, 16, 2, marks=_slow),   # GQA g=3
    pytest.param(1, 8, 4, 1, 32, 8, 4, marks=_slow),   # MQA
]


@pytest.mark.parametrize("b,sq,hq,kv,hd,ps,ppr", SWEEP)
def test_paged_kernel_bit_exact_vs_ref_and_dense(b, sq, hq, kv, hd, ps,
                                                 ppr):
    """The tripod the serving tier stands on: kernel == ref oracle ==
    dense-path SDPA over the gathered view, BITWISE."""
    key = jax.random.PRNGKey(b * 100 + ps)
    q, kp, vp, pt, kv_len, q_off = _case(key, b, sq, hq, kv, hd, ps, ppr,
                                         n_pages=b * ppr, dtype=jnp.bfloat16)
    ref = paged_attention_ref(q, kp, vp, pt, kv_len, q_off)
    ker = paged_attention(q, kp, vp, pt, kv_len, q_off)
    assert jnp.array_equal(ker, ref)
    cfg = AttnConfig(d_model=hq * hd, n_heads=hq, n_kv=kv, head_dim=hd)
    gk = gather_pages(kp, pt)
    gv = gather_pages(vp, pt)
    dense = _chunked_sdpa(q, gk, gv, cfg, kv_len=kv_len, q_offset=q_off)
    assert jnp.array_equal(ref, dense)
    assert jnp.array_equal(ker, dense)


@pytest.mark.parametrize("dtype", [
    jnp.bfloat16,
    pytest.param(jnp.float32, marks=_slow)])
def test_paged_kernel_dtypes(dtype):
    q, kp, vp, pt, kv_len, q_off = _case(jax.random.PRNGKey(7), 2, 4, 4,
                                         2, 8, 8, 3, n_pages=6, dtype=dtype)
    ref = paged_attention_ref(q, kp, vp, pt, kv_len, q_off)
    ker = paged_attention(q, kp, vp, pt, kv_len, q_off)
    assert ker.dtype == dtype
    assert jnp.array_equal(ker, ref)


def test_paged_kernel_fp8_cache_upcasts_like_dense_path():
    """fp8 K/V pages upcast to the query dtype inside the dot — the same
    branch the dense path takes (models/layers._sdpa)."""
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jax build")
    q, kp, vp, pt, kv_len, q_off = _case(jax.random.PRNGKey(9), 2, 4, 4,
                                         2, 8, 8, 3, n_pages=6,
                                         dtype=jnp.bfloat16)
    kp8 = kp.astype(jnp.float8_e4m3fn)
    vp8 = vp.astype(jnp.float8_e4m3fn)
    ref = paged_attention_ref(q, kp8, vp8, pt, kv_len, q_off)
    ker = paged_attention(q, kp8, vp8, pt, kv_len, q_off)
    assert ker.dtype == q.dtype
    assert jnp.array_equal(ker, ref)
    cfg = AttnConfig(d_model=4 * 8, n_heads=4, n_kv=2, head_dim=8)
    dense = _chunked_sdpa(q, gather_pages(kp8, pt), gather_pages(vp8, pt),
                          cfg, kv_len=kv_len, q_offset=q_off)
    assert jnp.array_equal(ker, dense)


def test_null_page_contents_never_leak_into_output():
    """Poisoning the null page must not change any output: every
    position the table routes to page 0 is excluded by the length mask
    with an exact softmax zero."""
    q, kp, vp, pt, kv_len, q_off = _case(jax.random.PRNGKey(11), 2, 4, 4,
                                         2, 8, 8, 3, n_pages=6,
                                         dtype=jnp.bfloat16)
    clean = paged_attention(q, kp, vp, pt, kv_len, q_off)
    kp_p = kp.at[0].set(jnp.asarray(1e4, kp.dtype))
    vp_p = vp.at[0].set(jnp.asarray(-1e4, vp.dtype))
    poisoned = paged_attention(q, kp_p, vp_p, pt, kv_len, q_off)
    assert jnp.array_equal(clean, poisoned)


def test_aliased_page_tables_bit_exact_with_materialized_copies():
    """The prefix-sharing contract: two rows whose tables point at the
    SAME physical pages (refcounted prefix sharing) must produce output
    bitwise identical to two rows reading private copies of those
    pages.  Gathers are read-only, so aliasing is invisible to both the
    kernel and the oracle — the scheduler's COW machinery exists purely
    to keep *writes* off shared pages."""
    key = jax.random.PRNGKey(21)
    b, sq, hq, kv, hd, ps, ppr = 2, 4, 4, 2, 8, 8, 3
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, hd)).astype(jnp.bfloat16)
    kp = jax.random.normal(kk, (8, ps, kv, hd)).astype(jnp.bfloat16)
    vp = jax.random.normal(kv_, (8, ps, kv, hd)).astype(jnp.bfloat16)
    # aliased: both rows share pages 1,2 for their prefix, own tails 3/4
    pt_alias = jnp.asarray([[1, 2, 3], [1, 2, 4]], jnp.int32)
    # materialized: row 1's prefix copied into private pages 5,6
    kp_mat = kp.at[5].set(kp[1]).at[6].set(kp[2])
    vp_mat = vp.at[5].set(vp[1]).at[6].set(vp[2])
    pt_mat = jnp.asarray([[1, 2, 3], [5, 6, 4]], jnp.int32)
    kv_len = jnp.asarray([ps * 3, ps * 3 - 2], jnp.int32)
    q_off = kv_len - sq
    for fn in (paged_attention_ref, paged_attention):
        aliased = fn(q, kp, vp, pt_alias, kv_len, q_off)
        materialized = fn(q, kp_mat, vp_mat, pt_mat, kv_len, q_off)
        assert jnp.array_equal(aliased, materialized)


def test_shape_validation_errors():
    q, kp, vp, pt, kv_len, q_off = _case(jax.random.PRNGKey(1), 2, 4, 4,
                                         2, 8, 8, 3, n_pages=6,
                                         dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="page_table rows"):
        paged_attention(q, kp, vp, pt[:1], kv_len, q_off)
    with pytest.raises(ValueError, match="head_dim"):
        paged_attention(q[..., :4], kp, vp, pt, kv_len, q_off)
    with pytest.raises(ValueError, match="shape"):
        paged_attention(q, kp, vp, pt, kv_len[:1], q_off)
