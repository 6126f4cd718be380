"""Weight-resident crossbar execution layer: program-once semantics, the
digital/crossbar backend switch, the scan-based reference path, and the
scheduler serving through resident tiles."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import engine as eng
from repro.core.engine import EngineConfig, ProgrammedLinear
from repro.core.executor import CrossbarExecutor, crossbar_linear, scope
from repro.core.quant import QuantConfig
from repro.models.model import build_model
from repro.serve.engine import BatchScheduler, Request


HIFI = EngineConfig(tile_rows=128, tile_cols=128, mode="deepnet",
                    quant=QuantConfig(w_bits=8, in_bits=10, adc_bits=14))


def _crossbar_cfg(smoke_cfg):
    return dataclasses.replace(smoke_cfg, backend="crossbar", xbar=HIFI,
                               dtype=jnp.float32)


# -- scan-based reference path -----------------------------------------------

@pytest.mark.parametrize("mode", ["expansion", "deepnet"])
@pytest.mark.parametrize("k,n,tile_rows,bpc", [
    (96, 80, 32, 1), (128, 33, 32, 1), (64, 48, 16, 2)])
def test_scan_reference_bit_identical_to_einsum(mode, k, n, tile_rows, bpc):
    """The scan and the einsum compute the same ADC outputs (the dots
    are exact: 0/1 pulses times small integer codes) and differ only in
    the ORDER of the f32 shift-add over the n_terms = in_bits * S *
    groups (pulse, slice, row-group) conversions: the scan adds one
    (pulse, slice) at a time, the einsum lets XLA reassociate the whole
    contraction.  Each reordered addition rounds by at most half an
    ulp of a partial sum, and the partial sums stay within the output
    range, so the two agree to n_terms ulps of max|y| (measured: at
    most 2) — bitwise wherever XLA happens to keep the order."""
    qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=10, bits_per_cell=bpc)
    cfg = EngineConfig(tile_rows=tile_rows, tile_cols=32, mode=mode,
                       quant=qc)
    w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(n), (7, k))
    pw = eng.program(w, cfg)
    y_scan = eng.matmul_reference(x, pw, cfg)
    y_einsum = eng._matmul_reference_einsum(x, pw, cfg)
    s, t = pw.pos.shape[:2]
    groups = t // 2 if (mode == "expansion" and t % 2 == 0) else t
    n_terms = qc.in_bits * s * groups
    ulp = float(jnp.finfo(jnp.float32).eps) * float(jnp.abs(y_einsum).max())
    assert float(jnp.abs(y_scan - y_einsum).max()) <= n_terms * ulp


# -- ProgrammedLinear pytree round-trip ---------------------------------------

def test_programmed_linear_pytree_round_trip():
    cfg = EngineConfig(tile_rows=32, tile_cols=32)
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48)) * 0.3
    pw = eng.program(w, cfg)
    leaves, treedef = jax.tree_util.tree_flatten(pw)
    pw2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(pw2, ProgrammedLinear)
    assert (pw2.k, pw2.n) == (pw.k, pw.n)
    assert jnp.array_equal(pw2.pos, pw.pos)
    assert jnp.array_equal(pw2.neg, pw.neg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    assert jnp.array_equal(eng.matmul(x, pw, cfg), eng.matmul(x, pw2, cfg))
    # and through jit, where the registered pytree is what gets traced
    y_jit = jax.jit(lambda p: eng.matmul(x, p, cfg))(pw)
    assert jnp.allclose(y_jit, eng.matmul(x, pw, cfg), atol=1e-6)


# -- program-once semantics ----------------------------------------------------

def test_executor_programs_each_weight_exactly_once():
    cfg = get_config("qwen3_4b", smoke=True)
    model = build_model(_crossbar_cfg(cfg))
    params = model.init(jax.random.PRNGKey(0))
    ex = model.executor
    n_first = ex.program_params(params)
    # 7 linears per block (wq wk wv wo wi wg wo) x n_layers + head
    assert n_first == 7 * cfg.n_layers + 1
    assert ex.stats["programmed"] == n_first
    assert ex.stats["cache_hits"] == 0
    # second walk: all cache hits, nothing re-programmed
    n_second = ex.program_params(params)
    assert n_second == 0
    assert ex.stats["programmed"] == n_first
    assert ex.stats["cache_hits"] == n_first
    # inference afterwards leaves the program counters untouched
    cache = model.init_cache(1, 16)
    toks = jnp.zeros((1, 4), jnp.int32)
    model.prefill(params, {"tokens": toks}, cache)
    assert ex.stats["programmed"] == n_first


def test_executor_rejects_serving_a_different_params_tree():
    """Resident tiles are physical state: a second checkpoint must not be
    silently served through tiles programmed from the first."""
    cfg = _crossbar_cfg(get_config("qwen3_4b", smoke=True))
    model = build_model(cfg)
    params_v1 = model.init(jax.random.PRNGKey(0))
    params_v2 = model.init(jax.random.PRNGKey(1))
    model.executor.program_params(params_v1)
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(RuntimeError, match="different params tree"):
        model.prefill(params_v2, {"tokens": toks}, model.init_cache(1, 16))
    # the programmed tree still serves fine
    model.prefill(params_v1, {"tokens": toks}, model.init_cache(1, 16))


def test_executor_rejects_tracers_before_programming():
    model = build_model(_crossbar_cfg(get_config("qwen3_4b", smoke=True)))
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((1, 1), jnp.int32)
    cache = model.init_cache(1, 8)
    with pytest.raises(RuntimeError, match="program"):
        jax.jit(model.decode_step)(params, toks, cache)
    # after eager programming the same jit traces fine
    model.executor.program_params(params)
    logits, _ = jax.jit(model.decode_step)(params, toks, cache)
    assert logits.shape[-1] == model.cfg.padded_vocab


# -- backend switch: crossbar forward vs digital -------------------------------

def test_crossbar_forward_matches_digital_within_quant_tolerance():
    base = dataclasses.replace(get_config("qwen3_4b", smoke=True),
                               dtype=jnp.float32)
    md = build_model(base)
    mc = build_model(_crossbar_cfg(base))
    params = md.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              base.vocab - 1).astype(jnp.int32)
    ld, _ = md.prefill(params, {"tokens": toks}, md.init_cache(2, 16))
    lx, _ = mc.prefill(params, {"tokens": toks}, mc.init_cache(2, 16))
    assert lx.shape == ld.shape
    rel = float(jnp.abs(lx - ld).max() / jnp.abs(ld).max())
    assert rel < 0.05, f"crossbar deviates {rel:.3f} from digital"


def test_crossbar_linear_routes_only_inside_active_scope():
    ex = CrossbarExecutor(EngineConfig(
        tile_rows=32, tile_cols=32,
        quant=QuantConfig(w_bits=8, in_bits=10, adc_bits=14)))
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.3
    ex.program_params({"head": w})
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    digital = x @ w
    # no active executor -> the digital thunk runs
    assert jnp.array_equal(crossbar_linear(x, w, "head",
                                           digital=lambda: x @ w), digital)
    with ex.activate():
        y = crossbar_linear(x, w, "head", digital=lambda: x @ w)
        # resident-tile read: quantized, so close-but-not-equal to digital
        assert not jnp.array_equal(y, digital)
        assert jnp.allclose(y, digital, rtol=0.2, atol=0.2)
        # unknown names fall back to digital even while active
        with scope("blocks"):
            z = crossbar_linear(x, w, "nonexistent",
                                digital=lambda: x @ w)
        assert jnp.array_equal(z, digital)


def test_crossbar_backend_rejected_for_non_transformer_families():
    cfg = dataclasses.replace(get_config("rwkv6_3b", smoke=True),
                              backend="crossbar")
    with pytest.raises(ValueError, match="transformer"):
        build_model(cfg)


# -- end to end: BatchScheduler over resident tiles ----------------------------

def test_scheduler_serves_through_crossbar_path():
    cfg = _crossbar_cfg(get_config("qwen3_4b", smoke=True))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=2, max_len=32)
    ex = model.executor
    n_programmed = ex.stats["programmed"]
    assert n_programmed == 7 * cfg.n_layers + 1  # programmed at init
    for rid in range(3):
        p = jax.random.randint(jax.random.PRNGKey(rid), (6,), 0,
                               cfg.vocab - 1).astype(jnp.int32)
        sched.submit(Request(rid=rid, prompt=p, max_new=4))
    done, steps = [], 0
    while len(done) < 3 and steps < 100:
        done += sched.step()
        steps += 1
    assert len(done) == 3
    assert all(len(r.out) >= 4 for r in done)
    # serving re-programmed NOTHING: weights stayed resident throughout
    assert ex.stats["programmed"] == n_programmed


def test_window_step_takes_tiles_as_arguments_not_constants():
    """The scheduler's crossbar window step reads the resident planes
    from its ``tiles`` argument: the lowered program holds no tile
    literals, so its text does not grow with the bytes programmed (at
    qwen3-4b widths the tiles are GBs, which a closure constant would
    write into the program and hold twice in device memory)."""
    sizes = {}
    for d_model in (64, 256):
        cfg = dataclasses.replace(get_config("qwen3_4b", smoke=True),
                                  d_model=d_model, d_ff=4 * d_model,
                                  backend="crossbar")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = BatchScheduler(model, params, n_slots=2, max_len=16)
        lane = sched._lanes["A"]
        tiles = model.executor.resident_tiles("A")
        text = lane.decode.lower(
            lane.params, tiles, jnp.zeros((lane.width, sched.chunk),
                                          jnp.int32),
            lane.cache, jnp.zeros((lane.width,), jnp.int32),
            jnp.float32(0.0)).as_text()
        tile_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(tiles))
        sizes[d_model] = (len(text), tile_bytes)
    (text_small, bytes_small), (text_big, bytes_big) = sizes[64], sizes[256]
    assert bytes_big > 2 * bytes_small
    assert text_big <= 1.05 * text_small
    assert text_big < bytes_big // 10
