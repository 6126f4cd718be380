"""Weight-residency benchmarks: program-once vs re-program-per-call, and
reference (scan) vs Pallas-kernel MAC throughput.

The paper's deployment contract is program-at-load / read-at-inference;
these benches quantify what that residency buys over the naive
``engine.linear`` (which re-quantizes, re-slices and re-"programs" the
weight matrix on every invocation), plus the model-level view: a smoke
transformer decode step on the crossbar backend, weights resident, served
exactly as the BatchScheduler runs it.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

# one timing harness for the whole BENCH artifact — residency numbers stay
# comparable with the paper benches
from benchmarks.paper_benches import _timeit
from repro.configs import get_config
from repro.core import engine as eng
from repro.core.engine import EngineConfig
from repro.core.quant import QuantConfig
from repro.models.model import build_model


def bench_program_once(quick: bool = False):
    """Resident-tile matmul vs program-and-run on every call."""
    b, k, n = (8, 128, 128) if quick else (16, 256, 256)
    reps = 3 if quick else 10
    cfg = EngineConfig(tile_rows=64, tile_cols=128, mode="deepnet",
                       quant=QuantConfig(w_bits=4, in_bits=8, adc_bits=10))
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (b, k))

    pw = eng.program(w, cfg)
    resident = jax.jit(lambda xx: eng.matmul(xx, pw, cfg))
    # w enters as an ARGUMENT so XLA cannot constant-fold the programming
    # step out of the per-call graph
    reprogram = jax.jit(lambda xx, ww: eng.linear(xx, ww, cfg))

    us_once = _timeit(resident, x, n=reps)
    us_reprog = _timeit(reprogram, x, w, n=reps)
    return {"us_per_call": us_once,
            "us_program_once": us_once,
            "us_reprogram_per_call": us_reprog,
            "program_once_speedup": us_reprog / max(us_once, 1e-9),
            "shape_bkn": [b, k, n]}


def bench_reference_vs_kernel(quick: bool = False):
    """Scan-based jnp reference vs the Pallas crossbar_mac kernel.

    On CPU the kernel runs in interpret mode (every grid step is traced
    Python), so the reference wins; on a real TPU the compiled kernel
    flips the comparison.  Both numbers land in the JSON either way so the ratio
    is tracked per-commit.
    """
    b, k, n = (8, 64, 64) if quick else (16, 128, 128)
    reps = 2 if quick else 5
    qc = QuantConfig(w_bits=4, in_bits=8, adc_bits=10)
    cfg_ref = EngineConfig(tile_rows=32, tile_cols=64, mode="deepnet",
                           quant=qc)
    cfg_ker = dataclasses.replace(cfg_ref, use_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(3), (b, k))
    pw = eng.program(w, cfg_ref)

    f_ref = jax.jit(lambda xx: eng.matmul(xx, pw, cfg_ref))
    f_ker = jax.jit(lambda xx: eng.matmul(xx, pw, cfg_ker))
    us_ref = _timeit(f_ref, x, n=reps)
    us_ker = _timeit(f_ker, x, n=reps)
    err = float(jnp.abs(f_ref(x) - f_ker(x)).max())
    return {"us_per_call": us_ref,
            "us_reference_scan": us_ref,
            "us_kernel_interpret": us_ker,
            "kernel_vs_reference": us_ref / max(us_ker, 1e-9),
            "max_abs_diff": err,
            "shape_bkn": [b, k, n]}


def bench_executor_decode(quick: bool = False):
    """Model-level residency: smoke-transformer decode step, crossbar vs
    digital backend, plus one-time programming cost."""
    cfg_d = get_config("qwen3_4b", smoke=True)
    xb = EngineConfig(tile_rows=64, tile_cols=128, mode="deepnet",
                      quant=QuantConfig(w_bits=4, in_bits=8, adc_bits=10))
    cfg_c = dataclasses.replace(cfg_d, backend="crossbar", xbar=xb)
    md = build_model(cfg_d)
    mc = build_model(cfg_c)
    params = md.init(jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    n_programmed = mc.executor.program_params(params)
    us_program = (time.perf_counter() - t0) * 1e6

    toks = jnp.zeros((2, 1), jnp.int32)
    cache_d = md.init_cache(2, 16)
    cache_c = mc.init_cache(2, 16)
    reps = 2 if quick else 5
    dec_d = jax.jit(lambda p, t, c: md.decode_step(p, t, c)[0])
    dec_c = jax.jit(lambda p, t, c: mc.decode_step(p, t, c)[0])
    us_digital = _timeit(dec_d, params, toks, cache_d, n=reps)
    us_crossbar = _timeit(dec_c, params, toks, cache_c, n=reps)
    return {"us_per_call": us_crossbar,
            "us_decode_crossbar": us_crossbar,
            "us_decode_digital": us_digital,
            "us_program_all_weights_once": us_program,
            "n_weights_programmed": n_programmed,
            "n_devices": mc.executor.n_devices,
            "program_cost_amortized_after_calls":
                us_program / max(us_crossbar, 1e-9)}
