#!/usr/bin/env python3
"""Chip smoke: serve qwen3-4b at its published widths on one TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py

All phases run in one process (a chip belongs to one process); any
failed check raises, and the script exits non-zero.

* Phase 0 — device.  Requires ``jax.devices()[0].platform == "tpu"``
  and exits 1 otherwise: this script never falls back to the CPU.
  Prints the device kind and count and the jax/jaxlib/libtpu versions.
* Phase 1 — digital serving.  ``get_config("qwen3-4b")`` with every
  width as published (d_model 2560, d_ff 9728, 32x128 query heads,
  vocab 151936) and the depth cut to the most layers that fit the
  chip's memory (see ``depth_for_chip``).  ``BatchScheduler`` is built
  the way ``launch/serve.py`` builds it (paged KV, default chunk, 4
  slots) and serves 8 requests of 128 prompt tokens and 16 new tokens.
  Every request must complete, and one request re-served alone must
  emit exactly the tokens it emitted in the batch (the scheduler's
  batched == serial invariant).
* Phase 2 — crossbar serving on the compiled kernel.  The same widths
  at 2 layers with ``backend="crossbar"`` and
  ``EngineConfig.use_kernel=True``: every linear layer reads its
  resident crossbar tiles through ``crossbar_mac``.  A few requests are
  served; the dispatch counter must show kernel lowerings and no
  reference lowering.  Then the kernel and ``engine.matmul_reference``
  run on the tiles of ``blocks.0.attn.wq`` and must agree (see
  ``check_kernel_vs_reference`` for the tolerance).

Weights are random, drawn from fixed seeds.  Compile seconds and steady
tokens/s are printed for information only.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    BatchScheduler, Request, make_window_step)

ARCH = "qwen3-4b"
SLOTS = 4
PAGE_SIZE = 8
# device bytes kept free beyond params, the step's bf16 weight copy and
# the KV pool: embedding gather, logits, allocator slack
RESERVE_BYTES = 2 * 10 ** 9


def check(ok: bool, what: str) -> None:
    """A smoke check: raise (and so fail the run) unless ``ok``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def serve(sched: BatchScheduler, reqs):
    """Submit ``reqs`` and step until all finish.  Returns (finished,
    seconds of the first step — compile included —, seconds and tokens
    of the remaining steps)."""
    for r in reqs:
        sched.submit(r)
    done, steps = [], 0
    t0 = time.perf_counter()
    done += sched.step()
    first_s = time.perf_counter() - t0
    first_tokens = sum(len(r.out) for r in reqs)
    t1 = time.perf_counter()
    while len(done) < len(reqs):
        done += sched.step()
        steps += 1
        if steps > 10_000:
            raise RuntimeError("scheduler did not drain")
    rest_s = time.perf_counter() - t1
    rest_tokens = sum(len(r.out) for r in reqs) - first_tokens
    return done, first_s, rest_s, rest_tokens


def make_requests(vocab: int, n: int, plen: int, max_new: int, seed: int):
    key = jax.random.PRNGKey(seed)
    reqs = []
    for rid in range(n):
        key, k = jax.random.split(key)
        prompt = jax.random.randint(k, (plen,), 0, vocab - 1).astype(
            jnp.int32)
        reqs.append(Request(rid=rid, prompt=prompt, max_new=max_new))
    return reqs


def depth_for_chip(full, bytes_limit: int, max_len: int) -> dict:
    """The most layers of ``full`` that fit ``bytes_limit``, from bytes.

    Per layer: its f32 parameters, plus the bf16 copy of them the
    digital step makes (XLA hoists the weight cast out of the layer
    scan; the v5e compile rehearsal reports it as temp bytes), plus the
    layer's share of the KV pool.  Fixed: embedding, head and norms.
    """
    key = jax.random.PRNGKey(0)

    def params_at(n_layers):
        model = build_model(dataclasses.replace(full, n_layers=n_layers))
        return tree_bytes(jax.eval_shape(model.init, key))

    per_layer = params_at(2) - params_at(1)
    fixed = params_at(1) - per_layer
    one = build_model(dataclasses.replace(full, n_layers=1))
    n_pages = SLOTS * max_len // PAGE_SIZE
    pool_layer = tree_bytes(jax.eval_shape(
        lambda: one.init_paged_cache(SLOTS, max_len, n_pages, PAGE_SIZE)))
    per = per_layer * 3 // 2 + pool_layer
    n = int((bytes_limit - fixed - RESERVE_BYTES) // per)
    return {"n_layers": max(1, min(full.n_layers, n)),
            "param_bytes_per_layer": per_layer, "fixed_param_bytes": fixed,
            "pool_bytes_per_layer": pool_layer}


def step_bytes(model, max_len: int, chunk: int) -> int:
    """Device bytes the compiled window step needs (arguments + temp +
    outputs not aliased to donated inputs), from the chip compiler's
    ``memory_analysis`` on abstract shapes — the check on
    ``depth_for_chip``."""
    n_pages = SLOTS * max_len // PAGE_SIZE
    args = (jax.eval_shape(model.init, jax.random.PRNGKey(0)), {},
            jax.ShapeDtypeStruct((SLOTS, chunk), jnp.int32),
            jax.eval_shape(lambda: model.init_paged_cache(
                SLOTS, max_len, n_pages, PAGE_SIZE)),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))
    ma = make_window_step(model).lower(*args).compile().memory_analysis()
    print(f"[phase 1] window step memory_analysis: arguments "
          f"{ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
          f"outputs {ma.output_size_in_bytes} B (aliased "
          f"{ma.alias_size_in_bytes} B)", flush=True)
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def phase_device():
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform is "
              f"{d0.platform!r}); refusing to run on another backend",
              file=sys.stderr)
        return None
    import jaxlib
    print(f"[phase 0] device {d0.device_kind!r} x{len(devs)}; jax "
          f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
          f"{importlib.metadata.version('libtpu')}", flush=True)
    return d0


def phase_digital(d0):
    full = get_config(ARCH)
    plen, max_new = 128, 16
    max_len = -(-(plen + max_new) // 32) * 32
    limit = d0.memory_stats()["bytes_limit"]
    depth = depth_for_chip(full, limit, max_len)
    cfg = dataclasses.replace(full, n_layers=depth["n_layers"])
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=SLOTS, max_len=max_len,
                           page_size=PAGE_SIZE)
    need = step_bytes(model, max_len, sched.chunk)
    check(need <= limit, f"window step needs {need} B of {limit} B")
    pool = tree_bytes(sched._lanes["A"].cache)
    print(f"[phase 1] qwen3-4b digital: {cfg.n_layers} of "
          f"{full.n_layers} layers at published widths (d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, {cfg.n_heads}x{cfg.head_dim} "
          f"query heads, vocab {cfg.vocab}); params "
          f"{tree_bytes(params)} B ({depth['param_bytes_per_layer']} B "
          f"per layer + {depth['fixed_param_bytes']} B embed/head); KV "
          f"pool {pool} B", flush=True)
    reqs = make_requests(cfg.vocab, 8, plen, max_new, seed=1)
    done, first_s, rest_s, rest_tokens = serve(sched, reqs)
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} completed")
    for r in reqs:
        check(r.done and len(r.out) == max_new,
              f"request {r.rid} emitted {len(r.out)} tokens")
        check(all(0 <= t < cfg.padded_vocab for t in r.out),
              f"request {r.rid} tokens out of range: {r.out}")
    solo = Request(rid=100, prompt=reqs[0].prompt, max_new=max_new)
    serve(sched, [solo])
    check(solo.out == reqs[0].out,
          f"batched != solo: {reqs[0].out} vs {solo.out}")
    check(obs.registry().total("serve_jit_retraces_total") == 0,
          "the window step re-traced")
    print(f"[phase 1] served {len(done)} requests x {max_new} tokens; "
          f"solo re-serve identical; first step (compile) {first_s:.2f} s; "
          f"steady {rest_tokens / rest_s:.1f} tok/s (information only); "
          f"peak {d0.memory_stats()['peak_bytes_in_use']} B", flush=True)
    print(f"[phase 1] req 0 tokens {reqs[0].out}", flush=True)
    return cfg.n_layers


def check_kernel_vs_reference(ex, name: str, rows: int = 8) -> None:
    """The kernel against the scan reference on one weight's tiles.

    The dots are exact at default TPU precision (0/1 pulses times cell
    codes below 2**bits_per_cell, summed over at most 256 rows), so the
    two paths produce the same ADC codes and differ only in the order
    of the f32 shift-add over pulses, slices and row groups.  The CPU
    contract (tests/test_kernels.py) is atol 1e-4 for outputs of order
    one; reassociation error scales with the magnitude of the partial
    sums, so the same bound is applied relative to this weight's
    largest output: tol = 1e-4 * max(1, max|y_ref|).
    """
    pw = ex.resident_tiles("A")[name]
    cfg_ref = dataclasses.replace(ex.cfg, mode=ex.mode_for(name),
                                  use_kernel=False)
    cfg_ker = dataclasses.replace(cfg_ref, use_kernel=True)
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, pw.k), jnp.float32)
    y_k = jax.jit(lambda x, pw: engine.matmul(x, pw, cfg_ker))(x, pw)
    y_r = jax.jit(lambda x, pw: engine.matmul_reference(x, pw, cfg_ref))(
        x, pw)
    y_k, y_r = np.asarray(y_k), np.asarray(y_r)
    check(y_k.shape == y_r.shape == (rows, pw.n),
          f"shapes {y_k.shape} / {y_r.shape}")
    check(np.isfinite(y_k).all() and np.isfinite(y_r).all(),
          "non-finite outputs")
    diff = float(np.abs(y_k - y_r).max())
    scale = float(np.abs(y_r).max())
    tol = 1e-4 * max(1.0, scale)
    print(f"[phase 2] {name} kernel vs reference on {rows} rows: max "
          f"|diff| {diff!r} (max |y| {scale!r}, tol {tol!r})", flush=True)
    check(diff <= tol, f"kernel and reference disagree: {diff} > {tol}")


def phase_crossbar(d0):
    full = get_config(ARCH)
    cfg = dataclasses.replace(
        full, n_layers=2, backend="crossbar",
        xbar=dataclasses.replace(full.xbar, use_kernel=True))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    reg = obs.registry()
    ref0 = reg.total("crossstack_dispatch_total", path="reference")
    ker0 = reg.total("crossstack_dispatch_total", path="kernel")
    t0 = time.perf_counter()
    sched = BatchScheduler(model, params, n_slots=SLOTS, max_len=64,
                           page_size=PAGE_SIZE)
    ex = model.executor
    jax.block_until_ready(ex.resident_tiles("A"))
    program_s = time.perf_counter() - t0
    print(f"[phase 2] qwen3-4b crossbar: {cfg.n_layers} layers at "
          f"published widths; {ex.n_resident} weight grids programmed in "
          f"{program_s:.2f} s; tiles {tree_bytes(ex.resident_tiles('A'))} "
          f"B; params {tree_bytes(params)} B", flush=True)
    reqs = make_requests(cfg.vocab, 4, 32, 8, seed=2)
    done, first_s, rest_s, rest_tokens = serve(sched, reqs)
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} completed")
    for r in reqs:
        check(r.done and len(r.out) == 8,
              f"request {r.rid} emitted {len(r.out)} tokens")
    n_ker = reg.total("crossstack_dispatch_total", path="kernel") - ker0
    n_ref = reg.total("crossstack_dispatch_total", path="reference") - ref0
    check(n_ker > 0 and n_ref == 0,
          f"{n_ker} kernel and {n_ref} reference dispatches")
    print(f"[phase 2] served {len(done)} requests; decode step lowered "
          f"{int(n_ker)} kernel and {int(n_ref)} reference dispatches; "
          f"first step (compile) {first_s:.2f} s; steady "
          f"{rest_tokens / rest_s:.1f} tok/s (information only)",
          flush=True)
    check_kernel_vs_reference(ex, "blocks.0.attn.wq")
    print(f"[phase 2] peak {d0.memory_stats()['peak_bytes_in_use']} B",
          flush=True)


def main() -> int:
    cache = enable_compile_cache() or os.environ["JAX_COMPILATION_CACHE_DIR"]
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    d0 = phase_device()
    if d0 is None:
        return 1
    print(f"[phase 0] compile cache {cache} "
          f"({'warm' if warm else 'cold'})", flush=True)
    phase_digital(d0)
    gc.collect()
    jax.clear_caches()
    phase_crossbar(d0)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
