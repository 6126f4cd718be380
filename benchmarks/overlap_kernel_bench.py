"""Overlap-window decode: Pallas kernel path vs the reference scan.

The deep-net mode's whole point is that reads keep streaming while the
twin plane programs (paper §III-B, Fig. 3c) — so the decode matmuls
issued DURING a hot-swap are the serving system's hot path.  Before this
bench's PR, ``engine.matmul`` abandoned the Pallas kernel whenever the
write-plane leakage was nonzero, i.e. precisely inside the overlap
window; now the leakage is a traced kernel operand and ``use_kernel``
traffic stays on the kernel path throughout.

The measured loop: program the smoke transformer onto crossbar tiles,
serve until steady state, open a chunked hot-swap (the window stays open
while chunks program between steps), and time decode inside the window
under both engine configs:

  * **kernel**    — ``use_kernel=True``: Pallas crossbar MAC with the
    leak fused pre-ADC (interpret mode on CPU; the real win is on TPU).
  * **reference** — the ``lax.scan`` over (pulse, slice) pairs that
    overlap reads used to fall back to.

Acceptance (exit code, enforced by the CI "Overlap-kernel smoke" step):

  1. the kernel-path overlap decode step is faster than the reference
     scan's, and
  2. the kernel policy's serving closures never dispatched the reference
     path (``engine.path_calls`` snapshot) — no silent fallback in the
     overlap window.

CLI: ``python benchmarks/overlap_kernel_bench.py --json
BENCH_overlap_kernel.json``
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core.engine import EngineConfig  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.engine import BatchScheduler, Request  # noqa: E402
from repro.serve.hotswap import finetune_delta  # noqa: E402

# the paper's operating point (10-bit reads), leakage modeled: overlap
# decode carries the write plane's common-mode term through the ADC
_XBAR = EngineConfig(tile_rows=64, tile_cols=128, mode="deepnet",
                     quant=QuantConfig(w_bits=4, in_bits=10, adc_bits=10),
                     swap_leakage=True)


def _scheduler(use_kernel: bool, n_slots: int, max_len: int):
    xbar = dataclasses.replace(_XBAR, use_kernel=use_kernel)
    cfg = dataclasses.replace(get_config("qwen3_4b", smoke=True),
                              backend="crossbar", xbar=xbar)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = BatchScheduler(model, params, n_slots=n_slots, max_len=max_len)
    for rid in range(n_slots):
        p = jax.random.randint(jax.random.PRNGKey(rid), (6,), 0,
                               model.cfg.vocab - 1).astype(jnp.int32)
        sched.submit(Request(rid=rid, prompt=p, max_new=max_len))
    return model, params, sched


def _time_overlap_decode(use_kernel: bool, warmup_steps: int,
                         timed_calls: int):
    """Decode-step wall time measured INSIDE an open swap window."""
    model, params, sched = _scheduler(use_kernel, n_slots=2, max_len=64)
    calls_before = dict(eng.path_calls)
    for _ in range(warmup_steps):
        sched.step()

    # open the window; 1 chunk/step keeps it open while we measure
    sched.begin_hot_swap(finetune_delta(params), chunks_per_step=1)
    sched.step()                       # first in-window step (swap active)
    assert sched.swap_in_flight, "swap closed before the overlap window"
    ex = model.executor
    leak = ex.current_leak_codes()
    assert float(leak) > 0.0, "overlap window must carry nonzero leakage"

    # the raw decode closure, mid-window: this is the hot path the
    # tentpole moves onto the kernel (swap bookkeeping excluded so the
    # number isolates kernel-vs-reference arithmetic)
    lane = sched._lanes["A"]
    cache = lane.cache
    toks = jnp.zeros((lane.width, sched.chunk), jnp.int32)
    toks = toks.at[:, 0].set(jnp.asarray(
        [r.out[-1] if r is not None and r.out else 0
         for r in lane.slots], jnp.int32))
    m = jnp.asarray([1 if r is not None else 0 for r in lane.slots],
                    jnp.int32)
    tiles = ex.resident_tiles("A")
    tok, cache = lane.decode(lane.params, tiles, toks, cache, m, leak)
    jax.block_until_ready(tok)
    t0 = time.perf_counter()
    for _ in range(timed_calls):
        tok, cache = lane.decode(lane.params, tiles, toks, cache, m,
                                 leak)
    jax.block_until_ready(tok)
    per_decode = (time.perf_counter() - t0) / timed_calls
    lane.cache = cache

    # end-to-end step() time through the rest of the window (decode +
    # chunk programming + write-verify), then drain
    in_window = 0
    t0 = time.perf_counter()
    while sched.swap_in_flight:
        sched.step()
        in_window += 1
    per_step = (time.perf_counter() - t0) / max(in_window, 1)
    calls = {k: eng.path_calls[k] - calls_before[k]
             for k in eng.path_calls}
    return {
        "per_overlap_decode_s": per_decode,
        "per_window_step_s": per_step,
        "window_steps_measured": in_window,
        "matmul_dispatches": calls,
        "leak_codes": float(leak),
        "swap_history_policies": [r["policy"] for r in sched.swap_history],
    }


def bench_overlap_kernel(quick: bool = False):
    """Returns the kernel-vs-reference overlap figures + acceptance flags."""
    warmup = 2 if quick else 4
    timed = 6 if quick else 16
    kern = _time_overlap_decode(use_kernel=True, warmup_steps=warmup,
                                timed_calls=timed)
    ref = _time_overlap_decode(use_kernel=False, warmup_steps=warmup,
                               timed_calls=timed)

    speedup = (ref["per_overlap_decode_s"]
               / max(kern["per_overlap_decode_s"], 1e-12))
    # the kernel policy's closures must have dispatched ONLY the kernel:
    # any "reference" dispatch means an overlap (or steady) decode fell
    # back to the scan — the regression this bench exists to catch
    no_fallback = (kern["matmul_dispatches"]["reference"] == 0
                   and kern["matmul_dispatches"]["kernel"] > 0)
    return {
        "us_per_call": kern["per_overlap_decode_s"] * 1e6,
        "overlap_decode_kernel_s": kern["per_overlap_decode_s"],
        "overlap_decode_reference_s": ref["per_overlap_decode_s"],
        "overlap_decode_speedup_kernel_vs_reference": speedup,
        "kernel_beats_reference": bool(speedup > 1.0),
        "window_step_kernel_s": kern["per_window_step_s"],
        "window_step_reference_s": ref["per_window_step_s"],
        "kernel_policy_dispatches": kern["matmul_dispatches"],
        "no_silent_reference_fallback": bool(no_fallback),
        "leak_codes_during_window": kern["leak_codes"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_overlap_kernel.json")
    args = ap.parse_args(argv)
    res = bench_overlap_kernel(quick=True)
    print("name,us_per_call,derived")
    derived = {k: v for k, v in res.items() if k != "us_per_call"}
    print(f"overlap_kernel,{res['us_per_call']:.1f},"
          f"{json.dumps(derived, default=float)}")
    from benchmarks.meta import append_trajectory, write_stamped
    results = {"overlap_kernel": res}
    meta = write_stamped(results, args.json, lane="overlap-kernel-smoke")
    append_trajectory(meta, results)
    print(f"# wrote {args.json} (sha={meta['git_sha'][:12]})")
    ok = res["kernel_beats_reference"] and res["no_silent_reference_fallback"]
    print(f"# acceptance: overlap decode kernel vs reference "
          f"{res['overlap_decode_speedup_kernel_vs_reference']:.2f}x "
          f"(>1x: {res['kernel_beats_reference']}), no reference fallback "
          f"in kernel policy: {res['no_silent_reference_fallback']} "
          f"(dispatches: {res['kernel_policy_dispatches']})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
