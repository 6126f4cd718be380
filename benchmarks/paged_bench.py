"""Paged-serving smoke: the paged KV pool + ragged continuous batching
under a real mixed-length workload, gated in the exit code (the CI
"Paged-serving smoke" step).

Phase 1 — ragged correctness (digital backend, one tenant): a stream of
prompt lengths spanning 8..max_len (>= 4 of the old padded prefill
buckets), admitted continuously, served twice — once from the paged
pool, once from the dense per-slot cache.  Gates:

  * zero dropped requests on both paths,
  * **bit-exact token streams** paged vs dense (the end-to-end half of
    the acceptance; the kernel-level half is test_paged_attention.py),
  * exactly ONE compiled decode closure for the tenant and a ZERO
    retrace delta across the whole mixed-length stream
    (``serve_jit_traces_total`` / ``serve_jit_retraces_total``),
  * page conservation at every step and full reclaim at drain
    (``pages_in_use + pages_free == n_pages``).

Phase 2 — decode throughput: steady-state tokens/s, paged vs dense
(fresh schedulers, warmed closures, interleaved best-of windows), gated
at paged >= 0.7x dense — the page-table gather must not cost the slot
path its throughput (the 30 % headroom absorbs CPU-interpret noise; on
TPU the gather is a kernel prefetch).

Phase 3 — multi-tenant + swap (crossbar backend): A/B multiplexed
serving of the same mixed-length stream with a mid-stream tenant-B
hot-swap.  Gates: zero dropped requests, zero retraces across the swap
window, conservation on every lane's pool, and decode steps served
DURING the write window (admissions kept flowing).

Phase 4 — prefix sharing: four requests with a common 24-token head
and distinct tails, staggered so followers admit after the head
request's prompt pages are written.  Three arms (dense oracle, paged
private, paged ``prefix_share=True``); gates: bit-exact streams on
both paged arms, shared peak ``pages_in_use`` STRICTLY below private,
shared pages aliased > 0, conservation every step, full reclaim at
drain, zero retrace delta.

Phase 5 — QoS preemption under pool saturation: an 8-page pool holds
two low-QoS residents when two high-QoS requests arrive; preemption
evicts the residents (pages reclaim, recomputable state spills to the
host stub) and replays them through chunked prefill.  Gates: all four
requests complete with ZERO drops, streams bit-exact vs dense,
>= 1 eviction, zero retrace delta, conservation + full reclaim.

Phase 6 — long-context kernel sweep (the two-lane dispatch): the
streamed online-softmax lane vs the gather-scratch lane at the kernel
level over growing windows (16/32/64 pages of 8), full-depth decode
reads.  Hard gates are the STRUCTURAL properties: streamed VMEM
scratch bytes CONSTANT across all windows (the O(page_block) claim —
the ring + online-softmax stats; ``streamed_resident_bytes`` records
the current whole-pool lowering's residency alongside, informational),
bounded-ulp parity (fp32 maxdiff < 1e-5) with stable argmax, and every
streamed call counted as a ``paged_streamed`` dispatch (a failing lane
raises; there is no fallback to count).  The streamed/scratch
throughput ratio at the longest window is soft-gated at
>= _LONGCTX_RATIO_GATE (0.9):
both lanes are interpret-mode wall clocks on a shared CPU runner, so a
hard 1.0 gate flaked on scheduler jitter unrelated to correctness —
the full ratio is still recorded per window in BENCH_paged.json.

CLI: ``python benchmarks/paged_bench.py --json BENCH_paged.json`` (exits
nonzero if any gate fails).
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

from repro import obs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.engine import EngineConfig  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve.engine import BatchScheduler, Request  # noqa: E402
from repro.serve.hotswap import finetune_delta  # noqa: E402

_XBAR = EngineConfig(tile_rows=64, tile_cols=128, mode="deepnet",
                     quant=QuantConfig(w_bits=4, in_bits=10, adc_bits=10))

_N_SLOTS, _MAX_LEN, _PAGE_SIZE = 3, 64, 8
# spans the old 8/16/32/64 padded buckets
_PLENS = (8, 13, 22, 35, 50, 62)
_THROUGHPUT_GATE = 0.7
# soft margin on the longctx streamed/scratch wall-clock ratio: timing
# noise between two CPU-interpret lanes must not flip CI (the
# structural gates — VMEM constancy, parity, argmax, dispatch counts —
# stay hard)
_LONGCTX_RATIO_GATE = 0.9


def _digital_cfg():
    return get_config("qwen3_4b", smoke=True)


def _crossbar_cfg():
    return dataclasses.replace(_digital_cfg(), backend="crossbar",
                               xbar=_XBAR)


def _prompt(rid, vocab, plen):
    return jax.random.randint(jax.random.PRNGKey(rid), (plen,), 0,
                              vocab - 1).astype(jnp.int32)


def _serve_reqs(sched, reqs, submit_at):
    """Submit ``reqs[i]`` at decode step ``submit_at[i]`` and drain;
    returns ({rid: tokens}, steps, conservation_held, peak pages in
    use across all lanes).  Staggering matters for the sharing phase:
    a follower can only alias prompt pages the head request has
    already written (and still holds)."""
    done, steps, conserved, peak = {}, 0, True, 0
    while len(done) < len(reqs) and steps < 2000:
        for r, t in zip(reqs, submit_at):
            if t == steps:
                sched.submit(r)
        for r in sched.step():
            done[r.rid] = list(r.out)
        in_use = 0
        for rep in sched.kv_report().values():
            conserved = conserved and rep["conservation_ok"]
            in_use += rep["pages_in_use"]
        peak = max(peak, in_use)
        steps += 1
    return done, steps, conserved, peak


def _serve_stream(sched, vocab, plens, max_new, model_id="A", rid0=0,
                  trickle=2, on_step=None):
    """Admit ``plens`` continuously (one submit every ``trickle`` steps)
    and drain; returns ({rid: tokens}, steps, conservation_held)."""
    pending = [(rid0 + i, p) for i, p in enumerate(plens)]
    done, steps, conserved = {}, 0, True
    while (len(done) < len(plens)) and steps < 1000:
        if pending and steps % trickle == 0:
            rid, plen = pending.pop(0)
            sched.submit(Request(rid=rid, prompt=_prompt(rid, vocab, plen),
                                 max_new=max_new, model_id=model_id))
        for r in sched.step():
            done[r.rid] = list(r.out)
        for rep in sched.kv_report().values():
            conserved = conserved and rep["conservation_ok"]
        if on_step is not None:
            on_step(steps)
        steps += 1
    return done, steps, conserved


def _ragged_phase(max_new):
    """Paged vs dense over the mixed-length stream (digital backend)."""
    cfg = _digital_cfg()
    reg = obs.registry()
    out = {}
    for kv in ("paged", "dense"):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        traces0 = reg.total("serve_jit_traces_total", closure="decode")
        retr0 = reg.total("serve_jit_retraces_total")
        sched = BatchScheduler(model, params, _N_SLOTS, _MAX_LEN, kv=kv,
                               page_size=_PAGE_SIZE)
        done, steps, conserved = _serve_stream(sched, cfg.vocab, _PLENS,
                                               max_new)
        pool_rep = sched.kv_report().get("A", {})
        out[kv] = {
            "streams": done,
            "completed": len(done),
            "steps": steps,
            "closures_traced": reg.total("serve_jit_traces_total",
                                         closure="decode") - traces0,
            "retrace_delta": reg.total("serve_jit_retraces_total") - retr0,
            "conservation_every_step": conserved,
            "pages_in_use_at_drain": pool_rep.get("pages_in_use", 0),
        }
    return out


def _throughput_phase(steps, repeats):
    """Steady-state decode tokens/s, paged vs dense, interleaved timed
    windows so machine drift hits both arms equally."""
    cfg = _digital_cfg()
    scheds = {}
    for kv in ("dense", "paged"):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = BatchScheduler(model, params, _N_SLOTS, _MAX_LEN, kv=kv,
                               page_size=_PAGE_SIZE)
        budget = (repeats + 2) * steps + 8
        for rid in range(_N_SLOTS):
            sched.submit(Request(rid=rid,
                                 prompt=_prompt(rid, cfg.vocab, 6),
                                 max_new=budget))
        for _ in range(4):      # admission chunks + decode warm
            sched.step()
        scheds[kv] = sched
    best = {"dense": 0.0, "paged": 0.0}
    for _ in range(repeats):
        for kv, sched in scheds.items():
            lane = sched._lanes["A"]
            tok0 = lane.tokens_served
            t0 = time.perf_counter()
            for _ in range(steps):
                sched.step()
            jax.block_until_ready(lane.cache["layers"]["len"])
            dt = time.perf_counter() - t0
            best[kv] = max(best[kv], (lane.tokens_served - tok0) / dt)
    return best["dense"], best["paged"]


def _swap_phase(max_new):
    """A/B multiplexed mixed-length stream with a mid-stream tenant-B
    hot-swap over the paged pool."""
    cfg = _crossbar_cfg()
    reg = obs.registry()
    model = build_model(cfg)
    params_a = model.init(jax.random.PRNGKey(0))
    params_b = finetune_delta(params_a, scale=0.04, seed=11)
    params_b2 = finetune_delta(params_a, scale=0.07, seed=23)
    sched = BatchScheduler(model, params_a, _N_SLOTS, _MAX_LEN,
                           tenants={"A": params_a, "B": params_b},
                           page_size=_PAGE_SIZE)
    n = len(_PLENS)
    for i, plen in enumerate(_PLENS):
        sched.submit(Request(rid=i, prompt=_prompt(i, cfg.vocab, plen),
                             max_new=max_new, model_id="A"))
        sched.submit(Request(rid=100 + i,
                             prompt=_prompt(100 + i, cfg.vocab, plen),
                             max_new=max_new, model_id="B"))
    for _ in range(3):
        sched.step()
    retr0 = reg.total("serve_jit_retraces_total")
    sched.begin_hot_swap(params_b2, chunks_per_step=4, tenant="B")
    done, steps, conserved = {}, 0, True
    while (len(done) < 2 * n or sched.swap_in_flight) and steps < 1000:
        for r in sched.step():
            done[r.rid] = list(r.out)
        for rep in sched.kv_report().values():
            conserved = conserved and rep["conservation_ok"]
        steps += 1
    swap_rep = sched.swap_history[0] if sched.swap_history else {}
    pools = sched.kv_report()
    return {
        "completed": len(done),
        "expected": 2 * n,
        "steps": steps,
        "retraces_across_swap_window":
            reg.total("serve_jit_retraces_total") - retr0,
        "swap_lifecycle": swap_rep.get("swap_mode"),
        "swap_decode_steps_during":
            swap_rep.get("decode_steps_during_swap", 0),
        "conservation_every_step": conserved,
        "pages_in_use_at_drain": sum(p["pages_in_use"]
                                     for p in pools.values()),
        "pools": pools,
    }


def _prefix_phase():
    """Shared-prefix workload, three arms: dense (the bit-exactness
    oracle), paged-private, and paged with --prefix-share.  Four
    requests carry the same 24-token head (a shared system prompt) and
    distinct 4-token tails, staggered so the head request's prompt
    pages are fully written before any follower admits.  The shared
    arm must serve the identical streams from strictly fewer peak
    pages."""
    cfg = _digital_cfg()
    head = _prompt(7000, cfg.vocab, 24)
    prompts = [jnp.concatenate([head, _prompt(7100 + i, cfg.vocab, 4)])
               for i in range(4)]
    # head admits at 0 and registers after ceil(28/chunk=4)=7 prefill
    # steps; followers trail it and each other
    submit_at = [0, 8, 10, 12]
    max_new = 4
    reg = obs.registry()
    arms = {}
    for arm, kv, share in (("dense", "dense", False),
                           ("private", "paged", False),
                           ("shared", "paged", True)):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        retr0 = reg.total("serve_jit_retraces_total")
        sched = BatchScheduler(model, params, 4, 32, kv=kv,
                               page_size=_PAGE_SIZE, prefix_share=share)
        reqs = [Request(rid=i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        done, steps, conserved, peak = _serve_reqs(sched, reqs, submit_at)
        arms[arm] = {
            "streams": done,
            "completed": len(done),
            "steps": steps,
            "peak_pages_in_use": peak,
            "conservation_every_step": conserved,
            "retrace_delta": reg.total("serve_jit_retraces_total") - retr0,
            "pages_in_use_at_drain": sum(
                rep["pages_in_use"]
                for rep in sched.kv_report().values()),
        }
        if share:
            arms[arm]["pages_shared_total"] = int(
                sched.metrics.total("serve_kv_pages_shared_total"))
            arms[arm]["shared_tokens_total"] = int(
                sched.metrics.total("serve_kv_shared_tokens_total"))
            arms[arm]["cow_total"] = int(
                sched.metrics.total("serve_kv_cow_total"))
    bit_exact = (arms["shared"]["streams"] == arms["dense"]["streams"]
                 and arms["private"]["streams"] == arms["dense"]["streams"])
    for a in arms.values():
        del a["streams"]
    return {
        "n_requests": len(prompts),
        "common_head_tokens": 24,
        "bit_exact_vs_dense": bool(bit_exact),
        "peak_pages_private": arms["private"]["peak_pages_in_use"],
        "peak_pages_shared": arms["shared"]["peak_pages_in_use"],
        "arms": arms,
    }


def _preempt_phase():
    """Pool-saturation preemption: two low-QoS requests fill a tight
    8-page pool; two high-QoS requests arrive behind them.  With
    --preemption the scheduler evicts the low-QoS residents (pages
    reclaim, state spills to the host stub) and replays them through
    chunked prefill after the high-QoS pair drains — every stream
    bit-exact vs the dense oracle, zero drops, zero retraces."""
    cfg = _digital_cfg()
    prompts = [_prompt(8000 + i, cfg.vocab, 20) for i in range(4)]
    qos = (1.0, 1.0, 4.0, 4.0)
    submit_at = [0, 0, 8, 8]
    max_new = 5
    reg = obs.registry()
    arms = {}
    for arm in ("dense", "preempt"):
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        retr0 = reg.total("serve_jit_retraces_total")
        if arm == "dense":
            sched = BatchScheduler(model, params, 4, 32, kv="dense")
        else:
            sched = BatchScheduler(model, params, 3, 32, kv="paged",
                                   page_size=_PAGE_SIZE, kv_pages=8,
                                   preemption=True)
        reqs = [Request(rid=i, prompt=p, max_new=max_new, qos=q)
                for i, (p, q) in enumerate(zip(prompts, qos))]
        done, steps, conserved, peak = _serve_reqs(sched, reqs, submit_at)
        arms[arm] = {
            "streams": done,
            "completed": len(done),
            "steps": steps,
            "conservation_every_step": conserved,
            "retrace_delta": reg.total("serve_jit_retraces_total") - retr0,
            "pages_in_use_at_drain": sum(
                rep["pages_in_use"]
                for rep in sched.kv_report().values()),
        }
        if arm == "preempt":
            arms[arm]["preemptions_total"] = int(
                sched.metrics.total("serve_preemptions_total"))
            arms[arm]["readmissions"] = sum(r.preemptions for r in reqs)
    bit_exact = arms["preempt"]["streams"] == arms["dense"]["streams"]
    for a in arms.values():
        del a["streams"]
    return {
        "n_requests": len(prompts),
        "kv_pages": 8,
        "bit_exact_vs_dense": bool(bit_exact),
        "preemptions": arms["preempt"]["preemptions_total"],
        "arms": arms,
    }


def _longctx_phase(windows=(16, 32, 64), repeats=3):
    """Kernel-level two-lane sweep over growing page-table widths: every
    row reads its full window (the decode worst case), both lanes timed
    back-to-back on identical operands."""
    import numpy as np

    from repro.kernels.paged_attention import (
        paged_attention as paged_op, paged_path_calls,
        scratch_lane_vmem_bytes, streamed_lane_resident_bytes,
        streamed_lane_vmem_bytes)

    b, sq, hq, kv, hd, ps, bp = 4, 1, 8, 2, 64, _PAGE_SIZE, 16
    base = dict(paged_path_calls)
    rows, argmax_stable = [], True
    for p_seq in windows:
        key = jax.random.PRNGKey(p_seq)
        kq, kk, kvk = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, sq, hq, hd), jnp.float32)
        n_pages = b * p_seq + 1
        kp = jax.random.normal(kk, (n_pages, ps, kv, hd), jnp.float32)
        vp = jax.random.normal(kvk, (n_pages, ps, kv, hd), jnp.float32)
        pt = (jnp.arange(1, b * p_seq + 1, dtype=jnp.int32)
              .reshape(b, p_seq))
        kv_len = jnp.full((b,), p_seq * ps, jnp.int32)
        q_off = kv_len - sq

        def run(lane):
            return paged_op(q, kp, vp, pt, kv_len, q_off, lane=lane,
                            block_pages=bp)

        timed = {}
        outs = {}
        for lane in ("streamed", "scratch"):
            outs[lane] = jax.block_until_ready(run(lane))   # trace + warm
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run(lane))
                best = min(best, time.perf_counter() - t0)
            timed[lane] = best * 1e6
        d_str = np.asarray(outs["streamed"], np.float32)
        d_scr = np.asarray(outs["scratch"], np.float32)
        argmax_stable = argmax_stable and bool(
            (d_str.reshape(b, -1).argmax(-1)
             == d_scr.reshape(b, -1).argmax(-1)).all())
        rows.append({
            "pages": p_seq,
            "tokens": p_seq * ps,
            "streamed_us_per_call": timed["streamed"],
            "scratch_us_per_call": timed["scratch"],
            "streamed_over_scratch":
                timed["scratch"] / max(timed["streamed"], 1e-9),
            "parity_maxdiff": float(np.abs(d_str - d_scr).max()),
            "scratch_lane_vmem_bytes":
                scratch_lane_vmem_bytes(p_seq, ps, kv, hd, jnp.float32),
            "streamed_lane_vmem_bytes":
                streamed_lane_vmem_bytes(b, sq, hq, kv, hd, p_seq, ps, bp,
                                         jnp.float32),
            # honest residency of the CURRENT whole-pool lowering (grows
            # with the pool until the TPU port's per-block DMA lands);
            # informational, not gated — the constancy gate is about the
            # scratch working set above
            "streamed_resident_bytes":
                streamed_lane_resident_bytes(b, sq, hq, kv, hd, p_seq, ps,
                                             bp, n_pages, jnp.float32),
        })
    calls = {k: paged_path_calls[k] - base[k] for k in base}
    return {
        "batch": b, "page_size": ps, "block_pages": bp,
        "kv_heads": kv, "head_dim": hd,
        "windows": rows,
        "streamed_vmem_constant":
            len({r["streamed_lane_vmem_bytes"] for r in rows}) == 1,
        "scratch_vmem_growth":
            rows[-1]["scratch_lane_vmem_bytes"]
            / rows[0]["scratch_lane_vmem_bytes"],
        "ratio_at_longest": rows[-1]["streamed_over_scratch"],
        "parity_maxdiff": max(r["parity_maxdiff"] for r in rows),
        "argmax_stable": argmax_stable,
        "dispatch_calls": calls,
    }


def bench_paged(quick: bool = False):
    max_new = 5 if quick else 10
    steps, repeats = (25, 3) if quick else (50, 5)

    ragged = _ragged_phase(max_new)
    bit_exact = ragged["paged"]["streams"] == ragged["dense"]["streams"]
    thr_dense, thr_paged = _throughput_phase(steps, repeats)
    swap = _swap_phase(max_new)
    prefix = _prefix_phase()
    preempt = _preempt_phase()
    longctx = _longctx_phase(windows=(16, 32, 64) if quick
                             else (16, 32, 64, 128),
                             repeats=5 if quick else 9)

    return {
        "us_per_call": 0.0,
        "n_requests": len(_PLENS),
        "prompt_lens": list(_PLENS),
        "former_buckets_spanned": 4,
        "paged_completed": ragged["paged"]["completed"],
        "dense_completed": ragged["dense"]["completed"],
        "paged_vs_dense_bit_exact": bool(bit_exact),
        "paged_closures_traced": ragged["paged"]["closures_traced"],
        "paged_retrace_delta": ragged["paged"]["retrace_delta"],
        "dense_closures_traced": ragged["dense"]["closures_traced"],
        "dense_retrace_delta": ragged["dense"]["retrace_delta"],
        "page_conservation_every_step":
            bool(ragged["paged"]["conservation_every_step"]),
        "pages_in_use_at_drain": ragged["paged"]["pages_in_use_at_drain"],
        "decode_tok_per_s_dense": thr_dense,
        "decode_tok_per_s_paged": thr_paged,
        "paged_over_dense_throughput": thr_paged / max(thr_dense, 1e-12),
        "throughput_gate": _THROUGHPUT_GATE,
        "swap": swap,
        "prefix_share": prefix,
        "preemption": preempt,
        "longctx": longctx,
    }


def accepted(res) -> bool:
    swap = res["swap"]
    pfx = res["prefix_share"]
    pre = res["preemption"]
    lc = res["longctx"]
    return (res["paged_completed"] == res["n_requests"]
            and res["dense_completed"] == res["n_requests"]
            and res["paged_vs_dense_bit_exact"]
            and res["paged_closures_traced"] == 1
            and res["paged_retrace_delta"] == 0
            and res["page_conservation_every_step"]
            and res["pages_in_use_at_drain"] == 0
            and res["paged_over_dense_throughput"]
            >= res["throughput_gate"]
            and swap["completed"] == swap["expected"]
            and swap["retraces_across_swap_window"] == 0
            and swap["swap_decode_steps_during"] > 0
            and swap["conservation_every_step"]
            and swap["pages_in_use_at_drain"] == 0
            # sharing: identical streams from strictly fewer peak pages
            and all(a["completed"] == pfx["n_requests"]
                    for a in pfx["arms"].values())
            and pfx["bit_exact_vs_dense"]
            and pfx["peak_pages_shared"] < pfx["peak_pages_private"]
            and pfx["arms"]["shared"]["pages_shared_total"] > 0
            and all(a["conservation_every_step"]
                    and a["retrace_delta"] == 0
                    and a["pages_in_use_at_drain"] == 0
                    for a in pfx["arms"].values())
            # preemption: saturation resolves with zero drops
            and all(a["completed"] == pre["n_requests"]
                    for a in pre["arms"].values())
            and pre["bit_exact_vs_dense"]
            and pre["preemptions"] >= 1
            and all(a["conservation_every_step"]
                    and a["retrace_delta"] == 0
                    and a["pages_in_use_at_drain"] == 0
                    for a in pre["arms"].values())
            # long-context two-lane sweep: structural gates hard
            # (constant VMEM scratch, bounded-ulp parity, stable argmax,
            # streamed dispatches counted); the wall-clock ratio gets a noise
            # margin — two interpret-mode lanes on a shared CPU runner
            # jitter for reasons unrelated to correctness
            and lc["ratio_at_longest"] >= _LONGCTX_RATIO_GATE
            and lc["streamed_vmem_constant"]
            and lc["parity_maxdiff"] < 1e-5
            and lc["argmax_stable"]
            and lc["dispatch_calls"]["paged_streamed"] > 0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_paged.json")
    args = ap.parse_args(argv)
    res = bench_paged(quick=True)
    print("name,us_per_call,derived")
    derived = {k: v for k, v in res.items() if k != "us_per_call"}
    print(f"paged_serving,{res['us_per_call']:.1f},"
          f"{json.dumps(derived, default=float)}")
    from benchmarks.meta import append_trajectory, write_stamped
    results = {"paged_serving": res}
    meta = write_stamped(results, args.json, lane="paged-smoke")
    append_trajectory(meta, results)
    print(f"# wrote {args.json} (sha={meta['git_sha'][:12]})")
    ok = accepted(res)
    swap = res["swap"]
    print(f"# acceptance: paged==dense bit-exact "
          f"({res['paged_vs_dense_bit_exact']}), closures traced "
          f"{res['paged_closures_traced']} (want 1), retrace delta "
          f"{res['paged_retrace_delta']} (want 0), conservation every "
          f"step ({res['page_conservation_every_step']}), pages leaked "
          f"at drain {res['pages_in_use_at_drain']} (want 0), "
          f"throughput paged/dense "
          f"{res['paged_over_dense_throughput']:.2f}x (gate >= "
          f"{res['throughput_gate']}), swap: "
          f"{swap['completed']}/{swap['expected']} done with "
          f"{swap['retraces_across_swap_window']} retraces and "
          f"{swap['swap_decode_steps_during']} decode steps in-window")
    pfx, pre = res["prefix_share"], res["preemption"]
    print(f"# prefix-share: bit-exact ({pfx['bit_exact_vs_dense']}), "
          f"peak pages {pfx['peak_pages_shared']} shared vs "
          f"{pfx['peak_pages_private']} private (want <), "
          f"{pfx['arms']['shared']['pages_shared_total']} pages aliased, "
          f"{pfx['arms']['shared']['shared_tokens_total']} prompt tokens "
          f"skipped, {pfx['arms']['shared']['cow_total']} COW copies; "
          f"preemption: bit-exact ({pre['bit_exact_vs_dense']}), "
          f"{pre['preemptions']} evictions, "
          f"{pre['arms']['preempt']['completed']}/{pre['n_requests']} "
          f"completed with 0 drops")
    lc = res["longctx"]
    print(f"# long-context: streamed/scratch "
          f"{lc['ratio_at_longest']:.2f}x at "
          f"{lc['windows'][-1]['tokens']} tokens (soft gate >= "
          f"{_LONGCTX_RATIO_GATE}), streamed VMEM scratch constant "
          f"({lc['streamed_vmem_constant']}: "
          f"{lc['windows'][0]['streamed_lane_vmem_bytes']} B; resident "
          f"{lc['windows'][-1]['streamed_resident_bytes']} B under the "
          f"whole-pool lowering) vs scratch "
          f"x{lc['scratch_vmem_growth']:.0f} growth, parity maxdiff "
          f"{lc['parity_maxdiff']:.2e} (gate < 1e-5), argmax stable "
          f"({lc['argmax_stable']}), streamed dispatches "
          f"{lc['dispatch_calls']['paged_streamed']} (want > 0)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
