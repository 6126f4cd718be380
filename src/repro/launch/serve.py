"""Serving driver: ``python -m repro.launch.serve --arch qwen3-4b --smoke``.

Continuous-batching decode over the BatchScheduler with synthetic prompts;
on a fleet the same file serves the full config on the production mesh
(params would come from checkpoint/manager.py instead of random init).

``--backend crossbar`` serves every linear layer from weight-resident
crossbar tiles: weights are programmed once at scheduler construction and
every decode step is a read-only bit-serial MAC (core/executor.py).

KV storage defaults to the block-paged pool (``--kv paged``): fixed
``--page-size`` pages, per-slot page tables, free-list alloc/reclaim;
prompts of any length stream into the running batch as ``--chunk``-token
prefill chunks through ONE compiled closure per tenant (no length
buckets, zero re-traces for any prompt mix).  ``--kv dense`` keeps the
per-slot dense cache — same closure and bit-identical streams.
``--stream-pages N`` routes decode attention through the
block-streamed online-softmax kernel lane once a row's page table is
at least N pages wide (peak VMEM bounded by ``--block-pages``
regardless of window length; bounded-ulp + argmax-stable vs the
default bitwise gather-scratch lane) and prints the per-lane traced
closure counts after the run.

``--hot-swap SPEC`` deploys a second checkpoint under live traffic
(deep-net mode at the serving tier, serve/hotswap.py): the new weights
program onto the write-shadow planes between decode steps and an atomic
flip promotes them with zero dropped requests.  SPEC is ``ft:<scale>``
(the serving params plus a scaled fine-tune delta), ``seed:<int>`` (a
fresh init — e.g. a recalibration sweep), a checkpoint directory
written by checkpoint/manager.py, or ``init`` (the serving params).

``--multiplex SPEC,SPEC[,SPEC...]`` serves N checkpoints from the plane
bank of one crossbar executor (multi-tenant plane multiplexing):
requests round-robin across tenants A, B, C, ..., each tenant decodes
from its own resident plane, and the physical device count is 1.0x a
single deployment's stacks instead of the N.0x dedicated arrays would
burn.  ``--stack-planes N`` sets the bank height (the paper's geometry
is 2; taller stacks host more tenants and/or a free staging plane);
``--qos W,W,...`` gives per-tenant QoS weights driving the scheduler's
slot split and admission order.  Combined with ``--hot-swap``, the swap
targets the LAST tenant: its planes reprogram under the other tenants'
uninterrupted traffic.

``--prefix-share`` turns on refcounted prompt-prefix sharing in the
paged pool: requests whose prompt head token-matches fully-written
pages of a resident request alias those physical pages (per-page
refcounts; copy-on-write when a row would write inside a shared page),
skipping the aliased prefill compute entirely.  ``--common-prefix N``
prepends the same N-token head to every synthetic prompt so the demo
has a shared system prompt to find.  ``--preemption`` adds QoS
preemption: when the pool (or a tenant's budget) saturates, the
lowest-QoS in-flight request is evicted — pages reclaim, the request
re-enters the queue and replays through chunked prefill with a
bit-identical output stream and zero drops.  Both require ``--kv
paged`` and compose with everything below.

``--mode-policy auto|expansion|deepnet|name=mode,...`` makes read mode a
per-weight bank policy (the paper's expansion mode at the serving tier):
expansion-programmed weights fuse two planes into one doubled-input
crossbar — both RE high, cutting worst-case IR drop by ~22% but giving
up the write shadow — while deep-net weights keep overlapped hot-swaps.
``auto`` picks expansion for accuracy-critical layers (attention/head)
and deep-net for swap-heavy MLP mats, scored by the exact nodal IR-drop
solves; the per-layer choices and deltas print via ``mode_report()``.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serve.engine import BatchScheduler, Request


def parse_mode_policy(spec):
    """``--mode-policy`` parsing: ``auto`` | ``expansion`` | ``deepnet``
    | ``name=mode[,name=mode...]`` (names may be dotted fragments like
    ``attn`` or ``blocks.0.mlp.wi``; ``default=<mode>`` covers the rest;
    mapped modes may themselves be ``auto``)."""
    if spec is None:
        return None
    if spec in ("auto", "expansion", "deepnet"):
        return spec
    policy = {}
    for item in spec.split(","):
        name, sep, mode = item.partition("=")
        name, mode = name.strip(), mode.strip()
        if not sep or not name or mode not in ("expansion", "deepnet",
                                               "auto"):
            raise SystemExit(
                f"--mode-policy: bad entry {item!r} (want auto | "
                f"expansion | deepnet | name=mode,... with mode one of "
                f"expansion/deepnet/auto)")
        policy[name] = mode
    return policy


def resolve_swap_params(spec: str, model, params):
    """Checkpoint-spec resolution for ``--hot-swap`` / ``--multiplex``."""
    if spec == "init":
        return params
    if spec.startswith("seed:"):
        try:
            seed = int(spec[5:])
        except ValueError:
            raise SystemExit(f"--hot-swap: {spec!r} needs an integer seed")
        return model.init(jax.random.PRNGKey(seed))
    if spec.startswith("ft:"):
        try:
            scale = float(spec[3:])
        except ValueError:
            raise SystemExit(f"--hot-swap: {spec!r} needs a float scale")
        from repro.serve.hotswap import finetune_delta
        return finetune_delta(params, scale=scale)
    if os.path.isdir(spec):
        from repro.checkpoint.manager import CheckpointManager
        return CheckpointManager(spec).restore(target=params)
    raise SystemExit(f"--hot-swap: unknown spec {spec!r} "
                     f"(want init, ft:<scale>, seed:<int>, or a "
                     f"checkpoint dir)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default="digital",
                    choices=["digital", "crossbar"],
                    help="crossbar = weight-resident tiles, program-once")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--kv", default="paged", choices=["paged", "dense"],
                    help="KV storage: paged = block-paged pool with "
                         "per-slot page tables (serve/kv_pool.py); dense "
                         "= per-slot dense cache (the bit-exactness "
                         "oracle)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (must divide --max-len)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="per-tenant page-pool budget for the "
                         "QoS-weighted split (default: slots * max_len "
                         "/ page_size pages per lane, i.e. no "
                         "oversubscription)")
    ap.add_argument("--chunk", type=int, default=4,
                    help="prompt tokens fed per step while a request "
                         "prefills inside the running decode batch")
    ap.add_argument("--stream-pages", type=int, default=0, metavar="N",
                    help="route paged decode attention through the "
                         "block-streamed online-softmax kernel lane "
                         "whenever a row's page table is >= N pages "
                         "wide (0 = keep the bitwise gather-scratch "
                         "lane; implies the paged Pallas kernel; "
                         "requires --kv paged)")
    ap.add_argument("--block-pages", type=int, default=16, metavar="N",
                    help="pages fetched per streamed attention block "
                         "(clamped to a divisor of the table width)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="refcounted prefix sharing: requests whose "
                         "prompt head matches fully-written pages of a "
                         "resident request alias those pages instead of "
                         "re-filling them (copy-on-write on sub-page "
                         "divergence); requires --kv paged")
    ap.add_argument("--preemption", action="store_true",
                    help="QoS preemption: when the page pool or a "
                         "tenant budget saturates, evict the lowest-QoS "
                         "in-flight request (its pages reclaim; the "
                         "request re-admits via chunked prefill with a "
                         "bit-identical stream); requires --kv paged")
    ap.add_argument("--common-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token head to every "
                         "synthetic prompt (a shared system prompt) so "
                         "--prefix-share has aliasable pages to find")
    ap.add_argument("--stagger", type=int, default=0, metavar="N",
                    help="submit request i at decode step i*N instead "
                         "of all upfront; sharing needs the head "
                         "request's prompt pages written (and still "
                         "resident) before a follower admits")
    ap.add_argument("--hot-swap", default=None, metavar="SPEC",
                    help="second checkpoint to deploy mid-serving "
                         "(ft:<scale> | seed:<int> | checkpoint dir); "
                         "requires --backend crossbar; under --multiplex "
                         "the swap targets the last tenant")
    ap.add_argument("--multiplex", default=None,
                    metavar="SPEC,SPEC[,SPEC...]",
                    help="serve N checkpoints (tenants A,B,C,...) from "
                         "the plane bank of one executor (specs as in "
                         "--hot-swap, plus 'init'); requires --backend "
                         "crossbar and stack-planes >= N")
    ap.add_argument("--stack-planes", type=int, default=None,
                    help="bank height: planes stacked per cell site "
                         "(default: the device model's 2, the paper "
                         "geometry)")
    ap.add_argument("--qos", default=None, metavar="W,W[,W...]",
                    help="per-tenant QoS weights for --multiplex (one "
                         "float per spec, e.g. 2,1,1): weighted slot "
                         "split + admission order in the scheduler")
    ap.add_argument("--mode-policy", default=None, metavar="POLICY",
                    help="per-weight crossbar read mode: auto (IR-drop-"
                         "aware — expansion for attention/head, deep-net "
                         "for swap-heavy MLP), expansion, deepnet, or "
                         "name=mode,... (e.g. head=expansion,default="
                         "auto); requires --backend crossbar")
    ap.add_argument("--tile-rows", type=int, default=None,
                    help="override crossbar tile rows (wordlines per "
                         "plane); expansion fusing pairs row-tiles "
                         "across the two planes, so it needs an even "
                         "count >= 2 per weight — e.g. --tile-rows 32 "
                         "splits the smoke model's d_model=64 weights "
                         "into 2 row-tiles")
    ap.add_argument("--swap-after", type=int, default=None,
                    help="begin the swap once this many requests finished "
                         "(default: half)")
    ap.add_argument("--swap-chunks", type=int, default=8,
                    help="shadow-plane chunks programmed per decode step")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.jsonl",
                    help="write the telemetry trace at exit: one JSON "
                         "object per line — request/swap spans, then "
                         "every metric sample (docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="print a one-line stats banner every N decode "
                         "steps (0 = off)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable scheduler metrics/span collection "
                         "(the overhead-baseline configuration)")
    args = ap.parse_args(argv)
    if args.no_telemetry and (args.metrics_out or args.metrics_interval):
        raise SystemExit("--no-telemetry contradicts --metrics-out / "
                         "--metrics-interval")
    if args.hot_swap and args.backend != "crossbar":
        raise SystemExit("--hot-swap requires --backend crossbar")
    if args.multiplex and args.backend != "crossbar":
        raise SystemExit("--multiplex requires --backend crossbar")
    if args.mode_policy and args.backend != "crossbar":
        raise SystemExit("--mode-policy requires --backend crossbar")
    if (args.prefix_share or args.preemption) and args.kv != "paged":
        raise SystemExit("--prefix-share/--preemption operate on the "
                         "page pool; they require --kv paged")
    if args.stream_pages and args.kv != "paged":
        raise SystemExit("--stream-pages routes the paged-attention "
                         "kernel; it requires --kv paged")
    mode_policy = parse_mode_policy(args.mode_policy)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family in ("encdec", "vlm", "rwkv6", "zamba2"):
        raise SystemExit("scheduler demo targets decoder LMs; "
                         "see examples/serve_batch.py for other families")
    cfg = dataclasses.replace(cfg, backend=args.backend)
    if args.stream_pages:
        cfg = dataclasses.replace(cfg, paged_kernel=True,
                                  paged_stream_pages=args.stream_pages,
                                  paged_block_pages=args.block_pages)
    if args.tile_rows is not None:
        cfg = dataclasses.replace(
            cfg, xbar=dataclasses.replace(cfg.xbar,
                                          tile_rows=args.tile_rows))
    if args.stack_planes is not None:
        from repro.core.device import DeviceConfig
        cfg = dataclasses.replace(
            cfg, xbar=dataclasses.replace(
                cfg.xbar, device=DeviceConfig(
                    stack_planes=args.stack_planes)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    tenants = None
    tenant_ids = ["A"]
    if args.multiplex:
        specs = args.multiplex.split(",")
        if len(specs) < 2:
            raise SystemExit("--multiplex wants >= 2 comma-separated "
                             "specs, e.g. init,ft:0.02 or "
                             "init,ft:0.02,seed:7")
        names = model.executor.tenant_names
        if len(specs) > len(names):
            raise SystemExit(
                f"--multiplex {len(specs)} tenants > {len(names)} plane "
                f"slots; raise --stack-planes to {len(specs)}")
        tenant_ids = list(names[:len(specs)])
        weights = [1.0] * len(specs)
        if args.qos:
            try:
                weights = [float(w) for w in args.qos.split(",")]
            except ValueError:
                raise SystemExit(f"--qos: {args.qos!r} wants floats")
            if len(weights) != len(specs):
                raise SystemExit(f"--qos wants one weight per "
                                 f"--multiplex spec ({len(specs)})")
        tenants = {
            t: (resolve_swap_params(s, model, params), w)
            for t, s, w in zip(tenant_ids, specs, weights)}
        params = tenants["A"][0]
    elif args.qos:
        raise SystemExit("--qos only applies under --multiplex")
    sched = BatchScheduler(model, params, n_slots=args.slots,
                           max_len=args.max_len, tenants=tenants,
                           mode_policy=mode_policy,
                           telemetry=not args.no_telemetry,
                           kv=args.kv, page_size=args.page_size,
                           kv_pages=args.kv_pages, chunk=args.chunk,
                           prefix_share=args.prefix_share,
                           preemption=args.preemption)
    if args.kv == "paged":
        pools = sched.kv_report()
        desc = ", ".join(f"{t}:{r['n_pages']}p" for t, r in pools.items())
        print(f"paged KV: page_size={args.page_size} tokens, pools "
              f"[{desc}], chunk={args.chunk} prompt tokens/step")
    if model.executor is not None:
        ex = model.executor
        print(f"crossbar backend: {ex.n_resident} resident weight grids, "
              f"{ex.n_devices} programmed devices/plane, "
              f"{ex.stack_planes}-plane banks "
              f"({ex.n_devices_physical} physical devices; "
              f"programmed={ex.stats['programmed']}, "
              f"cache_hits={ex.stats['cache_hits']})")
        for t, entry in ex.residency().items():
            m = entry["modes"]
            print(f"  resident tenant {t}: v{entry['version']} "
                  f"fingerprint={entry['fingerprint']} "
                  f"modes={m['expansion']} expansion / "
                  f"{m['deepnet']} deep-net")
        if mode_policy is not None:
            rep = sched.mode_report()
            agg = rep["aggregate"]
            print(f"mode policy: {agg['n_expansion']} expansion-fused / "
                  f"{agg['n_deepnet']} deep-net weight grids; mean "
                  f"worst-case IR-drop reduction on expansion layers "
                  f"{agg['ir_drop_reduction_expansion'] * 100:.1f}% "
                  f"(paper: 22%)")
            for name, entry in list(rep["layers"].items())[:6]:
                gain = (f"-{entry['ir_drop_reduction'] * 100:.1f}% IR drop"
                        if entry["mode"] == "expansion" else
                        f"-{entry['ir_drop_reduction'] * 100:.1f}% if fused")
                print(f"  {name}: {entry['mode']:9s} "
                      f"dev {entry['dev_deepnet']:.4f} -> "
                      f"{entry['dev_expansion']:.4f} ({gain})  "
                      f"[{entry['reason']}]")
            if len(rep["layers"]) > 6:
                print(f"  ... {len(rep['layers']) - 6} more weight grids "
                      f"(sched.mode_report() for the full table)")
    key = jax.random.PRNGKey(1)
    head = jax.random.randint(jax.random.PRNGKey(2),
                              (args.common_prefix,), 0,
                              cfg.vocab - 1).astype(jnp.int32)
    reqs = []
    for rid in range(args.requests):
        key, k = jax.random.split(key)
        prompt = jax.random.randint(k, (args.prompt_len,), 0,
                                    cfg.vocab - 1).astype(jnp.int32)
        if args.common_prefix:
            # a shared system prompt: identical head, distinct tails
            prompt = jnp.concatenate([head, prompt])
        # multiplexed serving round-robins the tenants' token streams
        model_id = tenant_ids[rid % len(tenant_ids)]
        # under --preemption the later half arrives at a higher QoS so a
        # saturated pool demonstrates eviction + re-admission
        qos = 2.0 if args.preemption and rid >= args.requests // 2 else 1.0
        reqs.append(Request(rid=rid, prompt=prompt, max_new=args.max_new,
                            model_id=model_id, qos=qos))

    swap_after = (args.swap_after if args.swap_after is not None
                  else args.requests // 2)
    swap_tenant = tenant_ids[-1]
    swap_params = (resolve_swap_params(args.hot_swap, model, params)
                   if args.hot_swap else None)

    def stats_banner(steps):
        if not args.metrics_interval or steps % args.metrics_interval:
            return
        reg = sched.metrics
        toks = int(reg.total("serve_tokens_total"))
        parts = []
        for t in sched.tenants:
            n = int(reg.total("serve_tokens_total", tenant=t))
            e = reg.total("serve_device_energy_joules_total", tenant=t)
            pj = e / n * 1e12 if n else 0.0
            parts.append(f"{t}:{n}tok/{pj:.0f}pJ")
        retr = int(obs.registry().total("serve_jit_retraces_total"))
        print(f"[obs] step {steps}: {toks} tokens "
              f"({', '.join(parts)}); jit retraces {retr}")

    t0 = time.time()
    done, steps, n_submitted = [], 0, 0
    while len(done) < args.requests and steps < 10_000:
        while (n_submitted < len(reqs)
               and steps >= n_submitted * args.stagger):
            sched.submit(reqs[n_submitted])
            n_submitted += 1
        if (swap_params is not None and not sched.swap_in_flight
                and not sched.swap_history and len(done) >= swap_after):
            hs = sched.begin_hot_swap(swap_params,
                                      chunks_per_step=args.swap_chunks,
                                      tenant=swap_tenant)
            print(f"hot-swap: staging {hs.plan.total_chunks} chunks onto "
                  f"tenant {swap_tenant}'s write planes after {len(done)} "
                  f"requests ({steps} decode steps)")
        done += sched.step()
        steps += 1
        stats_banner(steps)
    # requests can drain before the chunked swap completes — finish the
    # deployment rather than abandoning a half-written shadow plane
    # (idle steps still program chunks and promote at the boundary)
    if sched.swap_in_flight:
        print("hot-swap: requests drained mid-swap; finishing shadow "
              "programming before exit")
        while sched.swap_in_flight and steps < 20_000:
            sched.step()
            steps += 1
            stats_banner(steps)
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens in "
          f"{steps} decode steps, {dt:.2f}s "
          f"({total_tokens / max(dt, 1e-9):.1f} tok/s)")
    if args.stream_pages:
        rep = sched.attn_lane_report()
        d = rep["dispatch"]
        print(f"attn lanes: streamed >= {rep['stream_min_pages']}p of "
              f"{rep['pages_per_seq']}p table, "
              f"block={rep['block_pages']}p; traced closures "
              f"scratch={d['paged_scratch']} "
              f"streamed={d['paged_streamed']}")
    if (args.prefix_share or args.preemption) and sched.metrics.enabled:
        reg = sched.metrics
        if args.prefix_share:
            print(f"prefix sharing: "
                  f"{int(reg.total('serve_kv_pages_shared_total'))} pages "
                  f"aliased, "
                  f"{int(reg.total('serve_kv_shared_tokens_total'))} "
                  f"prompt tokens skipped, "
                  f"{int(reg.total('serve_kv_cow_total'))} copy-on-write "
                  f"page copies")
        if args.preemption:
            n_evict = int(reg.total("serve_preemptions_total"))
            print(f"preemption: {n_evict} evictions, "
                  f"{sum(r.preemptions for r in done)} re-admissions, "
                  f"zero dropped requests")
    if tenants:
        qos = sched.qos_report()
        for t in sched.tenants:
            reqs = [r for r in done if r.model_id == t]
            q = qos[t]
            print(f"  tenant {t}: {len(reqs)} requests, "
                  f"{sum(len(r.out) for r in reqs)} tokens; qos "
                  f"weight={q['weight']:g} slots={q['slots']} "
                  f"share={q['token_share'] * 100:.1f}% "
                  f"(fingerprint={model.executor.fingerprint(tenant=t)})")
    for r in done[:3]:
        print(f"  req {r.rid} [{r.model_id}]: {r.out[:8]}...")
    for rep in sched.swap_history:
        ex = model.executor
        t = rep.get("tenant", "A")
        print(f"hot-swap promoted [{rep['policy']} tenant {t}]: "
              f"version={ex.version(t)} "
              f"fingerprint={ex.fingerprint(tenant=t)} "
              f"wall={rep['wall_swap_s']:.2f}s "
              f"({rep['decode_steps_during_swap']} decode steps served "
              f"during the swap, zero dropped)")
        print(f"  device-time: overlapped window "
              f"{rep['device_swap_window_overlapped_s'] * 1e6:.1f}us vs "
              f"stop-the-world "
              f"{rep['device_swap_window_stop_world_s'] * 1e6:.1f}us; "
              f"throughput-during-swap ratio "
              f"{rep['throughput_ratio_overlap_vs_stop_world']:.2f}x; "
              f"steady-state overlap "
              f"{rep['overlap_frac_steady_state'] * 100:.1f}% at "
              f"{rep['in_bits']}-bit reads (paper: ~29% at 10-bit)")
    if model.executor is not None and sched.metrics.enabled:
        # live traffic-weighted device figures (Table-I accounting per
        # emitted token; see sched.mode_report()["traffic"])
        for t in sched.tenants:
            n = int(sched.metrics.total("serve_tokens_total", tenant=t))
            if not n:
                continue
            for mode in ("expansion", "deepnet"):
                e = sched.metrics.total(
                    "serve_device_energy_joules_total",
                    tenant=t, mode=mode)
                s = sched.metrics.total(
                    "serve_device_read_seconds_total",
                    tenant=t, mode=mode)
                if e:
                    print(f"  device [{t}/{mode}]: {s * 1e6:.1f}us read, "
                          f"{e / n * 1e12:.0f} pJ/token over {n} tokens")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(sched.tracer.to_jsonl())
            f.write(sched.metrics.to_jsonl())
            f.write(obs.tracer().to_jsonl())
            f.write(obs.registry().to_jsonl())
        n_spans = len(sched.tracer) + len(obs.tracer())
        print(f"telemetry: wrote {n_spans} spans + metric samples to "
              f"{args.metrics_out}")
        print("# --- Prometheus snapshot (scheduler + global) ---")
        print(sched.metrics.to_prometheus(), end="")
        print(obs.registry().to_prometheus(), end="")
    return done


if __name__ == "__main__":
    main()
