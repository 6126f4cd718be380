"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (see paper_benches.py) plus the
weight-residency benches (executor_bench.py), printed as
``name,us_per_call,derived`` CSV rows and written to a ``BENCH_*.json``
artifact, followed by the roofline summary if dry-run artifacts exist
(benchmarks/roofline.py builds the full table).

``--quick`` runs the smallest configs (the CI benchmark-smoke lane);
``--json PATH`` overrides the artifact path.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)   # so ``python benchmarks/run.py`` also works

from benchmarks import executor_bench as xb  # noqa: E402
from benchmarks import expansion_bench as eb  # noqa: E402
from benchmarks import hotswap_bench as hb  # noqa: E402
from benchmarks import multiplex_bench as mb  # noqa: E402
from benchmarks import obs_bench as ob  # noqa: E402
from benchmarks import overlap_kernel_bench as okb  # noqa: E402
from benchmarks import paged_bench as pgb  # noqa: E402
from benchmarks import paper_benches as pb  # noqa: E402
from benchmarks.meta import append_trajectory, write_stamped  # noqa: E402
from repro import obs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


BENCHES = [
    ("fig3a_hysteresis", pb.bench_hysteresis),
    ("fig3b_ir_drop_22pct", pb.bench_ir_drop),
    ("fig3cd_leakage_mc", pb.bench_leakage_mc),
    ("fig4_transient_readout", pb.bench_transient_readout),
    ("sec5_deepnet_speedup_29pct", pb.bench_deepnet_speedup),
    ("table1_characteristics", pb.bench_table1),
    ("engine_crossbar_mac", pb.bench_crossbar_mac),
]

# weight-residency benches take a ``quick`` kwarg (CI smoke lane)
RESIDENCY_BENCHES = [
    ("executor_program_once", xb.bench_program_once),
    ("executor_reference_vs_kernel", xb.bench_reference_vs_kernel),
    ("executor_decode_resident", xb.bench_executor_decode),
    ("hotswap_overlap", hb.bench_hotswap),
    ("multiplex_plane_sharing", mb.bench_multiplex),
    ("planebank_3tenant", mb.bench_planebank),
    ("overlap_kernel_decode", okb.bench_overlap_kernel),
    ("expansion_mode_policy", eb.bench_expansion),
    ("obs_telemetry", ob.bench_obs),
    ("paged_serving", pgb.bench_paged),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smallest configs; residency benches only")
    ap.add_argument("--json", default="BENCH_crossstack.json",
                    help="write all results to this JSON artifact")
    args = ap.parse_args(argv)
    enable_compile_cache()

    results = {}
    # --quick is CI's "Benchmark smoke" step, which is followed by
    # dedicated hotswap_bench.py / multiplex_bench.py runs — skip those
    # here to avoid paying the same serving loops twice per CI run.
    # planebank_3tenant deliberately stays in BOTH lanes: here so the
    # 3-tenant figures ride the main BENCH artifact + trajectory append
    # of every --quick run, and again in the dedicated CI "Plane-bank
    # smoke" step, which is what gates on the acceptance figures (exit
    # code) and uploads BENCH_planebank.json.  ~2 min of duplicated
    # serving loops per CI run, accepted for the standalone gate.
    quick_benches = [(n, f) for n, f in RESIDENCY_BENCHES
                     if n not in ("hotswap_overlap",
                                  "multiplex_plane_sharing",
                                  "overlap_kernel_decode",
                                  "expansion_mode_policy",
                                  "obs_telemetry",
                                  "paged_serving")]
    benches = ([(n, lambda f=f: f(quick=True)) for n, f in quick_benches]
               if args.quick else
               BENCHES + [(n, f) for n, f in RESIDENCY_BENCHES])
    print("name,us_per_call,derived")
    for name, fn in benches:
        res = fn()
        results[name] = dict(res)
        us = res.pop("us_per_call", 0.0)
        derived = json.dumps(res, default=float)
        print(f"{name},{us:.1f},{derived}")

    # final registry snapshot rides the artifact (underscore key: the
    # schema gate skips it when scanning for figures dicts) so every
    # BENCH_*.json records what actually executed — kernel vs reference
    # dispatches, program/swap events, jit trace/retrace counts
    reg = obs.registry()
    results["_registry"] = reg.snapshot()
    telemetry = {
        "dispatch_kernel": int(reg.total("crossstack_dispatch_total",
                                         path="kernel")),
        "dispatch_reference": int(reg.total("crossstack_dispatch_total",
                                            path="reference")),
        "jit_traces": int(reg.total("serve_jit_traces_total")),
        "jit_retraces": int(reg.total("serve_jit_retraces_total")),
    }
    # provenance stamp (git SHA, jax version, timestamp) + trajectory
    # append — BENCH_*.json artifacts are comparable across PRs
    meta = write_stamped(results, args.json,
                         lane="quick" if args.quick else "full")
    append_trajectory(meta, results, telemetry=telemetry)
    print(f"# telemetry: {telemetry}")
    print(f"# wrote {args.json} (sha={meta['git_sha'][:12]} "
          f"jax={meta['jax_version']} at {meta['timestamp_utc']})")

    # roofline summary (reads experiments/dryrun/*.json if present)
    try:
        from benchmarks.roofline import summary_rows
        rows = summary_rows("experiments/dryrun")
        if rows:
            print("\n# roofline (single-pod 16x16; seconds per step)")
            print("arch,shape,compute_s,memory_s,collective_s,bottleneck,"
                  "model_flops_ratio,peak_GiB")
            for r in rows:
                print(",".join(str(r[k]) for k in (
                    "arch", "shape", "compute_s", "memory_s",
                    "collective_s", "bottleneck", "model_flops_ratio",
                    "peak_gib")))
    except Exception as e:  # noqa: BLE001
        print(f"# roofline summary unavailable: {e!r}")


if __name__ == "__main__":
    main()
