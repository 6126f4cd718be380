#!/usr/bin/env python3
"""Chip benchmark: one cell, one seed, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up builds the cell's configuration with weights drawn from the seed,
builds ``BatchScheduler`` as ``launch/serve.py`` does and warms every
program the window will run.  The window drives ``submit``/``step``
with the cell's traffic for ``--seconds`` seconds: an open loop sends
requests on a Poisson schedule at the cell's fixed rate, a closed loop
keeps one request in flight per client; then, with nothing more sent,
it steps on until each request sent has its first token.  After the
window the program's state is freed and a plain reference
(``reference.py``) checks a seeded sample of the served tokens.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
of a steady stretch of the window), ``device`` and, last, ``checks``:
each number compared beside its limit, also printed as the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 3 and prints no result.

``run_cell`` also takes a rate other than the cell's (``sweep.py``
finds the knee with it) and controls to judge beside the program
(``readings.py`` reads the limits' two ends with them); the measured
runs use neither.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

import drive  # noqa: E402
import endtoend  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402
from peaks import peaks_for  # noqa: E402
from trace_reduce import STRETCH, reduce_xspace  # noqa: E402

DRAIN_S = 120.0          # stepping after the close, at most
TRACE_AT = 0.6           # traced stretch starts this far into the window
TRACE_S = 4.0            # and lasts about this long (whole steps)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts lowerings and backend compiles in this process."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    conf: Dict[str, Any]
    drv: drive.Driver
    t_start: float
    t_open: float
    t_close: float
    peaks: Dict[str, float]
    trace: Any = None                 # trace_reduce.Reduced, --trace 1
    traced_steps: List[drive.Step] = dataclasses.field(default_factory=list)
    t_trace: Optional[float] = None   # when the profiler started

    @property
    def untraced_tracks(self) -> List[drive.Track]:
        """Requests due in the window before the profiler started: the
        host-clock per-layer metrics read these, so that starting and
        stopping the profiler does not count as the system's delay."""
        end = self.t_close if self.t_trace is None else self.t_trace
        return [t for t in self.window_tracks if t.due < end]

    @property
    def window_tracks(self) -> List[drive.Track]:
        return [t for t in self.drv.tracks
                if self.t_open <= t.due < self.t_close]

    @property
    def window_steps(self) -> List[drive.Step]:
        return [s for s in self.drv.steps
                if s.t0 >= self.t_open and s.t1 <= self.t_close]


class StretchTracer:
    """Profiles whole steps from ``TRACE_AT`` of the window for about
    ``TRACE_S`` seconds, under the host span ``bench.traced``."""

    def __init__(self, t_open: float, seconds: float):
        self.t_from = t_open + TRACE_AT * seconds
        self.length = min(TRACE_S, 0.3 * seconds)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.span = None
        self.t0 = self.t1 = None

    def __call__(self, st: drive.Step) -> None:
        now = drive.clock()
        if self.t0 is None and now >= self.t_from:
            jax.profiler.start_trace(self.dir)
            self.span = TraceAnnotation(STRETCH)
            self.span.__enter__()
            self.t0 = drive.clock()
        elif (self.t0 is not None and self.t1 is None
              and now >= self.t0 + self.length):
            self.stop()

    def stop(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = drive.clock()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        try:
            found = list(Path(self.dir).rglob("*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            return reduce_xspace(str(found[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _set_up_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def judge(numbers: Dict[str, float], limits: Dict[str, Optional[float]],
          window: Dict[str, Dict[str, float]], compared: int):
    """``correct`` and the checks, each number beside its limit: the
    reference's numbers first, then the window's counts (limit 0)."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    checks.update(window)
    correct = compared > 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return bool(correct), checks


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None, rate: Optional[float] = None,
             controls: Sequence[str] = (), device=None) -> Dict[str, Any]:
    """Set up, measure and check one run of ``cell``; returns the result
    object (the last line the command prints).  Each control named is
    judged by the same checks as the program, its own tokens in place
    of the served ones, under ``controls``."""
    from repro import obs
    from repro.models.model import build_model
    from repro.serve.engine import BatchScheduler

    clock = drive.clock
    t_start = clock() if t_start is None else t_start
    device = device or jax.devices()[0]
    peaks = peaks_for(device.device_kind) if device.platform == "tpu" \
        else None
    compiles = CompileCounter()
    reg = obs.registry()
    ref_reads0 = reg.total("crossstack_dispatch_total", path="reference")
    setup: Dict[str, float] = {"process_start_s": clock() - t_start}
    conf, serve, mix = cell.config, cell.serve, cell.mix

    mark = clock()
    cfg = spec.model_config(conf)
    model = build_model(cfg)
    params = weights.make_params(conf, seed)
    jax.block_until_ready(params)
    setup["params_s"] = clock() - mark

    mark = clock()
    sched = BatchScheduler(model, params, n_slots=serve["slots"],
                           max_len=serve["max_len"],
                           page_size=serve["page_size"],
                           chunk=serve["chunk"])
    ex = model.executor
    if ex is not None:
        jax.block_until_ready(ex.resident_tiles("A"))
    setup["program_s"] = clock() - mark

    mark = clock()
    drive.warm_up(sched, cfg.vocab, serve["chunk"])
    setup["warmup_s"] = clock() - mark
    drv = drive.Driver(sched)
    mark = clock()
    if cell.open_loop:
        plans = traffic.open_loop(mix, rate or serve["rate_rps"], seconds,
                                  cfg.vocab, seed)
    else:
        stream = traffic.closed_loop(mix, cfg.vocab, seed)
        drive.start_closed(drv, stream, mix["clients"])
    setup["first_prefill_s"] = clock() - mark

    retraces0 = reg.total("serve_jit_retraces_total")
    compiles0 = compiles.n
    t_open = clock()
    tracer = StretchTracer(t_open, seconds) if trace else None
    drv.on_step = tracer
    if cell.open_loop:
        t_close = drive.run_open(drv, plans, t_open, seconds, DRAIN_S)
    else:
        t_close = drive.run_closed(drv, stream, t_open, seconds, DRAIN_S)
    if tracer is not None:
        tracer.stop()
    drv.on_step = None
    window: Dict[str, Dict[str, float]] = {
        "compiles_in_window": {"value": compiles.n - compiles0, "limit": 0},
        "retraces_in_window": {
            "value": reg.total("serve_jit_retraces_total") - retraces0,
            "limit": 0}}
    if ex is not None:
        window["reference_dispatches"] = {
            "value": reg.total("crossstack_dispatch_total",
                               path="reference") - ref_reads0, "limit": 0}
    mem = device.memory_stats() or {}
    run = Run(conf, drv, t_start, t_open, t_close, peaks)
    if tracer is not None:
        run.trace = tracer.reduce()
        run.t_trace = tracer.t0
        run.traced_steps = [s for s in drv.steps
                            if s.t0 >= tracer.t0 and s.t1 <= tracer.t1]

    if cell.open_loop:
        attempted = len(run.window_tracks)
    else:
        attempted = sum(1 for t in drv.tracks
                        if t.submit < t_close and (t.done is None
                                                   or t.done >= t_open))
    failed = sum(1 for t in run.window_tracks if not t.tokens)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            mod = importlib.import_module(f"metrics.{m['name']}")
            v = mod.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = endtoend.compute(run, [m["name"] for m in cell.end_to_end])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the check: program state freed, then the reference on a sample
    finished = [t.req for t in drv.tracks if t.req.done]
    drv.sched = sched = model = ex = None
    gc.collect()
    picked = reference.sample(finished, seed)
    t_ref = clock()
    n_cmp = sum(len(r.out) for r in picked)
    read = (reference.compare(weights.published(conf, params), conf, picked,
                              serve["max_len"], controls)
            if picked else {k: {"widest_gap": float("inf"),
                                "mean_gap": float("inf")}
                            for k in ("served", *controls)})
    params = None
    limits = serve["limits"]
    correct, checks = judge(read["served"], limits, window, n_cmp)

    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(mem.get("peak_bytes_in_use",
                                                    0))}}
    if run.trace is not None:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps}
    out["info"] = {
        "setup": setup, "reference_s": clock() - t_ref,
        "requests_compared": len(picked), "tokens_compared": n_cmp,
        "steps_in_window": len(run.window_steps),
        "completed_in_window": sum(1 for t in drv.tracks if t.done
                                   and t_open <= t.done <= t_close),
        "tokens_in_window": sum(s.tokens for s in run.window_steps),
        "rate_rps": (rate or serve.get("rate_rps")) if cell.open_loop
        else None, "backlog": _backlog(run) if cell.open_loop else None}
    if controls:
        out["controls"] = {}
        for name in controls:
            ok, ctl = judge(read[name], limits, window, n_cmp)
            out["controls"][name] = {"correct": ok, "checks": ctl}
    out["checks"] = checks
    return out


def _backlog(run: Run) -> List[int]:
    """Requests sent but not yet fed, at the end of each quarter of the
    window (``sweep.py`` reads a rise from one quarter to the next as a
    backlog that grows)."""
    out = []
    for q in (0.25, 0.5, 0.75, 1.0):
        t = run.t_open + q * (run.t_close - run.t_open)
        out.append(sum(1 for tr in run.drv.tracks
                       if tr.submit <= t and (tr.fed is None or tr.fed > t)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax.devices()[0].platform is "
              f"{devices[0].platform!r}; this benchmark runs only on the "
              f"chip", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 3
    _set_up_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, device=devices[0])
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
