"""The client side: drives ``BatchScheduler.submit`` / ``.step`` and
records, on the host clock, when each request was due, sent, first fed
and given each token, and what each step computed.

One thread, one process: the client and the scheduler's host code share
it, so a request due while a step runs is sent when the step returns;
``gen_lag_p90_ms`` reports how late that makes the generator.
Host spans (``jax.profiler.TraceAnnotation``) name what the host is
doing for the trace reduction: ``arrivals``, ``submit``, ``sched.step``
and ``client_bookkeeping``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve.engine import BatchScheduler, Request

from traffic import Planned

clock = time.perf_counter


@dataclasses.dataclass
class Track:
    """One request as the client saw it (host-clock seconds)."""
    plan: Planned
    req: Request
    due: float                        # when it was due to be sent
    client: int = -1                  # closed loop: the client sending it
    submit: Optional[float] = None
    fed: Optional[float] = None       # start of the first step that fed it
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    positions: int    # useful positions computed: prompt tokens fed + decodes
    context: int      # sum over those positions of the keys each attends to
    tokens: int       # tokens returned to clients


class Driver:
    def __init__(self, sched: BatchScheduler):
        self.sched = sched
        self.tracks: List[Track] = []
        self.active: List[Track] = []
        self.steps: List[Step] = []
        self.on_step: Optional[Callable[[Step], None]] = None

    def submit(self, plan: Planned, due: float, client: int = -1) -> Track:
        req = Request(rid=plan.idx, prompt=plan.prompt,
                      max_new=plan.max_new)
        tr = Track(plan, req, due, client)
        with TraceAnnotation("submit"):
            self.sched.submit(req)
        tr.submit = clock()
        self.tracks.append(tr)
        self.active.append(tr)
        return tr

    @property
    def busy(self) -> bool:
        return bool(self.active)

    def step(self) -> List[Track]:
        """One scheduler step; returns the tracks that completed in it."""
        before = []
        for tr in self.active:
            r = tr.req
            decoding = r.feed is not None and r.fed >= len(r.feed)
            before.append((tr, r.fed, decoding, len(r.out)))
        t0 = clock()
        with TraceAnnotation("sched.step"):
            self.sched.step()
        t1 = clock()
        with TraceAnnotation("client_bookkeeping"):
            positions = context = tokens = 0
            finished = []
            for tr, fed0, decoding, nout0 in before:
                r = tr.req
                if r.fed > fed0:
                    if tr.fed is None:
                        tr.fed = t0
                    positions += r.fed - fed0
                    context += (r.fed * (r.fed + 1)
                                - fed0 * (fed0 + 1)) // 2
                elif decoding and len(r.out) > nout0:
                    positions += 1
                    context += len(r.feed) + nout0
                new = len(r.out) - nout0
                tokens += new
                tr.tokens.extend([t1] * new)
                if r.done:
                    tr.done = t1
                    finished.append(tr)
            if finished:
                self.active = [t for t in self.active if not t.req.done]
            st = Step(t0, t1, positions, context, tokens)
            self.steps.append(st)
            if self.on_step is not None:
                self.on_step(st)
        return finished


def warm_up(sched: BatchScheduler, vocab: int, chunk: int) -> None:
    """Compile and warm every program the window will run: two requests
    through admission, a two-chunk prefill, decode, completion and slot
    release.  The scheduler is left empty."""
    rng = np.random.default_rng(0)
    reqs = [Request(rid=-1 - i, max_new=3,
                    prompt=rng.integers(0, vocab, chunk + 1, dtype=np.int32))
            for i in range(2)]
    for r in reqs:
        sched.submit(r)
    for _ in range(64):
        sched.step()
        if all(r.done for r in reqs):
            return
    raise RuntimeError("warm-up requests did not complete in 64 steps")


def run_open(drv: Driver, plans: List[Planned], t_open: float,
             seconds: float, drain_s: float) -> float:
    """Send ``plans`` on their schedule for ``seconds``, stepping whenever
    there is work; then, with arrivals stopped, step until every request
    sent has its first token or ``drain_s`` has passed.  Returns the
    close."""
    t_close = t_open + seconds
    i, n = 0, len(plans)
    while True:
        now = clock()
        if now >= t_close:
            break
        if i < n and t_open + plans[i].due <= now:
            with TraceAnnotation("arrivals"):
                while i < n and t_open + plans[i].due <= now:
                    drv.submit(plans[i], t_open + plans[i].due)
                    i += 1
        if drv.busy:
            drv.step()
        else:
            nxt = t_open + plans[i].due if i < n else t_close
            time.sleep(max(0.0, min(nxt, t_close) - clock()))
    _drain(drv, t_close, drain_s)
    return t_close


def start_closed(drv: Driver, stream: Iterator[Planned],
                 clients: int) -> None:
    """Each client sends its first request; step until every one has its
    first token (set-up: the window opens on steady decode)."""
    for c in range(clients):
        drv.submit(next(stream), clock(), client=c)
    for _ in range(100_000):
        if all(tr.tokens for tr in drv.tracks):
            return
        for tr in drv.step():
            drv.submit(next(stream), clock(), client=tr.client)
    raise RuntimeError("first prefill did not finish")


def run_closed(drv: Driver, stream: Iterator[Planned], t_open: float,
               seconds: float, drain_s: float) -> float:
    """Each client sends its next request as its last one completes, for
    ``seconds``; then, with no request sent, step until every request
    sent has its first token or ``drain_s`` has passed.  Returns the
    close."""
    t_close = t_open + seconds
    while clock() < t_close:
        for tr in drv.step():
            drv.submit(next(stream), clock(), client=tr.client)
    _drain(drv, t_close, drain_s)
    return t_close


def _drain(drv: Driver, t_close: float, drain_s: float) -> None:
    """Step until every request due before ``t_close`` has its first
    token, for at most ``drain_s`` after it."""
    while (any(not tr.tokens and tr.due < t_close for tr in drv.active)
           and clock() < t_close + drain_s):
        drv.step()
