"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The traced stretch is the benchmark's own host span ``bench.traced``.
Device work is the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
busy time is the union of those intervals inside the stretch, averaged
over the chips that ran anything.  Each ``sched.step`` host span, up to
the next one, owns the device work that ran in it.  An idle gap of the
device is charged to the host span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HOST_SPANS = ("sched.step", "arrivals", "submit", "client_bookkeeping")
STRETCH = "bench.traced"
DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]            # traced stretch, seconds
    busy_s: float                          # device busy, mean over chips
    step_busy_s: List[float]               # device busy per sched.step
    op_names: List[str]                    # per device op: its label
    op_start: np.ndarray                   # seconds
    op_end: np.ndarray
    idle_gaps: List[Tuple[str, float]]     # host span -> idle seconds

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the ops whose label matches."""
        rx = re.compile(pattern)
        return float(sum(e - s for n, s, e in
                         zip(self.op_names, self.op_start, self.op_end)
                         if rx.search(n)))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = defaultdict(float)
        for n, s, e in zip(self.op_names, self.op_start, self.op_end):
            tot[n] += float(e - s)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def union(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    """Merged intervals of [starts, ends), sorted."""
    out: List[List[float]] = []
    for s, e in sorted(zip(starts.tolist(), ends.tolist())):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covered(iv: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in iv)


def _label(ev) -> str:
    stats = dict(ev.stats)
    name = str(ev.name)
    long = str(stats.get("long_name", "") or stats.get("tf_op", ""))
    return f"{name} {long}".strip() if long else name


def reduce_xspace(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    host: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    devices: List[Tuple[List[str], np.ndarray, np.ndarray]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name == STRETCH:
                        s = ev.start_ns * 1e-9
                        host[ev.name].append((s, s + ev.duration_ns * 1e-9))
        elif DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                names, st, en = [], [], []
                for ev in line.events:
                    names.append(_label(ev))
                    st.append(ev.start_ns * 1e-9)
                    en.append((ev.start_ns + ev.duration_ns) * 1e-9)
                if names:
                    devices.append((names, np.asarray(st), np.asarray(en)))
    if not host[STRETCH]:
        raise ValueError(f"no {STRETCH!r} host span in the trace")
    lo, hi = host[STRETCH][0]
    if not devices:
        raise ValueError("no device ran an operation in the trace")
    steps = sorted(s for s, _ in host["sched.step"] if lo <= s < hi)
    bounds = list(zip(steps, steps[1:] + [hi]))
    busy, step_busy = [], np.zeros(len(bounds))
    all_names: List[str] = []
    all_s, all_e = [], []
    for names, st, en in devices:
        keep = (en > lo) & (st < hi)
        st, en = np.clip(st[keep], lo, hi), np.clip(en[keep], lo, hi)
        names = [n for n, k in zip(names, keep) if k]
        iv = union(st, en)
        busy.append(_covered(iv, lo, hi))
        step_busy += [_covered(iv, a, b) for a, b in bounds]
        all_names += names
        all_s.append(st)
        all_e.append(en)
    n_dev = len(devices)
    iv = union(np.concatenate(all_s), np.concatenate(all_e))
    return Reduced(window=(lo, hi), busy_s=float(np.mean(busy)),
                   step_busy_s=(step_busy / n_dev).tolist(),
                   op_names=all_names, op_start=np.concatenate(all_s),
                   op_end=np.concatenate(all_e),
                   idle_gaps=_idle_gaps(iv, lo, hi, host))


def _idle_gaps(busy, lo, hi, host) -> List[Tuple[str, float]]:
    """Idle seconds of the stretch, charged by host span, largest first."""
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    tot: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        best: Optional[str] = None
        best_cov = 0.0
        for name in HOST_SPANS:
            cov = _covered(host[name], a, b)
            if cov > best_cov:
                best, best_cov = name, cov
        tot[best or "no host span"] += b - a
    return sorted(tot.items(), key=lambda kv: -kv[1])[:10]
