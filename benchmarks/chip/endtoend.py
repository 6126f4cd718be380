"""End-to-end metrics, from the client's records of one run.

Each is taken over all the work of the window: every request due in it,
every gap between tokens inside it, every token returned in it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def p90(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 90)) if values else None


def ttft_p90_ms(run) -> Optional[float]:
    """p90 over requests due in the window of (first token - due); a
    closed loop's request is due when its client sends it."""
    return p90([(tr.tokens[0] - tr.due) * 1e3 for tr in run.window_tracks
                if tr.tokens])


def tpot_p90_ms(run) -> Optional[float]:
    """p90 over every gap between consecutive tokens of a request that
    begins and ends inside the window."""
    gaps = []
    for tr in run.drv.tracks:
        t = np.asarray(tr.tokens)
        if t.size < 2:
            continue
        a, b = t[:-1], t[1:]
        inside = (a >= run.t_open) & (b <= run.t_close)
        gaps.extend(((b - a)[inside] * 1e3).tolist())
    return p90(gaps)


def output_tok_s(run) -> float:
    n = sum(int(np.count_nonzero((np.asarray(tr.tokens) >= run.t_open)
                                 & (np.asarray(tr.tokens) <= run.t_close)))
            for tr in run.drv.tracks)
    return n / (run.t_close - run.t_open)


def setup_s(run) -> float:
    return run.t_open - run.t_start


READERS = {"ttft_p90_ms": ttft_p90_ms, "tpot_p90_ms": tpot_p90_ms,
           "output_tok_s": output_tok_s, "setup_s": setup_s}


def compute(run, names: List[str]) -> Dict[str, float]:
    out = {}
    for name in names:
        v = READERS[name](run)
        if v is None:
            raise RuntimeError(f"{name}: nothing to measure in the window")
        out[name] = v
    return out
