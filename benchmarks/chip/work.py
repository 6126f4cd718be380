"""The work a window step must do, from the published shapes and the
useful tokens only.

A step's useful positions are the prompt tokens it fed plus the rows it
decoded; its emitted tokens are the positions whose logits a client
receives (a row's last prompt chunk, and every decode).  The trunk's
work is counted per useful position, the head's per emitted token, and
attention per key each position attends to.  Pad positions of the
window and padding rows of the vocabulary are never counted, so a change
that stops computing them is read against the same work, and no share
can pass 100% because a count went stale.  The shapes and the counts of
each model family are its plug-in's (``spec.family``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import spec


def linears(conf) -> Tuple[List[Tuple[str, int, int]], Tuple[int, int]]:
    """(name, K, N) of one layer's linears at published widths, and the
    head's (K, N)."""
    return spec.family(conf).linears(conf)


def step_flops(conf, positions: int, context: int, tokens: int) -> float:
    """Model FLOPs of one step: the linears' 2 per weight per useful
    position (the head's per emitted token) plus attention over each
    position's keys."""
    return spec.family(conf).step_flops(conf, positions, context, tokens)


def step_bytes(conf, positions: int, context: int) -> float:
    """Least HBM bytes of one step: every weight read once, the cache of
    every attended key read and of every useful position written."""
    return spec.family(conf).step_bytes(conf, positions, context)


def least_step_seconds(conf, steps, peaks: Dict[str, float]) -> float:
    """Sum over ``steps`` of the larger of compute and memory bound."""
    return sum(max(step_flops(conf, s.positions, s.context, s.tokens)
                   / peaks["bf16_flops"],
                   step_bytes(conf, s.positions, s.context)
                   / peaks["hbm_bytes_s"]) for s in steps)
