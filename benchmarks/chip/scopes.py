"""Device time by named scope, from the profiler trace and the window
step's compiled HLO.

The v5e trace gives each device op its HLO instruction
(``%fusion.210 = bf16[16,4,2560]{...} fusion(...)``), a start and a
duration, but no ``op_name``.  The window step's compiled HLO text
(``BatchScheduler.window_hlo``, fetched after the window) gives each
instruction its ``op_name`` metadata, which carries the program's named
scopes (``jit(window_step)/window/head/...``).  An op is charged to a
scope when its instruction name and result shape, layouts aside, match
an instruction of that text whose ``op_name`` holds ``/<scope>/``.
Matching the shape as well keeps out the small eager programs that run
between steps, whose instructions reuse names such as ``%fusion.3``.

The body ops of a loop nest under the loop's own event on the trace's
line, so each op counts its self time: its interval less the ops nested
directly inside it.  The cells run on one chip; the ops of all devices
are taken as one line.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import numpy as np

SCOPES = ("attention", "mlp", "head", "sample")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYOUT = re.compile(r"\{[^}]*\}")


def result_shape(rhs: str) -> str:
    """The result type that opens an instruction's right-hand side,
    with its layouts dropped: ``bf16[4,8]{1,0:T(4,128)} fusion(...)``
    gives ``bf16[4,8]``."""
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            rhs = rhs[:i]
            break
    return _LAYOUT.sub("", rhs)


def instructions(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (result shape, ``op_name``), for every
    instruction of the text that carries ``op_name`` metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        op = _OP_NAME.search(line) if m else None
        if op:
            out[m.group(1)] = (result_shape(m.group(2)), op.group(1))
    return out


def self_seconds(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Each interval's own time: its length less the intervals nested
    directly inside it (an interval that starts inside another is
    nested in it, up to the other's end)."""
    own = (end - start).astype(float)
    stack = []
    for i in np.lexsort((-end, start)):
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(end[i], end[parent]) - start[i]
        stack.append(i)
    return np.maximum(own, 0.0)


def scope_seconds(trace, hlo_text: str) -> Dict[str, float]:
    """Self seconds of the trace's device ops under each of ``SCOPES``."""
    table = instructions(hlo_text)
    own = self_seconds(np.asarray(trace.op_start), np.asarray(trace.op_end))
    out = dict.fromkeys(SCOPES, 0.0)
    for label, secs in zip(trace.op_names, own):
        name, _, rhs = label.partition(" = ")
        hit = table.get(name)
        if hit is None or hit[0] != result_shape(rhs):
            continue
        for scope in SCOPES:
            if f"/{scope}/" in hit[1]:
                out[scope] += float(secs)
    return out


def step_ms(run, scope: str) -> Optional[float]:
    """Device self time under ``scope`` per traced step that ran
    anything, in ms; None where the trace has none, or the program
    cannot give its window step's HLO."""
    if run.trace is None:
        return None
    secs = getattr(run, "scope_seconds", None)
    if secs is None:
        fetch = getattr(run.drv.sched, "window_hlo", None)
        if fetch is None:
            return None
        secs = run.scope_seconds = scope_seconds(run.trace, fetch())
    steps = sum(1 for b in run.trace.step_busy_s if b > 0)
    if not steps or secs[scope] <= 0:
        return None
    return secs[scope] / steps * 1e3
