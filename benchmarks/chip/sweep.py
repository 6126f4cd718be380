#!/usr/bin/env python3
"""Knee sweep for an open-loop cell, run on the chip when the cell is
defined.

    python3 benchmarks/chip/sweep.py <cell> <seconds> <fractions> <seeds>

One process.  Every window is a whole run of the cell (``run.run_cell``:
its own set-up, window and check).  First the capacity: the cell's mix
as a closed loop with one client per slot, every slot kept busy; its
completed requests/s.  Then an open-loop window of ``seconds`` at each
fraction of that capacity, once per seed.  Prints one JSON line per
window: the rate, the time to first token, the token gap, the backlog
(sent but not yet fed) at the end of each quarter of the window and
whether the run was correct.  The knee is the highest rate at which,
on every seed and at every rate below it, the backlog never rises from
one quarter to the next; the cell's file takes 0.8 x knee as its fixed
``rate_rps``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
from typing import Optional

import run as R  # first: puts the program's sources on the path
import spec


def grows(backlog) -> bool:
    """Any rise from one quarter of the window to the next."""
    return any(b > a for a, b in zip(backlog, backlog[1:]))


def main(name: str, seconds: float, fractions, seeds) -> Optional[float]:
    """Runs the sweep; returns 0.8 x knee, or None where no rate is
    flat."""
    cell = spec.load_cell(name)
    R._set_up_cache()
    slots = cell.serve["slots"]
    busy = dataclasses.replace(
        cell, mix=dict(cell.mix, loop="closed", clients=slots))
    out = R.run_cell(busy, seeds[0], seconds, trace=False)
    cap = out["info"]["completed_in_window"] / seconds
    print(json.dumps({"cell": name, "capacity_rps": cap,
                      "steps_in_window": out["info"]["steps_in_window"],
                      "correct": out["correct"]}), flush=True)
    knee, flat = None, True
    for f in sorted(fractions):
        for seed in seeds:
            gc.collect()
            out = R.run_cell(cell, seed, seconds, trace=False, rate=cap * f)
            backlog = out["info"]["backlog"]
            flat = flat and not grows(backlog)
            print(json.dumps({
                "cell": name, "frac": f, "rate_rps": cap * f, "seed": seed,
                "requests": out["attempted"], "failed": out["failed"],
                **{k: m["value"] for k, m in out["metrics"].items()},
                "backlog_quarters": backlog, "correct": out["correct"],
                "checks": out["checks"]}), flush=True)
        if flat:
            knee = cap * f
    rate = None if knee is None else round(0.8 * knee, 2)
    print(json.dumps({"cell": name, "knee_rps": knee, "rate_rps": rate}),
          flush=True)
    return rate


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]),
         [float(f) for f in sys.argv[3].split(",")],
         [int(s) for s in sys.argv[4].split(",")])
