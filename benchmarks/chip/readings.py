#!/usr/bin/env python3
"""The two readings a cell's limits are set from, run on the chip when
the cell is defined.

    python3 benchmarks/chip/readings.py <cell> <seconds> <seeds> \
        [<control seeds>]

One process.  Every seed is a whole run of the cell at its own load
(``run.run_cell``): the program's numbers (the lower readings) on every
seed, and, on the control seeds, each of the configuration's controls
judged by the same checks (the upper readings).  Prints one JSON line
per run; a control has to read ``correct: false``.
"""
from __future__ import annotations

import gc
import json
import sys

import run as R  # first: puts the program's sources on the path
import reference
import spec


def main(name: str, seconds: float, seeds, control_seeds) -> None:
    cell = spec.load_cell(name)
    R._set_up_cache()
    controls = tuple(reference.controls_for(cell.config))
    for seed in seeds:
        gc.collect()
        out = R.run_cell(cell, seed, seconds, trace=False,
                         controls=controls if seed in control_seeds else ())
        print(json.dumps({
            "cell": name, "seed": seed, "correct": out["correct"],
            "checks": out["checks"], "controls": out.get("controls"),
            "tokens_compared": out["info"]["tokens_compared"],
            "reference_s": out["info"]["reference_s"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]),
         [int(s) for s in sys.argv[3].split(",")],
         {int(s) for s in sys.argv[4].split(",")} if len(sys.argv) > 4
         else set())
