"""What one cell is, read from data files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics; ``configs/<config>.json`` holds the model as it is
run, ``mixes/<mix>.json`` the traffic parameters and
``cells/<cell>.json`` the serving shape (slots, cache depth, chunk, page
size and, for an open loop, the fixed rate), and ``families/<name>.py``
the model family that a configuration's ``reference`` key names.
Adding a cell, or a model, adds files and entries; no code here names a
cell or a model.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]       # configs/<config>.json
    mix: Dict[str, Any]          # mixes/<mix>.json
    serve: Dict[str, Any]        # cells/<cell>.json
    end_to_end: List[Dict[str, Any]]   # the cell's end-to-end metrics
    per_layer: List[Dict[str, Any]]    # the cell's per-layer metrics
    chips: int

    @property
    def open_loop(self) -> bool:
        return self.mix["loop"] == "open"


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json",
              base: Path = HERE) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = load_json(bench_path)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    serve = load_json(base / "cells" / f"{name}.json")
    for key, want in (("config", entry["config"]),
                      ("mix", entry["traffic"])):
        if serve[key] != want:
            raise ValueError(f"cells/{name}.json says {key} "
                             f"{serve[key]!r}, BENCHMARK.json {want!r}")
    return Cell(
        name=name,
        config=load_json(base / "configs" / f"{entry['config']}.json"),
        mix=load_json(base / "mixes" / f"{entry['traffic']}.json"),
        serve=serve,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        chips=int(entry["chips"]))


def family(conf: Dict[str, Any]) -> ModuleType:
    """The model plug-in ``families/<reference>.py`` that the
    configuration's ``reference`` key names (see ``families``)."""
    name = conf.get("reference")
    if name is None:
        raise ValueError(f"configuration {conf.get('name')!r} has no "
                         f"'reference' key naming its model plug-in "
                         f"families/<reference>.py")
    if not (isinstance(name, str) and name.isidentifier()
            and importlib.util.find_spec(f"families.{name}")):
        raise ValueError(f"configuration {conf.get('name')!r}: its reference "
                         f"{name!r} names no model plug-in: no file "
                         f"families/{name}.py")
    return importlib.import_module(f"families.{name}")


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(conf).program_config(conf)


def crossbar_engine(conf: Dict[str, Any]):
    """The program's ``EngineConfig`` for the configuration's
    ``crossbar`` block (the same keys for every family)."""
    from repro.core.engine import EngineConfig
    from repro.core.quant import QuantConfig

    xb = conf["crossbar"]
    return EngineConfig(
        tile_rows=xb["tile_rows"], tile_cols=xb["tile_cols"],
        mode=xb["mode"], use_kernel=xb["use_kernel"],
        quant=QuantConfig(w_bits=xb["w_bits"], in_bits=xb["in_bits"],
                          adc_bits=xb["adc_bits"],
                          bits_per_cell=xb["bits_per_cell"],
                          per_channel=xb["per_channel"]))
