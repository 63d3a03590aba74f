"""Finds a cell and everything it names, by name, from files.

``BENCHMARK.json`` at the checkout's root lists the cells.  A cell names

* a configuration, the file its entry gives (``bench/configs/``), whose
  ``graph.generator`` names a generator ``bench/generators/<name>.py``
  with a ``graph(params, rng)`` function;
* a traffic mix ``bench/traffic/<name>.json``, data, whose ``loop`` names
  the loop ``bench/loops/<loop>.py`` (a ``Loop`` class) that drives it.

Each metric but ``setup_s``, end to end or per layer, has a reader
``bench/metrics/<name>.py`` with a ``read(run)`` function.  Adding any of
these is new files plus new entries: no code here or in ``run.py`` names
a cell, a configuration, a mix, a loop, a generator or a metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Callable, Dict, List

SETUP = "setup_s"  # the one metric the harness takes itself


class CellError(Exception):
    """The cell, or something it names, is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    generator: ModuleType
    loop: ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Callable]


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {path}: {e}") from e


def load_module(path: str, kind: str, needs: str) -> ModuleType:
    """The module at ``path``, which must define ``needs``."""
    if not os.path.isfile(path):
        raise CellError(f"no {kind} at {path}")
    name = "bench_" + kind.replace(" ", "_") + "_" + os.path.basename(
        path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    if not hasattr(mod, needs):
        raise CellError(f"{path} defines no {needs}")
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise CellError(f"no workload named {name!r} in BENCHMARK.json "
                        f"(have: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    here = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(here, "traffic",
                                      w["traffic"] + ".json"))
    generator = load_module(os.path.join(
        here, "generators", f"{config['graph']['generator']}.py"),
        "generator", "graph")
    loop = load_module(os.path.join(here, "loops", f"{traffic['loop']}.py"),
                       "loop", "Loop")
    e2e = [m for m in bench.get("end_to_end", []) if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench.get("per_layer", [])
             if _applies(m, name) and m.get("moves") in reported]
    readers = {m["name"]: load_module(os.path.join(
        here, "metrics", m["name"] + ".py"), "metric reader", "read").read
        for m in e2e + layer if m["name"] != SETUP}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, generator=generator, loop=loop,
                end_to_end=e2e, per_layer=layer, readers=readers)
