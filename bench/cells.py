"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell is one entry of ``workloads``: a configuration under a traffic
mix. Every part lives in a file of its own, named after its entry, so a
new cell adds files and entries and edits nothing:

- ``bench/configs/<config>.json``: the deployment's sizes (the entry's
  ``file``), whose ``kernel`` names the plain reference
  ``bench/refs/<kernel>.py``;
- ``bench/tests/small/<config>.json``: the configuration keys that cut
  it to a size the CPU tests run in about a second;
- ``bench/traffic/<traffic>.json``: the load the generator offers;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

The tests in ``bench/tests/`` then hold every cell to the same checks,
whatever the shape of its kernel's arguments.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Callable

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, loaded from files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    ref: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, Callable]


def _module(path: pathlib.Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    """One JSON document; a missing file raises ``FileNotFoundError``."""
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return load_json(root / "BENCHMARK.json")


def reference(kernel: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The plain reference of one kernel: ``bench/refs/<kernel>.py``."""
    return _module(root / "bench" / "refs" / f"{kernel}.py",
                   f"bench_ref_{kernel}")


def reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _module(root / "bench" / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric}").read


def applies(metric: dict, cell: str) -> bool:
    """Whether a metric entry is reported in a cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Load one cell of ``BENCHMARK.json`` by its name.

    Raises:
        KeyError: no cell, configuration or metric of that name.
        FileNotFoundError: a file the entry names is missing.
    """
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; choose "
                       f"from {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[entry["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic"
                        / f"{entry['traffic']}.json")
    per_layer = [m for m in bench["per_layer"] if applies(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, ref=reference(config["kernel"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if applies(m, name)],
                per_layer=per_layer,
                readers={m["name"]: reader(m["name"], root)
                         for m in per_layer})
