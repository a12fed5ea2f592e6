"""Where the benchmark finds a cell's parts, by name.

``BENCHMARK.json`` at the repository root names every cell (``workloads``),
its configuration and its traffic mix, and every metric. Each part lives
in a file of its own under this directory, found by its name:

- ``configs/<config>.json``: the deployment (scene grid, radar parameters,
  point targets, the plan variant and precision it runs, the limit of the
  comparison with the reference);
- ``traffic/<traffic>.json``: the traffic mix (loop, batch, number of
  distinct echoes) that the one generator in ``harness`` reads;
- ``metrics/<metric>.py``: the reader of one per-layer metric, a function
  ``read(run)`` that returns a number, or None where the run holds
  nothing to read.

Adding a configuration, a mix or a per-layer metric is adding files here
and entries in ``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    workloads: Optional[list] = None    # None: every cell

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # [Metric] this cell reports with --trace 0
    per_layer: list             # [(Metric, reader)] with --trace 1


def _metric(entry: dict) -> Metric:
    return Metric(entry["name"], entry["unit"], entry.get("workloads"))


def load_reader(name: str, base: pathlib.Path = HERE) -> Callable:
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"sarbench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_cell(name: str, benchmark: pathlib.Path = ROOT / "BENCHMARK.json",
              base: pathlib.Path = HERE) -> Cell:
    bench = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark.name}; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    config = json.loads((base / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((base / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in map(_metric, bench["end_to_end"]) if m.applies_to(name)]
    per_layer = [(m, load_reader(m.name, base))
                 for m in map(_metric, bench["per_layer"])
                 if m.applies_to(name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)
