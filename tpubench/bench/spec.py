"""What a cell is, read from files: ``BENCHMARK.json`` names the cells; each
cell names a configuration file under ``configs/`` and a traffic file under
``traffic/``; each per-layer metric is a reader under ``metrics/``.

Nothing here knows a configuration, a mix or a metric by name, so a later
change adds a cell with new files and new entries alone.

A metric named ``<quantity>.<qualifier>`` is ``<quantity>`` measured in
the cells it lists, split off where those cells take a bound or an
end-to-end metric of their own (``train_tokens_per_s.save``).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

#: ``<checkout>/tpubench``
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def quantity(name: str) -> str:
    """What a metric name measures: ``step_mfu.save`` -> ``step_mfu``."""
    return name.split(".", 1)[0]


class SpecError(ValueError):
    """A benchmark file is missing or does not say what a cell needs."""


def _load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing benchmark file {path}") from e


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    bound: Optional[float] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str, end_to_end: Dict[str, "Metric"]) -> bool:
        if self.workloads is not None:
            return cell in self.workloads
        if self.moves is not None:  # a per-layer metric follows what it moves
            return end_to_end[self.moves].applies_to(cell, end_to_end)
        return True


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    bench_dir: str = BENCH_DIR


def load_cell(name: str, benchmark_json: Optional[str] = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bj = _load_json(benchmark_json or os.path.join(os.path.dirname(bench_dir),
                                                   "BENCHMARK.json"))
    cells = {w["name"]: w for w in bj["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bj["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name} names unknown config {w['config']}")
    root = os.path.dirname(bench_dir)
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    e2e = {m["name"]: Metric(**m) for m in bj["end_to_end"]}
    per_layer = [Metric(**m) for m in bj["per_layer"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in e2e.values() if m.applies_to(name, e2e)],
        per_layer=[m for m in per_layer if m.applies_to(name, e2e)],
        bench_dir=bench_dir)
