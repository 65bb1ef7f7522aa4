"""One cell of ``BENCHMARK.json``, with the files the harness finds by name.

``bench/configs/<config>.json`` holds a configuration's sizes,
``bench/traffic/<traffic>.json`` the training job run on it, and
``bench/workloads/<cell>.json`` the limits of the cell's comparison. A
metric applies to the cells its ``workloads`` key lists, or to every cell
without one; a per-layer metric is read by ``bench/metrics/<name>.py``."""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def load(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = _read(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark.name}; known: {sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    applies = lambda m: name in m.get("workloads", [name])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read(ROOT / files[w["config"]]),
        traffic=_read(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_read(BENCH / "workloads" / f"{name}.json")["limits"],
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )
