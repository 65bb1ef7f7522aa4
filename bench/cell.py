"""One cell of ``BENCHMARK.json``, with the files the harness finds by name.

``bench/configs/<config>.json`` holds a configuration's sizes,
``bench/traffic/<traffic>.json`` the training job run on it, and
``bench/workloads/<cell>.json`` the limits of the cell's comparison. A
metric applies to the cells its ``workloads`` key lists, or to every cell
without one; a per-layer metric is read by ``bench/metrics/<name>.py``.

A configuration's ``interaction`` key names
``bench/reference/interactions/<interaction>.py``, the arithmetic between
the bottom and the top MLP. Its module gives ``out_dim``, ``n_keys``,
``init``, ``apply``, ``forward_flops`` and ``PROGRAM_KEYS``; ``dot.py``
there says what each is. A configuration's ``multi_hot`` is one bag size
for every table or a list of one per table (``bench/reference/shapes.py``).
So a new DLRM architecture is new files: its config, its interaction, its
traffic and workload files, its metric readers and its entries in
``BENCHMARK.json``. Metric readers and interactions are loaded from their
files by ``load_module``; a name with no file fails there, naming the
file."""
from __future__ import annotations

import functools
import importlib.util
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


@functools.lru_cache(maxsize=None)
def load_module(directory: Path, name: str):
    """The module ``<directory>/<name>.py``, loaded from its file once per process."""
    path = Path(directory) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{Path(directory).name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
