"""The comparison that decides ``correct``.

Three numbers, each against a limit kept in ``bench/workloads/<cell>.json``
(``PERF.md`` gives the readings each limit was set from):

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over every step of the check run;
* ``grad_gap``: the largest gap, over the leaves (dense weights, and each
  embedding table), between the norms of the first step's gradient, each
  measured against the larger of the reference's norm of that leaf and of
  the median leaf;
* ``change_gap``: the same for the norm of each leaf's change over the run,
  per replica for the dense leaves. Leaves whose reference gradient is under
  a thousandth of the median leaf's move by round-off alone and are left
  out.

The widest gaps swing from seed to seed: on a rare seed the two sides part
after the first step and the loss and change gaps read hundreds of times
their usual value, the same on every run of that seed (``PERF.md``).

A reading that is missing, or not a number, fails."""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from bench.reference.train import Readings

NAMES = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3  # gradient share of the median leaf under which a leaf is left out


def _gap(prog: Dict, ref: Dict, keys) -> float:
    keys = list(keys)
    if not keys or any(k not in prog for k in keys):
        return math.inf
    med = float(np.median([ref[k] for k in keys]))
    worst = 0.0
    for k in keys:
        p, r = prog[k], ref[k]
        if not (math.isfinite(p) and math.isfinite(r)):
            return math.inf
        worst = max(worst, abs(p - r) / max(abs(r), med))
    return worst


def _base(name: str) -> str:
    """``r2.top.0.w`` -> ``top.0.w``; a table keeps its name."""
    return name.split(".", 1)[1] if name.startswith("r") and name[1].isdigit() else name


def gaps(prog: Optional[Readings], ref: Readings) -> Dict[str, float]:
    if prog is None:
        return {k: math.inf for k in NAMES}
    loss = math.inf
    if prog.losses.keys() == ref.losses.keys() and ref.losses:
        loss = max(abs(prog.losses[k] - ref.losses[k]) / abs(ref.losses[k]) for k in ref.losses)
        loss = loss if math.isfinite(loss) else math.inf
    med_g = float(np.median(list(ref.grad.values())))
    moving = [k for k in ref.change if ref.grad.get(_base(k), 0.0) >= STILL * med_g]
    return {
        "loss_gap": loss,
        "grad_gap": _gap(prog.grad, ref.grad, ref.grad.keys()),
        "change_gap": _gap(prog.change, ref.change, moving),
    }


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in limits and values[k] <= limits[k] for k in values) and set(values) == set(NAMES)
