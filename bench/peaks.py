"""Published peaks of each chip, from ``peaks.json``, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {TABLE.name}; "
                       f"known: {sorted(devices)}")
    return {k: float(v) for k, v in devices[device_kind].items()}
