"""Backend detection shared by all kernel wrappers.

The Pallas kernels target TPU; everywhere else they run through the Pallas
interpreter (numerically identical, jit-compatible). The backend is probed
once per process — wrappers default ``interpret=None`` and resolve it here
instead of hardcoding ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> compiled Pallas on TPU, interpreter elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def resolve_strategy(strategy: Optional[str], *, tpu: str, fallback: str) -> str:
    """Pick a kernel grid strategy per backend: ``tpu`` names the
    fine-grid DMA kernel compiled on TPU, ``fallback`` the
    coarse-grid variant that stays fast through the interpreter (its
    per-grid-step cost is a buffer copy). Explicit ``strategy`` wins."""
    if strategy is None:
        return tpu if on_tpu() else fallback
    if strategy not in (tpu, fallback):
        raise ValueError(
            f"unknown kernel strategy {strategy!r}; expected {tpu!r} or "
            f"{fallback!r}")
    return strategy
