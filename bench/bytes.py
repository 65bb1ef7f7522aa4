"""Logical bytes the embedding kernels must move, counted from the ids.

Copied from the fused-path arithmetic of ``benchmarks/emb_bench.py``
(``fused_bytes``), split into its two kernels. Rows are logical rows of
``dim`` float32 values, not the 128-lane rows a kernel may move, so the
count stays the same whatever kernel does the work.

* lookup+pool: every id's row is read once, every bag's sum written once;
* sparse Adagrad: every occurrence's gradient row is read once, every
  distinct row's table and accumulator entries are read and written once.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

F32 = 4


def lookup_bytes(ids: np.ndarray, dim: int) -> int:
    """ids: (B, F, m) per-table ids of one batch."""
    n_items, n_bags = ids.size, ids.size // ids.shape[-1]
    return F32 * dim * (n_items + n_bags)


def distinct_rows(ids: np.ndarray) -> int:
    """Distinct (table, id) pairs of one (B, F, m) batch."""
    return sum(len(np.unique(ids[:, f, :])) for f in range(ids.shape[1]))


def update_bytes(ids: np.ndarray, dim: int) -> int:
    return F32 * dim * (ids.size + 4 * distinct_rows(ids))


def total(batches: Iterable[np.ndarray], dim: int, fn) -> int:
    return sum(fn(np.asarray(b), dim) for b in batches)
