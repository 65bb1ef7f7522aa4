"""Logical bytes the embedding kernels must move, counted from the ids.

Copied from the fused-path arithmetic of ``benchmarks/emb_bench.py``
(``fused_bytes``), split into its two kernels. Rows are logical rows of
``dim`` float32 values, not the 128-lane rows a kernel may move, so the
count stays the same whatever kernel does the work.

* lookup+pool: every id's row is read once, every bag's sum written once;
* sparse Adagrad: every occurrence's gradient row is read once, every
  distinct row's table and accumulator entries are read and written once.

A batch's ids are (B, F, m), or laid out by ``bags``, one bag size per
table (``reference/shapes.py``); a batch holds B x F bags either way.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

import numpy as np

from bench.reference.shapes import bag_sizes, blocks

F32 = 4
Bags = Optional[Sequence[int]]


def lookup_bytes(ids: np.ndarray, dim: int, bags: Bags = None) -> int:
    """ids: the per-table ids of one batch."""
    n_bags = ids.shape[0] * len(bag_sizes(ids, bags))
    return F32 * dim * (ids.size + n_bags)


def distinct_rows(ids: np.ndarray, bags: Bags = None) -> int:
    """Distinct (table, id) pairs of one batch."""
    return sum(len(np.unique(b)) for b in blocks(ids, bag_sizes(ids, bags)))


def update_bytes(ids: np.ndarray, dim: int, bags: Bags = None) -> int:
    return F32 * dim * (ids.size + 4 * distinct_rows(ids, bags))


def total(batches: Iterable[np.ndarray], dim: int, fn, bags: Bags = None) -> int:
    return sum(fn(np.asarray(b), dim, bags) for b in batches)
