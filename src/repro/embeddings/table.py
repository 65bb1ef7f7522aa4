"""Sharded embedding tables with Hogwild-style sparse Adagrad updates.

All categorical tables are packed into ONE collection of ``total_rows`` rows
of ``dim`` — the TPU-native analogue of the paper's embedding parameter
servers. Adagrad accumulators are co-located with the rows (paper §3.2).
Both are stored lane-dense from ``init_tables`` on (``kernels/layout.py``):
with ``dim`` dividing 128, ``128 // dim`` rows share one 128-lane row, so a
``d=64`` collection is a ``(total_rows / 2, 128)`` array and no kernel call
ever pads or relayouts it. ``to_logical`` / ``from_logical`` convert to and
from the ``(total_rows, dim)`` view that checkpoints and tests read. Updates
are immediate scatter-adds per trainer with no cross-replica gradient
averaging: the preserved Hogwild property (see DESIGN.md §2).

Forward (``lookup``) and backward (``sparse_adagrad_update_fused``) dispatch to
the fused Pallas kernels by default (``kernels/embedding_bag`` lookup+pool,
``kernels/sparse_adagrad`` scatter-Adagrad; compiled on TPU, interpreter
elsewhere — DESIGN.md §7). ``lookup_ref`` / ``sparse_adagrad_update`` are the
pure-jnp oracles the kernels are tested against.

The greedy LPT bin-packing planner mirrors the paper's load-balancing of tables
across embedding PSs; the SPMD path uses uniform row sharding, while
``embeddings/shards.py`` consumes the plan directly: ``ThreadedShadowRunner``
splits the packed table into per-PS shards with genuinely independent Hogwild
state and routes lookups by the plan.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import layout
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op
from repro.kernels.sparse_adagrad.ref import sparse_adagrad_ref
from repro.models.layers import Params


@dataclass(frozen=True)
class TableSpec:
    sizes: Tuple[int, ...]
    dim: int
    multi_hot: int

    @property
    def offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.sizes)[:-1]]).astype(np.int32)

    @property
    def total_rows(self) -> int:
        return int(sum(self.sizes))


def spec_from_config(cfg) -> TableSpec:
    return TableSpec(tuple(cfg.table_sizes), cfg.embedding_dim, cfg.multi_hot)


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_tables(spec: TableSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    """Lane-dense ``{"table", "acc"}``. Row ``r`` holds the same values as
    row ``r`` of ``normal(key, (total_rows, dim)) * dim**-0.5``: the
    generator is keyed by the linear element index, so the packed shape is
    drawn directly — the logical array never exists on the device. Slots
    past ``total_rows`` are zero, as ``from_logical`` leaves them."""
    d, n = spec.dim, spec.total_rows
    width, rows = layout.lane_width(d), layout.lane_rows(n, d)
    p = layout.rows_per_lane_row(width, d)
    if p * d == width:
        table = jax.random.normal(key, (rows, width), jnp.float32)
        row = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) * p
            + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // d
        )
        table = jnp.where(row < n, table, 0.0)
    else:
        table = layout.pack(jax.random.normal(key, (n, d), jnp.float32))
    return {
        "table": (table * d**-0.5).astype(dtype),
        "acc": jnp.zeros((rows, width), jnp.float32),
    }


def to_logical(state: Params, spec: TableSpec) -> Params:
    """Lane-dense state -> host ``(total_rows, dim)`` numpy arrays (the
    checkpoint / external view)."""
    return {
        k: layout.unpack(np.asarray(v), spec.total_rows, spec.dim) for k, v in state.items()
    }


def from_logical(state: Params, spec: TableSpec) -> Params:
    """Inverse of ``to_logical``: packed on the host, then moved to the
    device once."""
    return {k: jnp.asarray(layout.pack(np.asarray(v))) for k, v in state.items()}


def global_row_ids(spec: TableSpec, idx: jnp.ndarray) -> jnp.ndarray:
    """idx: (B, F, m) per-feature local row ids -> global packed row ids."""
    offsets = jnp.asarray(spec.offsets)
    return idx + offsets[None, :, None]


def lookup_ref(state: Params, spec: TableSpec, idx: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle for ``lookup``: dense take + sum-pool (materializes the
    (B, F, m, d) gathered vectors the fused kernel never forms)."""
    rows = global_row_ids(spec, idx)
    vecs = layout.take_rows(state["table"], rows, spec.dim)  # (B, F, m, d)
    return jnp.sum(vecs, axis=2)


def lookup(
    state: Params,
    spec: TableSpec,
    idx: jnp.ndarray,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Sum-pooled lookup. idx: (B, F, m) -> (B, F, dim). One fused
    lookup+pool kernel launch by default; ``use_pallas=False`` is the oracle."""
    if not use_pallas:
        return lookup_ref(state, spec, idx)
    rows = global_row_ids(spec, idx)
    return embedding_bag_op(state["table"], rows, dim=spec.dim, interpret=interpret)


def sparse_adagrad_update(
    state: Params,
    spec: TableSpec,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    lr: float,
    eps: float = 1e-8,
) -> Params:
    """Row-sparse Adagrad. g_pooled: (B, F, d) — with sum pooling each of the
    multi-hot rows receives the pooled gradient unchanged. Duplicate rows in a
    batch scatter-add, which matches Hogwild's unsynchronized-accumulate."""
    rows = global_row_ids(spec, idx).reshape(-1, idx.shape[-1])  # (B*F, m)
    g = g_pooled.reshape(-1, spec.dim)
    table, acc = sparse_adagrad_ref(state["table"], state["acc"], rows, g, lr, eps, spec.dim)
    return {"table": table, "acc": acc}


def sparse_adagrad_update_fused(
    state: Params,
    spec: TableSpec,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    lr: float,
    eps: float = 1e-8,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Params:
    """``sparse_adagrad_update`` through the fused scatter kernel: acc update +
    rsqrt-scaled row add in one launch, duplicate-row accumulate semantics
    identical to the oracle (tested in tests/test_embedding_substrate.py)."""
    if not use_pallas:
        return sparse_adagrad_update(state, spec, idx, g_pooled, lr, eps)
    bags = global_row_ids(spec, idx).reshape(-1, idx.shape[-1])  # (B*F, m)
    g = g_pooled.reshape(-1, g_pooled.shape[-1])
    table, acc = sparse_adagrad_op(
        state["table"], state["acc"], bags, g, lr=lr, eps=eps, dim=spec.dim,
        interpret=interpret)
    return {"table": table, "acc": acc}


def bin_pack(costs: Sequence[float], n_bins: int) -> List[List[int]]:
    """Greedy LPT (longest-processing-time) bin packing: the paper's strategy for
    distributing embedding lookup cost across embedding PSs (§3.1)."""
    order = np.argsort(costs)[::-1]
    loads = np.zeros(n_bins)
    bins: List[List[int]] = [[] for _ in range(n_bins)]
    for i in order:
        b = int(np.argmin(loads))
        bins[b].append(int(i))
        loads[b] += costs[i]
    return bins


def lookup_costs(spec: TableSpec, batch_size: int) -> np.ndarray:
    """Profiled-cost model: lookups dominate; cost ~ batch * multi_hot * dim,
    identical per feature here, plus a memory-residency term ~ rows."""
    per_lookup = batch_size * spec.multi_hot * spec.dim
    return np.array([per_lookup + 1e-3 * s * spec.dim for s in spec.sizes])
