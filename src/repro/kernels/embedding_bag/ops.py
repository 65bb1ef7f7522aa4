"""Jitted public wrapper for the embedding-bag kernels.

Flattens arbitrary bag batch dims and picks the grid strategy per backend: the
DMA-gather kernel compiled on TPU (the table stays in HBM), the bag-blocked
kernel through the interpreter elsewhere (coarse grid — the interpreter's cost
is per grid step). Both are the same fused lookup+pool launch; ``strategy``
forces one explicitly and ``use_pallas=False`` falls back to the pure-jnp
oracle.

Tables arrive lane-dense (``kernels/layout.py``): ``dim`` is the logical row
width, and a plain ``(rows, dim)`` table is the one-row-per-lane-row case. No
call pads, slices or relayouts the table; the compiled path only needs its
width to be a multiple of 128, which ``embeddings.table.init_tables`` stores."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret, resolve_strategy
from repro.kernels.embedding_bag.embedding_bag import (
    BLOCK_BAGS,
    embedding_bag,
    embedding_bag_blocked,
)
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.layout import row_view


def _pad_bags(idx: jnp.ndarray, multiple: int) -> jnp.ndarray:
    """Pad (n_bags, m) ids to a bag multiple; padded bags look up row 0 and
    are sliced off by the caller."""
    pad = (-idx.shape[0]) % multiple
    return jnp.pad(idx, ((0, pad), (0, 0))) if pad else idx


@functools.partial(
    jax.jit,
    static_argnames=("dim", "use_pallas", "interpret", "strategy", "block_bags"),
)
def embedding_bag_op(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    dim: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    strategy: str | None = None,
    block_bags: int = 512,
) -> jnp.ndarray:
    """table: lane-dense (lane_rows, width) holding rows of ``dim`` (default:
    width, one row per lane row); idx: (..., m) -> (..., dim) sum-pooled."""
    dim = dim or table.shape[-1]
    flat_idx = idx.reshape(-1, idx.shape[-1]).astype(jnp.int32)
    n_bags = flat_idx.shape[0]
    if not use_pallas:
        out = embedding_bag_ref(table, flat_idx, dim)
    elif resolve_strategy(strategy, tpu="stream", fallback="block") == "stream":
        out = embedding_bag(
            table, _pad_bags(flat_idx, BLOCK_BAGS),
            dim=dim, interpret=resolve_interpret(interpret))[:n_bags]
    else:
        bb = min(block_bags, n_bags)
        out = embedding_bag_blocked(
            row_view(table, dim), _pad_bags(flat_idx, bb), block_bags=bb,
            interpret=resolve_interpret(interpret))[:n_bags, :dim]
    return out.reshape(*idx.shape[:-1], dim)
