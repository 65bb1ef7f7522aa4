"""Jitted public wrapper for the fused sparse-Adagrad kernels.

Flattens bag batch dims and picks the grid strategy per backend: the
run-walking DMA kernel compiled on TPU (occurrences sorted by lane row so a
lane row's duplicates form one consecutive run), the occurrence-blocked kernel
through the interpreter elsewhere. ``use_pallas=False`` falls back to the
pure-jnp oracle.

Tables arrive lane-dense (``kernels/layout.py``): ``dim`` is the logical row
width, and a plain ``(rows, dim)`` table is the one-row-per-lane-row case. No
call pads, slices or relayouts the table or the accumulator; the compiled path
only needs their width to be a multiple of 128, which
``embeddings.table.init_tables`` stores."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret, resolve_strategy
from repro.kernels.layout import row_view
from repro.kernels.sparse_adagrad.ref import sparse_adagrad_ref
from repro.kernels.sparse_adagrad.sparse_adagrad import (
    sparse_adagrad_blocked,
    sparse_adagrad_rows,
)


@functools.partial(
    jax.jit,
    static_argnames=("lr", "eps", "dim", "use_pallas", "interpret", "strategy",
                     "block_items"),
)
def sparse_adagrad_op(
    table: jnp.ndarray,
    acc: jnp.ndarray,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    *,
    lr: float,
    eps: float = 1e-8,
    dim: int | None = None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    strategy: str | None = None,
    block_items: int = 1024,
):
    """table / acc: lane-dense (lane_rows, width) holding rows of ``dim``
    (default: width), acc fp32; idx: (..., m) row ids; g_pooled: (..., dim)
    pooled grads, bag dims matching idx's. Returns (new_table, new_acc)."""
    dim = dim or table.shape[-1]
    m = idx.shape[-1]
    flat_idx = idx.reshape(-1, m).astype(jnp.int32)
    g = g_pooled.reshape(-1, dim).astype(jnp.float32)
    if not use_pallas:
        return sparse_adagrad_ref(table, acc, flat_idx, g, lr, eps, dim)
    interp = resolve_interpret(interpret)

    if resolve_strategy(strategy, tpu="rows", fallback="block") == "rows":
        return sparse_adagrad_rows(
            table, acc.astype(jnp.float32), flat_idx, g, dim=dim, lr=lr, eps=eps,
            interpret=interp)

    # Occurrence lists (bag-major) on the one-row-per-row view, plus a zero
    # gradient row for padded occurrences (bag id n_bags -> g row of zeros).
    n_bags = flat_idx.shape[0]
    n_items = n_bags * m
    rows = flat_idx.reshape(-1)
    bags = jnp.repeat(jnp.arange(n_bags, dtype=jnp.int32), m)
    t, a = row_view(table, dim), row_view(acc, dim)
    w = t.shape[-1]
    g = jnp.concatenate([jnp.pad(g, ((0, 0), (0, w - dim))), jnp.zeros((1, w))])
    bi = min(block_items, n_items)
    pad_i = (-n_items) % bi
    if pad_i:
        rows = jnp.pad(rows, (0, pad_i))  # row 0 x zero grad: exact no-op
        bags = jnp.pad(bags, (0, pad_i), constant_values=n_bags)
    new_t, new_a = sparse_adagrad_blocked(
        t, a.astype(jnp.float32), rows, bags, g,
        lr=lr, eps=eps, block_items=bi, interpret=interp)
    return new_t.reshape(table.shape), new_a.reshape(acc.shape)
