"""Pallas TPU kernels: fused row-sparse Adagrad scatter (the embedding backward).

One launch applies the whole backward for a batch of multi-hot bags: the
accumulator update ``acc[r] += g^2`` and the rsqrt-scaled row add
``table[r] -= lr * rsqrt(acc_final[r] + eps) * g`` — with duplicate-row
ACCUMULATE semantics matching the pytree oracle exactly: every occurrence's
``g^2`` lands in the accumulator first, and the row step is scaled by that
FINAL accumulator (``embeddings.table.sparse_adagrad_update`` computes the
same thing via scatter-add + gather).

Tables are lane-dense (``kernels/layout.py``): ``p = width // dim`` logical
rows share one 128-lane row. Two grid strategies over the same semantics
(DESIGN.md §7.1):

* ``sparse_adagrad_rows`` — run-walking DMA. Occurrences are sorted by LANE
  row (stable, so duplicates keep their order) and lays each one's
  pooled gradient at its row's lane segment, zeros elsewhere. Grid =
  (n_items / 1024,): each step takes 1024 sorted occurrences — their lane
  row ids and run flags from an SMEM block, their gradients as a (1024,
  width) VMEM block — and walks them. A run's first occurrence DMAs the acc
  and table lane rows HBM->VMEM; every occurrence adds ``g^2`` and ``g``; the
  run's last applies the final-acc-scaled step and DMAs both rows back.
  Lanes of a lane row that no occurrence touched see ``+0`` and ``-0``: two
  rows sharing one lane row never lose each other's update. The VMEM
  run state persists across grid steps, so a run may straddle blocks.
  Tables stay in HBM (``pl.ANY``) and are aliased in/out: untouched lane
  rows are never read or written. This is the path compiled on TPU.

* ``sparse_adagrad_blocked`` — occurrence-blocked. Grid = (n_items / block,);
  table, acc, and g_pooled are VMEM-resident blocks, each step scatter-adds a
  block of occurrences in-body, and the last step applies the fused row
  update for the whole table at once. Requires the (shard's) table to fit in
  VMEM; this is the off-TPU interpret path (the interpreter's per-grid-step
  cost is a buffer copy, so the coarse grid keeps it fast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import rows_per_lane_row

# Occurrences per grid step: an SMEM block of 1-D int32 must be a multiple
# of 1024 long.
ITEMS_PER_BLOCK = 1024
FIRST, LAST = 1, 2  # run flags, in the low bits of each code


def _rows_kernel(code_ref, g_ref, table_in, acc_in, table_ref, acc_ref,
                 t_row, a_row, g_sum, sem, *, lr: float, eps: float,
                 n_items: int):
    del table_in, acc_in  # aliased to table_ref / acc_ref
    block = pl.program_id(0)

    def move_rows(q, to_hbm):
        # the run's acc and table lane rows, both copies in flight at once
        pairs = [(acc_ref.at[pl.ds(q, 1)], a_row), (table_ref.at[pl.ds(q, 1)], t_row)]
        copies = [pltpu.make_async_copy(*(pair[::-1] if to_hbm else pair), sem.at[k])
                  for k, pair in enumerate(pairs)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    def occurrence(i, carry):
        @pl.when(block * ITEMS_PER_BLOCK + i < n_items)
        def _():
            code = code_ref[i]
            q = code >> 2  # lane row

            @pl.when((code & FIRST) != 0)
            def _():
                move_rows(q, to_hbm=False)
                g_sum[...] = jnp.zeros_like(g_sum)

            g = g_ref[pl.ds(i, 1), :]
            a_row[...] += g * g
            g_sum[...] += g

            @pl.when((code & LAST) != 0)
            def _():
                scale = lr * jax.lax.rsqrt(a_row[...] + eps)
                t_row[...] = (t_row[...].astype(jnp.float32)
                              - scale * g_sum[...]).astype(t_row.dtype)
                move_rows(q, to_hbm=True)

        return carry

    jax.lax.fori_loop(0, ITEMS_PER_BLOCK, occurrence, 0)


def _run_layout(idx, g, width, dim):
    """Occurrences sorted by lane row (stable), each coded ``lane_row * 4 +
    FIRST|LAST`` and its gradient laid at its lane segment (zeros elsewhere),
    padded to whole kernel blocks. Occurrences are taken id-major (hot index
    j of every bag, then j + 1): a transpose is cheap where flattening the
    narrow (n_bags, m) array directly costs the TPU compiler seconds; the
    stable sort keeps each row's duplicates in this order."""
    n_bags, m = idx.shape
    rows = idx.T.reshape(-1)
    bags = jnp.tile(jnp.arange(n_bags, dtype=jnp.int32), m)
    p = rows_per_lane_row(width, dim)
    q = rows // p
    order = jnp.argsort(q, stable=True)
    q, seg, bags = q[order], (rows % p)[order], bags[order]
    new = q[1:] != q[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), new])
    last = jnp.concatenate([new, jnp.ones((1,), bool)])
    code = q * 4 + jnp.where(first, FIRST, 0) + jnp.where(last, LAST, 0)
    g = jnp.take(g, bags, axis=0)
    if p > 1:
        g = jnp.concatenate([jnp.where((seg == k)[:, None], g, 0.0) for k in range(p)], axis=1)
    elif width > dim:
        g = jnp.pad(g, ((0, 0), (0, width - dim)))
    pad = (-q.shape[0]) % ITEMS_PER_BLOCK
    return jnp.pad(code, (0, pad)), jnp.pad(g, ((0, pad), (0, 0)))


def sparse_adagrad_rows(
    table: jnp.ndarray,
    acc: jnp.ndarray,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    *,
    dim: int,
    lr: float,
    eps: float = 1e-8,
    interpret: bool = False,
):
    """table / acc: (lane_rows, width) lane-dense, ``p = width // dim`` rows
    per lane row (1 if dim does not divide width), acc fp32; idx: (n_bags,
    m) int32 logical row ids; g_pooled: (n_bags, dim) fp32. Returns
    (new_table, new_acc); untouched lane rows are bit-identical (aliased
    in/out)."""
    n_items = idx.size
    code, g_lanes = _run_layout(idx, g_pooled, table.shape[-1], dim)
    n_pad, width = g_lanes.shape
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_rows_kernel, lr=lr, eps=eps, n_items=n_items),
        grid=(n_pad // ITEMS_PER_BLOCK,),
        in_specs=[
            pl.BlockSpec((ITEMS_PER_BLOCK,), lambda b: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((ITEMS_PER_BLOCK, width), lambda b: (b, 0)),
            hbm, hbm,
        ],
        out_specs=[hbm, hbm],
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, width), table.dtype),
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # operand order: (code, g_lanes, table, acc)
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(code, g_lanes, table, acc)


def _blocked_kernel(rows_ref, bags_ref, g_ref, table_ref, acc_ref,
                    out_table_ref, out_acc_ref, sum_ref,
                    *, lr: float, eps: float, n_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_acc_ref[...] = acc_ref[...]
        out_table_ref[...] = table_ref[...]
        sum_ref[...] = jnp.zeros_like(sum_ref)

    ids = rows_ref[i]  # (block_items,) row ids of this occurrence block
    g = jnp.take(g_ref[...], bags_ref[i], axis=0).astype(jnp.float32)
    out_acc_ref[...] = out_acc_ref[...].at[ids].add(g * g)
    sum_ref[...] = sum_ref[...].at[ids].add(g)

    @pl.when(i == n_blocks - 1)
    def _():
        # All g^2 landed: one vectorized final-acc-scaled step for every row
        # (untouched rows have sum 0 — their step is exactly zero).
        scale = lr * jax.lax.rsqrt(out_acc_ref[...] + eps)
        out_table_ref[...] = (
            table_ref[...].astype(jnp.float32) - scale * sum_ref[...]
        ).astype(out_table_ref.dtype)


def sparse_adagrad_blocked(
    table: jnp.ndarray,
    acc: jnp.ndarray,
    rows: jnp.ndarray,
    bags: jnp.ndarray,
    g_pooled: jnp.ndarray,
    *,
    lr: float,
    eps: float = 1e-8,
    block_items: int = 1024,
    interpret: bool = False,
):
    """table / acc: (rows, w) one logical row per row (``layout.row_view``),
    acc fp32; rows/bags: (n_items,) int32 occurrence row and bag ids, in any
    order; g_pooled: (n_bags, w). n_items must be a multiple of
    ``block_items`` (the ops.py wrapper pads with zero-gradient
    occurrences). Returns (new_table, new_acc), aliased in/out."""
    n_items = rows.shape[0]
    n_rows, d = table.shape
    n_bags = g_pooled.shape[0]
    assert n_items % block_items == 0, (n_items, block_items)
    n_blocks = n_items // block_items
    table_spec = pl.BlockSpec((n_rows, d), lambda i, rows_ref, bags_ref: (0, 0))
    g_spec = pl.BlockSpec((n_bags, d), lambda i, rows_ref, bags_ref: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[g_spec, table_spec, table_spec],
        out_specs=[table_spec, table_spec],
        scratch_shapes=[pltpu.VMEM((n_rows, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_blocked_kernel, lr=lr, eps=eps, n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ],
        # operand order incl. scalar prefetch: (rows, bags, g, table, acc)
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(rows.reshape(n_blocks, block_items), bags.reshape(n_blocks, block_items),
      g_pooled, table, acc)
