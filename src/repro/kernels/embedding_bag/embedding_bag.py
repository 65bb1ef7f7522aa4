"""Pallas TPU kernels: fused embedding-bag (gather + sum-pool).

The paper's embedding PSs spend their cycles on exactly this op (lookup + partial
pooling, §3.1). Tables are lane-dense (``kernels/layout.py``): a ``(lane_rows,
width)`` array holding ``p = width // dim`` logical rows per lane row. Two grid
strategies over the same semantics (DESIGN.md §7.1):

* ``embedding_bag`` — DMA gather. The table stays in HBM (``pl.ANY``). Grid =
  (n_bags / 1024,); each step reads its bags' ``m`` lane-row ids from ``m``
  SMEM blocks of 1024, DMAs one lane row per id into a ``(m, 1024, width)``
  VMEM scratch (at most ``DMA_WINDOW`` bags' copies in flight), and
  sum-pools in-body: for each of the ``p`` lane
  segments a masked sum, then the segments fold into the ``(1024, dim)``
  output block. No block is narrower than a full lane row and the ids
  never sit in SMEM all at once, so this compiles at production widths. This
  is the path compiled on TPU.

* ``embedding_bag_blocked`` — bag-blocked. Grid = (n_bags / block_bags,); the
  table is a single VMEM-resident block and each grid step gathers + pools a
  whole block of bags in-body. Requires the (shard's) table to fit in VMEM, and
  is the off-TPU interpret path: the Pallas interpreter's per-grid-step cost is
  a buffer copy, so the coarse grid keeps the fused op fast everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import rows_per_lane_row

# An SMEM block of 1-D int32 ids must be a multiple of 1024 long; the ids
# arrive id-major (all bags' j-th ids, then the (j+1)-th), one block per j.
BLOCK_BAGS = 1024
# Bags whose row DMAs may be in flight at once (x m copies): a bounded
# window, never a whole block's 1024 x m copies queued at the DMA engine.
DMA_WINDOW = 32


def _gather_kernel(*refs, m, p, dim):
    ids_refs, (seg_ref, table_hbm, out_ref, buf, sem) = refs[:m], refs[m:]
    tb = buf.shape[1]

    def wait_bag():
        # every copy moves one lane row: a same-shaped descriptor waits one out
        for j in range(m):
            pltpu.make_async_copy(table_hbm.at[pl.ds(0, 1)], buf.at[j, pl.ds(0, 1)], sem).wait()

    def start_bag(i, carry):
        for j in range(m):
            pltpu.make_async_copy(table_hbm.at[pl.ds(ids_refs[j][i], 1)],
                                  buf.at[j, pl.ds(i, 1)], sem).start()

        @pl.when(i >= DMA_WINDOW)
        def _():
            wait_bag()

        return carry

    def drain(i, carry):
        wait_bag()
        return carry

    jax.lax.fori_loop(0, tb, start_bag, 0)
    jax.lax.fori_loop(0, min(tb, DMA_WINDOW), drain, 0)

    width = buf.shape[-1]
    segs = [jnp.zeros((tb, width), jnp.float32) for _ in range(p)]
    seg = seg_ref[...]  # (tb, m) lane segment of each id
    for j in range(m):
        x = buf[j].astype(jnp.float32)
        if p == 1:
            segs[0] = segs[0] + x
        else:
            s = seg[:, j:j + 1]
            for k in range(p):
                segs[k] = segs[k] + jnp.where(s == k, x, 0.0)
    out = segs[0][:, :dim]
    for k in range(1, p):
        out = out + segs[k][:, k * dim:(k + 1) * dim]
    out_ref[...] = out


def embedding_bag(table: jnp.ndarray, idx: jnp.ndarray, *, dim: int,
                  interpret: bool = False) -> jnp.ndarray:
    """table: (lane_rows, width) lane-dense, ``p = width // dim`` rows per lane
    row (1 if dim does not divide width); idx: (n_bags, m) int32 logical row
    ids, n_bags a multiple of ``BLOCK_BAGS`` -> (n_bags, dim) sums."""
    n_bags, m = idx.shape
    width = table.shape[-1]
    p = rows_per_lane_row(width, dim)
    tb = BLOCK_BAGS
    assert n_bags % tb == 0, (n_bags, tb)
    nb = n_bags // tb
    # id-major flattening: a transpose is cheap where flattening the narrow
    # (n_bags, m) array directly costs the TPU compiler seconds
    lane_ids = (idx // p).T.reshape(-1)
    smem = [pl.BlockSpec((tb,), lambda b, j=j: (j * nb + b,), memory_space=pltpu.SMEM)
            for j in range(m)]
    return pl.pallas_call(
        functools.partial(_gather_kernel, m=m, p=p, dim=dim),
        grid=(nb,),
        in_specs=smem + [
            pl.BlockSpec((tb, m), lambda b: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tb, dim), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_bags, dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, tb, width), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(*[lane_ids] * m, idx % p, table)


def _blocked_kernel(idx_ref, table_ref, out_ref):
    b = pl.program_id(0)
    ids = idx_ref[b]  # (block_bags, m) row ids from SMEM
    block_bags, m = ids.shape
    vecs = jnp.take(table_ref[...], ids.reshape(-1), axis=0)
    vecs = vecs.reshape(block_bags, m, -1).astype(jnp.float32)
    out_ref[...] = jnp.sum(vecs, axis=1)


def embedding_bag_blocked(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    block_bags: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """table: (rows, w) one logical row per row (``layout.row_view``); idx:
    (n_bags, m) int32 -> (n_bags, w) sums.

    n_bags must be a multiple of ``block_bags`` (the ops.py wrapper pads); the
    whole table is one resident block, so rows * w must fit in VMEM — fine for
    plan-sharded tables and for the interpreter, not for a monolithic
    production table (use ``embedding_bag`` there)."""
    n_bags, m = idx.shape
    rows, d = table.shape
    assert n_bags % block_bags == 0, (n_bags, block_bags)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_bags // block_bags,),
        in_specs=[pl.BlockSpec((rows, d), lambda b, idx_ref: (0, 0))],
        out_specs=pl.BlockSpec((block_bags, d), lambda b, idx_ref: (b, 0)),
    )
    return pl.pallas_call(
        _blocked_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_bags, d), jnp.float32),
        interpret=interpret,
    )(idx.reshape(n_bags // block_bags, block_bags, m), table)
