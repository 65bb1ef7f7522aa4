"""Lane-dense row layout shared by the embedding tables and their kernels.

A TPU moves HBM rows in 128-lane tiles, and the chip's compiler refuses a
DMA of a narrower row slice. So a table of ``(rows, dim)`` logical rows is
stored as ``(lane_rows, width)`` with ``width`` a multiple of 128:

* ``dim`` divides 128 -> ``p = 128 // dim`` logical rows share one lane row
  (row ``r`` lives in lane row ``r // p`` at lanes ``(r % p) * dim``). This
  is a row-major reshape, so ``(N, 64)`` packs to ``(N / 2, 128)`` at the
  same byte count;
* otherwise -> one logical row per lane row, right-padded to the next
  multiple of 128.

Every kernel wrapper reads a table through this rule from its shape and
``dim``: ``p = width // dim`` when ``dim`` divides ``width``, else 1. A plain
``(rows, dim)`` array is therefore the ``p = 1`` case of the same layout
(how tests and the interpret path hand tables in); only ``width % 128 == 0``
is required for the compiled TPU path.

The helpers work on numpy (host) and jax (device) arrays alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128


def lane_width(dim: int) -> int:
    """Stored lane width for logical rows of ``dim``."""
    return LANE if LANE % dim == 0 else -(-dim // LANE) * LANE


def rows_per_lane_row(width: int, dim: int) -> int:
    """Logical rows held by one lane row of ``width`` lanes."""
    return width // dim if width % dim == 0 else 1


def lane_rows(n_rows: int, dim: int) -> int:
    """Lane rows needed to store ``n_rows`` logical rows of ``dim``."""
    p = rows_per_lane_row(lane_width(dim), dim)
    return -(-n_rows // p)


def _xp(x):
    return np if isinstance(x, np.ndarray) else jnp


def row_view(x, dim: int):
    """One logical row per row: ``(lane_rows * p, width // p)``. Exact
    packing gives ``(rows, dim)``; a lane-padded layout is returned as is.
    On the TPU this reshape is a relayout copy — oracles and the interpret
    path only; kernels index lane rows directly."""
    return x.reshape(-1, x.shape[-1] // rows_per_lane_row(x.shape[-1], dim))


def pack_rows(x, width: int):
    """Inverse of ``row_view``: ``(n, w)`` rows -> ``(ceil(n / p), width)``,
    padding trailing rows with zeros (``w`` is ``dim``, or ``width``)."""
    xp = _xp(x)
    p = width // x.shape[-1]
    pad = (-x.shape[0]) % p
    if pad:
        x = xp.pad(x, ((0, pad), (0, 0)))
    return x.reshape(-1, width)


def pack(x):
    """Logical ``(n_rows, dim)`` -> lane-dense ``(lane_rows, lane_width)``."""
    dim = x.shape[-1]
    width = lane_width(dim)
    if rows_per_lane_row(width, dim) == 1 and width > dim:
        x = _xp(x).pad(x, ((0, 0), (0, width - dim)))
    return pack_rows(x, width)


def unpack(x, n_rows: int, dim: int):
    """Lane-dense -> logical ``(n_rows, dim)``."""
    return row_view(x, dim)[:n_rows, :dim]


@functools.partial(jax.jit, static_argnames="dim")
def take_rows(x, rows, dim: int):
    """Logical rows ``rows`` (any shape) of a lane-dense array -> ``(...,
    dim)``. Gathers whole lane rows, then selects each row's lane segment."""
    width = x.shape[-1]
    p = rows_per_lane_row(width, dim)
    v = jnp.take(x, rows // p, axis=0)
    if p == 1:
        return v[..., :dim]
    v = v.reshape(*v.shape[:-1], p, dim)
    seg = (rows % p)[..., None, None]
    return jnp.take_along_axis(v, seg, axis=-2)[..., 0, :]


@functools.partial(jax.jit, static_argnames="dim")
def set_rows(x, rows, vals, dim: int):
    """Write logical rows (1-D, unique) of a lane-dense jax array; the other
    logical rows sharing their lane rows are left untouched."""
    p = rows_per_lane_row(x.shape[-1], dim)
    lanes = (rows % p)[:, None] * dim + jnp.arange(dim)[None, :]
    return x.at[(rows // p)[:, None], lanes].set(vals.astype(x.dtype))
