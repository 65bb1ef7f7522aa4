"""Pure-jnp oracle for the fused sparse-Adagrad kernels.

The array-level oracle behind ``embeddings.table.sparse_adagrad_update``:
duplicate rows scatter-ADD into the accumulator, and every occurrence's row
step is scaled by the FINAL accumulator (scatter-add first, gather after)."""
import jax
import jax.numpy as jnp

from repro.kernels.layout import row_view


def sparse_adagrad_ref(
    table: jnp.ndarray,
    acc: jnp.ndarray,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    lr: float,
    eps: float = 1e-8,
    dim: int | None = None,
):
    """table / acc: lane-dense rows of ``dim`` (default: one row per table
    row), acc fp32; idx: (n_bags, m) row ids; g_pooled: (n_bags, dim).
    Returns (new_table, new_acc) in the input layout."""
    dim = dim or table.shape[-1]
    n_bags, m = idx.shape
    t, a = row_view(table, dim), row_view(acc, dim)
    rows = idx.reshape(-1)  # (n_bags * m,) occurrence order: bag-major
    g = jnp.repeat(g_pooled.astype(jnp.float32), m, axis=0)
    g = jnp.pad(g, ((0, 0), (0, t.shape[-1] - dim)))
    a = a.at[rows].add(g * g)
    scale = lr * jax.lax.rsqrt(a.at[rows].get() + eps)
    t = t.at[rows].add((-scale * g).astype(t.dtype))
    return t.reshape(table.shape), a.reshape(acc.shape)
