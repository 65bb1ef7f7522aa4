"""Pure-jnp oracle for the embedding-bag kernel."""
import jax.numpy as jnp

from repro.kernels.layout import take_rows


def embedding_bag_ref(table: jnp.ndarray, idx: jnp.ndarray, dim: int | None = None) -> jnp.ndarray:
    """table: lane-dense rows of ``dim`` (default: one row per table row);
    idx: (n_bags, m) -> (n_bags, dim) sum-pooled, fp32."""
    rows = take_rows(table, idx, dim or table.shape[-1])
    return jnp.sum(rows.astype(jnp.float32), axis=1)
