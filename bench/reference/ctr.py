"""The synthetic click stream, written out from its definition.

Clicks come from a hidden teacher: a latent row per categorical value, a
dense projection and an output vector. Batch ``i`` of stream ``seed`` is a
pure function of ``(seed, i)``; its ids are ``floor(u^2 * size)`` for
``u ~ U[0, 1)``, so low ids are hot, drawn in one ``uniform`` call in the
batch's id layout (``shapes.py``: (B, F, m), or (B, sum of m_f) for bag
sizes per table). The same ``jax.random`` draws in the same order give the
trainer's batches bit for bit, which is how the reference sees exactly the
examples the trainer saw. The stream is drawn
op by op at the default matmul precision, as the trainer draws it: a label
is a Bernoulli draw, and a probability computed more precisely would flip
a few of them."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.shapes import Shapes

TEACHER_K = 8


def make_teacher(shapes: Shapes, seed: int) -> Dict[str, jnp.ndarray]:
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "true_rows": jax.random.normal(k1, (shapes.n_rows, TEACHER_K)) * 0.8,
        "w_dense": jax.random.normal(k2, (shapes.n_dense, TEACHER_K)) * 0.5,
        "w_out": jax.random.normal(k3, (TEACHER_K,)),
        "bias": jnp.asarray(-1.5),
    }


def _keys(seed: int, batch_idx: int):
    return jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), batch_idx), 3)


def gen_ids(shapes: Shapes, seed: int, batch_idx: int, batch_size: int) -> jnp.ndarray:
    """Per-table row ids of batch ``batch_idx``, in the id layout of ``shapes``."""
    _, ks, _ = _keys(seed, batch_idx)
    sizes = jnp.asarray(shapes.by_column(shapes.table_sizes, np.int32))
    u = jax.random.uniform(ks, shapes.id_shape(batch_size))
    return jnp.minimum((u * u * sizes).astype(jnp.int32), sizes - 1)


def gen_batch(shapes: Shapes, teacher, seed: int, batch_idx: int, batch_size: int):
    kd, _, kl = _keys(seed, batch_idx)
    dense = jax.random.normal(kd, (batch_size, shapes.n_dense))
    idx = gen_ids(shapes, seed, batch_idx, batch_size)
    rows = idx + jnp.asarray(shapes.by_column(shapes.offsets), jnp.int32)
    latent = jnp.sum(jnp.take(teacher["true_rows"], rows, axis=0), axis=tuple(range(1, idx.ndim)))
    latent = latent / shapes.n_ids + dense @ teacher["w_dense"]
    score = jnp.tanh(latent) @ teacher["w_out"] + teacher["bias"]
    labels = jax.random.bernoulli(kl, jax.nn.sigmoid(score)).astype(jnp.float32)
    return {"dense": dense, "sparse": idx, "labels": labels}
