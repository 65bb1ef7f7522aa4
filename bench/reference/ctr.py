"""The synthetic click stream, written out from its definition.

Clicks come from a hidden teacher: a latent row per categorical value, a
dense projection and an output vector. Batch ``i`` of stream ``seed`` is a
pure function of ``(seed, i)``; its ids are ``floor(u^2 * size)`` for
``u ~ U[0, 1)``, so low ids are hot. The same ``jax.random`` draws in the
same order give the trainer's batches bit for bit, which is how the
reference sees exactly the examples the trainer saw. The stream is drawn
op by op at the default matmul precision, as the trainer draws it: a label
is a Bernoulli draw, and a probability computed more precisely would flip
a few of them."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

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
    """(B, F, m) per-table row ids of batch ``batch_idx``."""
    _, ks, _ = _keys(seed, batch_idx)
    sizes = jnp.asarray(shapes.table_sizes)
    u = jax.random.uniform(ks, (batch_size, shapes.n_tables, shapes.multi_hot))
    return jnp.minimum((u * u * sizes[None, :, None]).astype(jnp.int32), sizes[None, :, None] - 1)


def gen_batch(shapes: Shapes, teacher, seed: int, batch_idx: int, batch_size: int):
    kd, _, kl = _keys(seed, batch_idx)
    dense = jax.random.normal(kd, (batch_size, shapes.n_dense))
    idx = gen_ids(shapes, seed, batch_idx, batch_size)
    rows = idx + jnp.asarray(shapes.offsets, jnp.int32)[None, :, None]
    latent = jnp.sum(jnp.take(teacher["true_rows"], rows, axis=0), axis=(1, 2))
    latent = latent / (shapes.n_tables * shapes.multi_hot) + dense @ teacher["w_dense"]
    score = jnp.tanh(latent) @ teacher["w_out"] + teacher["bias"]
    labels = jax.random.bernoulli(kl, jax.nn.sigmoid(score)).astype(jnp.float32)
    return {"dense": dense, "sparse": idx, "labels": labels}
