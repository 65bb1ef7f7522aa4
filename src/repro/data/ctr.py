"""Synthetic CTR data with ground-truth logistic structure.

The production datasets in the paper are private; we generate clicks from a hidden
teacher (true per-row embedding vectors + a random interaction MLP) so that (a)
loss decreases are meaningful, (b) different sync algorithms are comparable on an
identical stream, and (c) the stream is one-pass by construction: batch ``i`` is a
pure function of (seed, i) and is never revisited — matching the paper's one-pass
training constraint.

A batch is one compiled program per batch shape, with ``batch_idx`` traced,
so the trainer threads that draw batches at once spend one dispatch each under
the interpreter lock rather than dozens of eager operations. The seed's key is
made outside the program: the benchmark's seeds exceed int32, and a traced
seed would be converted to int32 and give a different key.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CTRTeacher:
    """Hidden ground-truth model; fields are device arrays."""

    true_rows: jnp.ndarray  # (total_rows, k) true latent per categorical row
    w_dense: jnp.ndarray  # (n_dense, k)
    w_out: jnp.ndarray  # (k,)
    bias: jnp.ndarray  # ()


def make_teacher(cfg, seed: int = 0, k: int = 8) -> CTRTeacher:
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    total = int(sum(cfg.table_sizes))
    return CTRTeacher(
        true_rows=jax.random.normal(k1, (total, k)) * 0.8,
        w_dense=jax.random.normal(k2, (cfg.n_dense_features, k)) * 0.5,
        w_out=jax.random.normal(k3, (k,)),
        bias=jnp.asarray(-1.5),  # base CTR well below 50%, like real ads data
    )


@functools.lru_cache(maxsize=256)
def _key(seed: int) -> jnp.ndarray:
    return jax.random.PRNGKey(seed)


@functools.partial(jax.jit, static_argnames=("table_sizes", "n_dense", "multi_hot", "batch_size"))
def _gen(key, batch_idx, true_rows, w_dense, w_out, bias, *,
         table_sizes: Tuple[int, ...], n_dense: int, multi_hot: int, batch_size: int):
    kd, ks, kl = jax.random.split(jax.random.fold_in(key, batch_idx), 3)
    F, m = len(table_sizes), multi_hot
    dense = jax.random.normal(kd, (batch_size, n_dense))
    sizes = jnp.asarray(table_sizes)
    # Zipf-ish skew: square a uniform to concentrate on low ids (hot rows).
    u = jax.random.uniform(ks, (batch_size, F, m))
    idx = jnp.minimum((u * u * sizes[None, :, None]).astype(jnp.int32), sizes[None, :, None] - 1)

    offsets = np.concatenate([[0], np.cumsum(table_sizes)[:-1]]).astype(np.int32)
    rows = idx + jnp.asarray(offsets)[None, :, None]
    latent = jnp.sum(jnp.take(true_rows, rows, axis=0), axis=(1, 2))  # (B, k)
    latent = latent / (F * m) + dense @ w_dense
    score = jnp.tanh(latent) @ w_out + bias
    prob = jax.nn.sigmoid(score)
    labels = jax.random.bernoulli(kl, prob).astype(jnp.float32)
    return {"dense": dense, "sparse": idx, "labels": labels}


def gen_batch(cfg, teacher: CTRTeacher, seed: int, batch_idx: int, batch_size: int) -> Dict[str, jnp.ndarray]:
    """Pure function of (seed, batch_idx): the one-pass stream.

    Draws ``normal(kd)``, ``uniform(ks)`` and ``bernoulli(kl)`` from
    ``split(fold_in(PRNGKey(seed), batch_idx), 3)``, in one compiled program
    per batch shape ``(table_sizes, n_dense_features, multi_hot,
    batch_size)``: sizes and offsets are its constants, ``batch_idx`` and the
    teacher are traced, so every batch of a shape runs the same program.
    ``PRNGKey(seed)`` is made outside it, once per seed, because a seed above
    int32 cannot be traced without changing the key."""
    return _gen(_key(seed), np.uint32(batch_idx), teacher.true_rows, teacher.w_dense,
                teacher.w_out, teacher.bias, table_sizes=tuple(cfg.table_sizes),
                n_dense=cfg.n_dense_features, multi_hot=cfg.multi_hot, batch_size=batch_size)


def generator_programs() -> int:
    """How many batch programs ``gen_batch`` has compiled: one per batch
    shape, however many batches it has drawn."""
    return _gen._cache_size()


def stream(cfg, teacher: CTRTeacher, seed: int, batch_size: int,
           n_batches: int, start: int = 0) -> Iterator[Dict[str, jnp.ndarray]]:
    for i in range(start, start + n_batches):
        yield gen_batch(cfg, teacher, seed, i, batch_size)


def normalized_entropy(bce: float, base_ctr: float) -> float:
    """The paper's quality metric style: BCE normalized by the entropy of the
    background CTR [He et al. 2014]."""
    p = base_ctr
    h = -(p * np.log(p) + (1 - p) * np.log(1 - p))
    return float(bce / h)
