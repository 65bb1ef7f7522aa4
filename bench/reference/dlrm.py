"""DLRM training arithmetic in plain ``jax.numpy`` (arXiv:1906.00091).

Bottom MLP (ReLU after every layer) -> pairwise dot interaction of the
bottom output with the pooled embeddings (upper triangle, row-major) ->
top MLP (ReLU but after the last layer) -> one logit; binary cross-entropy
with logits. Dense replicas train with Adagrad; the embedding rows with
row-sparse Adagrad, where every occurrence of a row in a bag receives the
bag's pooled gradient, the accumulator takes all occurrences first and each
occurrence then steps by the final accumulator. EASGD's exchange moves the
centre toward a replica and the replica toward the new centre.

Dense weights are a flat dict keyed ``bottom.<i>.w`` / ``top.<i>.b``, the
embedding collection one logical ``(rows, dim)`` table. ``dtype`` is the
working precision: float32 for the reference, bfloat16 for its control."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.shapes import Shapes

Params = Dict[str, jnp.ndarray]


def leaf_names(shapes: Shapes):
    out = []
    for part, n in (("bottom", len(shapes.bottom_mlp)), ("top", len(shapes.top_mlp))):
        for i in range(n):
            out += [f"{part}.{i}.w", f"{part}.{i}.b"]
    return out


def init_dense(shapes: Shapes, key, dtype=jnp.float32) -> Params:
    """Weights ~ N(0, 1/fan_in), biases 0; layer k of the stack bottom-then-top
    draws from the k-th of one split of ``key``."""
    nb, nt = len(shapes.bottom_mlp), len(shapes.top_mlp)
    keys = jax.random.split(key, nb + nt)
    out: Params = {}
    k = 0
    for part, dims in (
        ("bottom", (shapes.n_dense,) + shapes.bottom_mlp),
        ("top", (shapes.top_in,) + shapes.top_mlp),
    ):
        for i in range(len(dims) - 1):
            w = jax.random.normal(keys[k], (dims[i], dims[i + 1]), jnp.float32) * dims[i] ** -0.5
            out[f"{part}.{i}.w"] = w.astype(dtype)
            out[f"{part}.{i}.b"] = jnp.zeros((dims[i + 1],), dtype)
            k += 1
    return out


def init_table(shapes: Shapes, key, dtype=jnp.float32) -> jnp.ndarray:
    """Logical (rows, dim) collection, rows ~ N(0, 1/dim)."""
    t = jax.random.normal(key, (shapes.n_rows, shapes.dim), jnp.float32) * shapes.dim**-0.5
    return t.astype(dtype)


def _mlp(p: Params, part: str, n: int, x, final_linear: bool):
    for i in range(n):
        x = x @ p[f"{part}.{i}.w"] + p[f"{part}.{i}.b"]
        if not (final_linear and i == n - 1):
            x = jnp.maximum(x, 0)
    return x


def forward(shapes: Shapes, p: Params, dense_x, pooled) -> jnp.ndarray:
    """Logits (B,) from dense features (B, n_dense) and pooled (B, F, dim)."""
    bot = _mlp(p, "bottom", len(shapes.bottom_mlp), dense_x.astype(pooled.dtype), False)
    z = jnp.concatenate([bot[:, None, :], pooled], axis=1)
    dots = jnp.einsum("bfd,bgd->bfg", z, z)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    feat = jnp.concatenate([bot, dots[:, iu, ju]], axis=-1)
    return _mlp(p, "top", len(shapes.top_mlp), feat, True)[:, 0]


def bce(logits, labels) -> jnp.ndarray:
    labels = labels.astype(logits.dtype)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def global_rows(shapes: Shapes, ids) -> jnp.ndarray:
    """(B, F, m) per-table ids -> global row ids of the collection."""
    return ids + jnp.asarray(shapes.offsets, jnp.int32)[None, :, None]


def pooled_lookup(table, rows) -> jnp.ndarray:
    """Sum-pool: (B, F, m) global rows -> (B, F, dim)."""
    return jnp.sum(jnp.take(table, rows, axis=0), axis=2)


def loss_and_grads(shapes: Shapes, p: Params, batch, pooled):
    """(loss, grad of the dense weights, grad of the pooled embeddings)."""

    def f(p_, pooled_):
        return bce(forward(shapes, p_, batch["dense"], pooled_), batch["labels"])

    loss, (g_p, g_pooled) = jax.value_and_grad(f, argnums=(0, 1))(p, pooled)
    return loss, g_p, g_pooled


def adagrad(p: Params, acc: Params, g: Params, lr: float, eps: float):
    acc = {k: acc[k] + g[k] * g[k] for k in p}
    p = {k: p[k] - lr * g[k] * jax.lax.rsqrt(acc[k] + eps) for k in p}
    return p, acc


def sparse_adagrad(table, acc, rows, g_pooled, lr: float, eps: float):
    """rows (B, F, m) global rows; g_pooled (B, F, dim)."""
    m = rows.shape[-1]
    flat = rows.reshape(-1)
    g = jnp.repeat(g_pooled.reshape(-1, g_pooled.shape[-1]), m, axis=0).astype(acc.dtype)
    acc = acc.at[flat].add(g * g)
    step = lr * g * jax.lax.rsqrt(acc[flat] + eps)
    return table.at[flat].add(-step.astype(table.dtype)), acc


def easgd_pair(center: Params, w: Params, alpha: float):
    center = {k: (1 - alpha) * center[k] + alpha * w[k] for k in w}
    w = {k: (1 - alpha) * w[k] + alpha * center[k] for k in w}
    return center, w
