"""DLRM training arithmetic in plain ``jax.numpy`` (arXiv:1906.00091).

Bottom MLP (ReLU after every layer) -> the configuration's interaction of
the bottom output with the pooled embeddings (``interactions/<name>.py``;
DLRM's is ``dot``) -> top MLP (ReLU but after the last layer) -> one logit;
binary cross-entropy with logits. A bag's embedding is the sum of its rows.
Dense replicas train with Adagrad; the embedding rows with row-sparse
Adagrad, where every occurrence of a row in a bag receives the bag's pooled
gradient, the accumulator takes all occurrences first and each occurrence
then steps by the final accumulator. EASGD's exchange moves the centre
toward a replica and the replica toward the new centre.

Dense weights are a flat dict keyed ``bottom.<i>.w`` / ``top.<i>.b`` and by
the interaction's own names, the embedding collection one logical ``(rows,
dim)`` table. ``dtype`` is the working precision: float32 for the
reference, bfloat16 for its control."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.shapes import Shapes, bag_sizes, column_tables, spans

Params = Dict[str, jnp.ndarray]


def leaf_names(shapes: Shapes):
    out = []
    for part, n in (("bottom", len(shapes.bottom_mlp)), ("top", len(shapes.top_mlp))):
        for i in range(n):
            out += [f"{part}.{i}.w", f"{part}.{i}.b"]
    mod = shapes.interact
    keys = jax.ShapeDtypeStruct((mod.n_keys(shapes), 2), jnp.uint32)
    return out + list(jax.eval_shape(lambda k: mod.init(shapes, k), keys))


def init_dense(shapes: Shapes, key, dtype=jnp.float32) -> Params:
    """Weights ~ N(0, 1/fan_in), biases 0; layer k of the stack bottom-then-top
    draws from the k-th of one split of ``key``, and the interaction from the
    keys after them."""
    mod = shapes.interact
    nb, nt = len(shapes.bottom_mlp), len(shapes.top_mlp)
    keys = jax.random.split(key, nb + nt + mod.n_keys(shapes))
    out: Params = {}
    k = 0
    for part, dims in (
        ("bottom", (shapes.n_dense,) + shapes.bottom_mlp),
        ("top", (mod.out_dim(shapes),) + shapes.top_mlp),
    ):
        for i in range(len(dims) - 1):
            w = jax.random.normal(keys[k], (dims[i], dims[i + 1]), jnp.float32) * dims[i] ** -0.5
            out[f"{part}.{i}.w"] = w.astype(dtype)
            out[f"{part}.{i}.b"] = jnp.zeros((dims[i + 1],), dtype)
            k += 1
    out.update(mod.init(shapes, keys[nb + nt:], dtype))
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
    feat = shapes.interact.apply(shapes, p, bot, pooled)
    return _mlp(p, "top", len(shapes.top_mlp), feat, True)[:, 0]


def bce(logits, labels) -> jnp.ndarray:
    labels = labels.astype(logits.dtype)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def global_rows(shapes: Shapes, ids) -> jnp.ndarray:
    """Per-table ids of a batch -> global row ids of the collection."""
    return ids + jnp.asarray(shapes.by_column(shapes.offsets), jnp.int32)


def pool(x, bags) -> jnp.ndarray:
    """Sum each table's bag: (B, ..., dim) rows in a batch's id layout -> (B, F, dim)."""
    flat = x.reshape(x.shape[0], -1, x.shape[-1])
    return jnp.stack([jnp.sum(flat[:, a:a + m], axis=1) for a, m in spans(bags)], axis=1)


def pooled_lookup(table, rows, bags=None) -> jnp.ndarray:
    """Sum-pool: global rows, (B, F, m) or laid out by ``bags`` -> (B, F, dim)."""
    return pool(jnp.take(table, rows, axis=0), bag_sizes(rows, bags))


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


def sparse_adagrad(table, acc, rows, g_pooled, lr: float, eps: float, bags=None):
    """rows: global rows, (B, F, m) or laid out by ``bags``; g_pooled (B, F, dim)."""
    flat = rows.reshape(-1)
    g = g_pooled[:, column_tables(bag_sizes(rows, bags))].reshape(-1, g_pooled.shape[-1]).astype(acc.dtype)
    acc = acc.at[flat].add(g * g)
    step = lr * g * jax.lax.rsqrt(acc[flat] + eps)
    return table.at[flat].add(-step.astype(table.dtype)), acc


def easgd_pair(center: Params, w: Params, alpha: float):
    center = {k: (1 - alpha) * center[k] + alpha * w[k] for k in w}
    w = {k: (1 - alpha) * w[k] + alpha * center[k] for k in w}
    return center, w
