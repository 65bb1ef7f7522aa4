"""A test-only interaction: the bottom output followed by every table's
pooled vector, each scaled by a weight of its table (``concat.gate``, drawn
around 1). It shows a new architecture entering as a file, weights and all;
no program runs it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

PROGRAM_KEYS = ()


def out_dim(shapes) -> int:
    return shapes.bottom_mlp[-1] + shapes.n_tables * shapes.dim


def n_keys(shapes) -> int:
    return 1


def init(shapes, keys, dtype=jnp.float32) -> dict:
    gate = 1.0 + 0.1 * jax.random.normal(keys[0], (shapes.n_tables,), jnp.float32)
    return {"concat.gate": gate.astype(dtype)}


def apply(shapes, params, bottom_out, pooled):
    scaled = pooled * params["concat.gate"][None, :, None]
    return jnp.concatenate([bottom_out, scaled.reshape(scaled.shape[0], -1)], axis=-1)


def forward_flops(shapes) -> int:
    return shapes.n_tables * shapes.dim
