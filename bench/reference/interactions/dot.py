"""DLRM's pairwise dot interaction (arXiv:1906.00091), the interface of an
interaction file.

The harness loads ``bench/reference/interactions/<name>.py`` by the
configuration's ``interaction`` key (``bench/cell.py``'s ``load_module``)
and reads, from whatever file it names:

* ``out_dim(shapes)``: the width of the features the top MLP takes;
* ``n_keys(shapes)``: how many keys ``init`` draws from. They follow the
  MLPs' keys in the one ``jax.random.split`` of the reference's
  ``init_dense``, so an interaction without weights leaves the MLPs' draw
  as it is;
* ``init(shapes, keys, dtype)``: the interaction's dense weights, a dict of
  named leaves that train with the MLPs' (dense Adagrad, EASGD);
* ``apply(shapes, params, bottom_out, pooled)``: the top MLP's features,
  from the bottom MLP's output (B, d), the pooled embeddings (B, F, d) and
  the dict of every dense leaf;
* ``forward_flops(shapes)``: the interaction's operations per example, by
  ``bench/flops.py``'s convention (``2 * m * n * k`` for a product);
* ``PROGRAM_KEYS``: config keys that ``bench/program.py`` passes as they
  are to the program's ``DLRMConfig`` (a list as a tuple); a key the
  program lacks fails in set-up.

Dot: the bottom output and the F pooled vectors make n = F + 1 vectors;
their n(n - 1)/2 pairwise dot products (upper triangle, row-major) follow
the bottom output. No weights, and nothing beyond the program's own
``interaction`` key."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

PROGRAM_KEYS = ()


def out_dim(shapes) -> int:
    n = shapes.n_tables + 1
    return shapes.bottom_mlp[-1] + n * (n - 1) // 2


def n_keys(shapes) -> int:
    return 0


def init(shapes, keys, dtype=jnp.float32) -> dict:
    return {}


def apply(shapes, params, bottom_out, pooled):
    z = jnp.concatenate([bottom_out[:, None, :], pooled], axis=1)
    dots = jnp.einsum("bfd,bgd->bfg", z, z)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    return jnp.concatenate([bottom_out, dots[:, iu, ju]], axis=-1)


def forward_flops(shapes) -> int:
    n = shapes.n_tables + 1
    return 2 * n * n * shapes.dim
