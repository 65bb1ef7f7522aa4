"""Replay of the check run and the held-out evaluation, in plain arithmetic.

The check run is a fixed interleaving of the threaded trainer's work: in
each sweep trainers 0..R-1 take one step each in turn (lookup, dense step,
sparse update); between two sweeps one EASGD round exchanges every replica
with the centre in slot order. The schedule ends on a trainer's step: the
runner stops its shadow thread once the trainers are done. ``replay`` follows the same schedule from the seed
and returns what ``bench/check.py`` compares: each step's loss, the first
step's gradient norms as the optimizers' accumulators hold them, and the
norm of each leaf's change over the whole run."""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ctr
from bench.reference import dlrm as ref
from bench.reference.shapes import Shapes, blocks


@dataclass(frozen=True)
class Hyper:
    n_trainers: int
    batch_size: int
    lr: float
    emb_lr: float
    alpha: float
    eps: float = 1e-8


@dataclass
class Readings:
    """What the comparison reads from one run of the check schedule.

    ``losses[(i, it)]``: trainer ``i``'s loss at its step ``it``.
    ``grad[name]``: norm of the first step's gradient of a dense leaf or of
    ``table.<f>``, worked out from the Adagrad accumulators after that step.
    ``change[name]``: norm of ``r<i>.<leaf>`` (replica ``i``) or
    ``table.<f>`` minus its initial value after the whole schedule."""

    losses: Dict[Tuple[int, int], float] = field(default_factory=dict)
    grad: Dict[str, float] = field(default_factory=dict)
    change: Dict[str, float] = field(default_factory=dict)


def schedule(n_trainers: int, n_sweeps: int) -> List[tuple]:
    out: List[tuple] = []
    for it in range(n_sweeps):
        if it:
            out.append(("sync", it - 1))
        out += [("train", i, it) for i in range(n_trainers)]
    return out


def touched_ids(shapes: Shapes, batches: Sequence[dict]) -> List[np.ndarray]:
    """Per table, the sorted distinct ids the batches touch."""
    ids = np.concatenate([np.asarray(b["sparse"]) for b in batches], axis=0)
    return [np.unique(b) for b in blocks(ids, shapes.bags)]


def table_segments(shapes: Shapes, touched: List[np.ndarray], pad_to: int = 0):
    """Global rows of the touched ids, in order, and the table each belongs
    to; padded to ``pad_to`` rows, so that every seed runs the same
    programs, with the last row of the collection in no table."""
    rows = np.concatenate([shapes.offsets[f] + t for f, t in enumerate(touched)])
    seg = np.concatenate([np.full(len(t), f) for f, t in enumerate(touched)])
    pad = max(pad_to - len(rows), 0)
    rows = np.concatenate([rows, np.full(pad, shapes.n_rows - 1)])
    seg = np.concatenate([seg, np.full(pad, shapes.n_tables)])
    return jnp.asarray(rows, jnp.int32), jnp.asarray(seg, jnp.int32)


def segment_norms(x, seg, n_tables: int) -> jnp.ndarray:
    """Norm of the rows of ``x`` per table: sqrt of the summed squares."""
    sq = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1)
    return jnp.sqrt(jax.ops.segment_sum(sq, seg, num_segments=n_tables))


def segment_sqrt_sum(x, seg, n_tables: int) -> jnp.ndarray:
    """sqrt of the summed entries per table (an accumulator of squares)."""
    s = jnp.sum(x.astype(jnp.float32), axis=-1)
    return jnp.sqrt(jax.ops.segment_sum(s, seg, num_segments=n_tables))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(6, 7))
def _step(shapes, lr, emb_lr, eps, p, acc_p, table, acc_t, touched_rows, batch):
    """One trainer step on the touched rows of the collection: ``table`` and
    ``acc_t`` hold global row ``touched_rows[k]`` at row ``k``."""
    rows = jnp.searchsorted(touched_rows, ref.global_rows(shapes, batch["sparse"]))
    pooled = ref.pooled_lookup(table, rows, shapes.bags)
    loss, g_p, g_pooled = ref.loss_and_grads(shapes, p, batch, pooled)
    p, acc_p = ref.adagrad(p, acc_p, g_p, lr, eps)
    table, acc_t = ref.sparse_adagrad(table, acc_t, rows, g_pooled, emb_lr, eps, shapes.bags)
    return loss, p, acc_p, table, acc_t


@functools.partial(jax.jit, static_argnums=(0,))
def _round(alpha, center, ws):
    out = []
    for w in ws:
        center, w = ref.easgd_pair(center, w, alpha)
        out.append(w)
    return center, out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _take_segments(x, rows, seg, n_tables, sqrt_sum=False):
    v = jnp.take(x, rows, axis=0)
    return segment_sqrt_sum(v, seg, n_tables) if sqrt_sum else segment_norms(v, seg, n_tables)


def replay(
    shapes: Shapes,
    hyper: Hyper,
    seed: int,
    batches: Dict[Tuple[int, int], dict],
    sched: Sequence[tuple],
    touched: List[np.ndarray],
    dtype=jnp.float32,
) -> Readings:
    """Run ``sched`` from the seed's initial weights at precision ``dtype``."""
    with jax.default_matmul_precision("highest"):
        kw, ke = jax.random.split(jax.random.PRNGKey(seed))
        w0 = ref.init_dense(shapes, kw, dtype)
        steps = [batches[(ev[1], ev[2])]["sparse"] for ev in sched if ev[0] == "train"]
        rows, seg = table_segments(shapes, touched, sum(int(np.prod(b.shape)) for b in steps))
        # only the rows the schedule touches are kept: the whole collection
        # is drawn once, as the program draws it, and let go
        t0_rows = jnp.take(ref.init_table(shapes, ke, dtype), rows, axis=0)
        table = t0_rows.copy()  # each step donates the table it is given
        acc_t = jnp.zeros_like(table)
        compact = jnp.arange(rows.shape[0], dtype=jnp.int32)
        ws = [dict(w0) for _ in range(hyper.n_trainers)]
        accs = [{k: jnp.zeros_like(v) for k, v in w0.items()} for _ in range(hyper.n_trainers)]
        center = dict(w0)
        out = Readings()
        cast = lambda b: {k: (v.astype(dtype) if v.dtype == jnp.float32 else v) for k, v in b.items()}
        for ev in sched:
            if ev[0] == "sync":
                center, ws = _round(hyper.alpha, center, ws)
                continue
            _, i, it = ev
            loss, ws[i], accs[i], table, acc_t = _step(
                shapes, hyper.lr, hyper.emb_lr, hyper.eps, ws[i], accs[i], table, acc_t, rows,
                cast(batches[(i, it)]))
            out.losses[(i, it)] = float(loss)
            if not out.grad:
                out.grad = {k: float(jnp.sqrt(jnp.sum(v.astype(jnp.float32)))) for k, v in accs[i].items()}
                g_t = np.asarray(_take_segments(acc_t, compact, seg, shapes.n_tables, True))
                out.grad.update({f"table.{f}": float(v) for f, v in enumerate(g_t)})
        for i, w in enumerate(ws):
            for k, v in w.items():
                out.change[f"r{i}.{k}"] = float(
                    jnp.linalg.norm((v.astype(jnp.float32) - w0[k].astype(jnp.float32)).ravel()))
        moved = table.astype(jnp.float32) - t0_rows.astype(jnp.float32)
        d_t = np.asarray(segment_norms(moved, seg, shapes.n_tables))
        out.change.update({f"table.{f}": float(v) for f, v in enumerate(d_t)})
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _bce_sum(shapes, p, dense, pooled, labels):
    logits = ref.forward(shapes, p, dense, pooled)
    return ref.bce(logits, labels) * labels.shape[0], jnp.sum(labels)


def eval_ne(
    shapes: Shapes,
    p: Dict[str, jnp.ndarray],
    pooled_fn: Callable[[jnp.ndarray], jnp.ndarray],
    teacher,
    seed: int,
    n_batches: int,
    batch_size: int,
) -> float:
    """Normalized entropy of the model (``p`` and the tables behind
    ``pooled_fn``) on the held-out stream ``seed``: mean BCE over the entropy
    of the set's own click rate [He et al. 2014]."""
    bce = clicks = 0.0
    with jax.default_matmul_precision("highest"):
        for j in range(n_batches):
            b = ctr.gen_batch(shapes, teacher, seed, j, batch_size)
            s, c = _bce_sum(shapes, p, b["dense"], pooled_fn(b["sparse"]), b["labels"])
            bce += float(s)
            clicks += float(c)
    n = n_batches * batch_size
    q = clicks / n
    return (bce / n) / float(-(q * np.log(q) + (1 - q) * np.log(1 - q)))
