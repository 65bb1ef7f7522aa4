"""Everything the benchmark knows of the program under test.

The runner is built exactly as ``launch/train.py``'s ``run_dlrm`` builds it
for ``--threaded``. Its state is read through the program's own layout: the
plan that assigns tables to embedding shards, and lane-dense rows (``128 //
dim`` logical rows to a 128-lane row); a batch's ids in the layout of the
configuration's bag sizes (``bench/reference/shapes.py``). ``CheckRun``
drives the runner's own ``run`` through a fixed interleaving of its
threads, so that the reference can follow it, and reads the numbers that
``bench/check.py`` compares."""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dlrm import pool
from bench.reference.shapes import column_tables, interaction
from bench.reference.train import Readings

LANE = 128


def _as_given(v):
    return tuple(v) if isinstance(v, list) else v


def program_config(config: dict):
    """The program's ``DLRMConfig`` for one configuration file: its sizes, and
    the keys its interaction file lists in ``PROGRAM_KEYS``, as they are."""
    from repro.configs import dlrm_ctr

    keys = interaction(config["interaction"]).PROGRAM_KEYS
    fields = {f.name for f in dataclasses.fields(dlrm_ctr.CONFIG)}
    missing = [k for k in keys if k not in fields]
    if missing:
        raise KeyError(f"interaction {config['interaction']!r} passes {missing} to the "
                       f"program, whose DLRMConfig has no such field")
    return dataclasses.replace(
        dlrm_ctr.CONFIG,
        name=config["name"],
        n_dense_features=config["n_dense_features"],
        n_sparse_features=len(config["table_sizes"]),
        embedding_dim=config["embedding_dim"],
        table_sizes=tuple(config["table_sizes"]),
        multi_hot=_as_given(config["multi_hot"]),
        bottom_mlp=tuple(config["bottom_mlp"]),
        top_mlp=tuple(config["top_mlp"]),
        interaction=config["interaction"],
        dtype=config["dtype"],
        **{k: _as_given(config[k]) for k in keys},
    )


def build_runner(config: dict, traffic: dict, seed: int):
    """The threaded runner for one configuration file and one traffic file."""
    from repro import optim
    from repro.core.membership import FaultSpec
    from repro.core.runners import ThreadedShadowRunner
    from repro.core.sync import SyncConfig

    cfg = program_config(config)
    sync_cfg = SyncConfig(
        algo=traffic["algo"], mode=traffic["mode"], gap=traffic["sync_gap"],
        alpha=traffic["alpha"], delay=traffic["sync_delay"])
    return ThreadedShadowRunner(
        cfg,
        sync_cfg,
        n_trainers=traffic["trainers"],
        batch_size=traffic["batch_size"],
        optimizer=optim.make(traffic["optimizer"], traffic["lr"]),
        seed=seed,
        sync_sleep_s=0.0,
        fault_spec=FaultSpec(),
    )


def use_teacher(runner, teacher: dict) -> None:
    """Give the runner the benchmark's click teacher (the task every seed trains on)."""
    from repro.data.ctr import CTRTeacher

    runner.teacher = CTRTeacher(teacher["true_rows"], teacher["w_dense"], teacher["w_out"],
                                teacher["bias"])


def leaf_dict(tree) -> Dict[str, jnp.ndarray]:
    """A dense-weight pytree as ``{"bottom.0.w": leaf, ...}``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)] = leaf
    return out


@functools.partial(jax.jit, static_argnames=("dim",))
def _gather_lane_rows(table, lane, seg, dim):
    rows = jnp.take(table, lane, axis=0)
    rows = rows.reshape(rows.shape[0], -1, dim)
    return jnp.take_along_axis(rows, seg[:, None, None], axis=1)[:, 0, :]


def shard_row_index(plan, ids: List[np.ndarray], pad: Sequence[int]):
    """Where the logical rows ``ids[f]`` of each table lie in the program's
    sharded, lane-dense tables: per shard, the lane row and the lane segment
    of each row, padded to ``pad[s]`` rows so that every seed reads with the
    same programs, and the ``(table, start, count)`` spans of the tables."""
    p = LANE // plan.spec.dim
    parts = []
    for s, feats in enumerate(plan.bins):
        local, spans, at = [], [], 0
        for f in feats:
            local.append(plan.feature_local_offset[f] + ids[f])
            spans.append((f, at, len(ids[f])))
            at += len(ids[f])
        local = np.concatenate(local) if local else np.zeros(0, np.int64)
        local = np.pad(local, (0, pad[s] - len(local)))
        parts.append((jnp.asarray(local // p, jnp.int32), jnp.asarray(local % p, jnp.int32), spans))
    return parts


def pooled_reader(plan, tables: Sequence[jnp.ndarray], bags: Sequence[int]):
    """``ids -> pooled (B, F, dim)`` over the program's tables, for ids laid
    out by ``bags``, one bag size per table."""
    dim, p = plan.spec.dim, LANE // plan.spec.dim
    col = column_tables(bags)
    off = jnp.asarray(np.asarray(plan.feature_local_offset)[col], jnp.int32)
    shard_of = np.asarray(plan.feature_shard)[col]
    bags = tuple(bags)

    @jax.jit
    def pooled(tables, ids):
        local = ids.reshape(ids.shape[0], -1) + off[None, :]
        out = jnp.zeros(local.shape + (dim,), jnp.float32)
        for s, t in enumerate(tables):
            mask = jnp.asarray(shard_of == s)[None, :]
            loc = jnp.where(mask, local, 0)
            rows = jnp.take(t, loc // p, axis=0).reshape(*loc.shape, p, dim)
            rows = jnp.take_along_axis(rows, (loc % p)[..., None, None], axis=-2)[..., 0, :]
            out = out + jnp.where(mask[..., None], rows, 0.0)
        return pool(out, bags)

    tables = tuple(tables)
    return lambda ids: pooled(tables, ids)


class CheckAbort(RuntimeError):
    pass


class CheckRun:
    """One ``runner.run(n_sweeps)`` whose threads take their turns in the
    order of ``bench.reference.train.schedule``.

    A trainer takes its turn when it fetches its batch
    (``repro.data.ctr.gen_batch``, at the top of each iteration, before it
    reads its replica, its optimizer state and the tables); the shadow
    thread when it asks for the live replicas (``membership.active_ids``,
    before the round reads the planes and the centre). Outside its turn the
    shadow thread is told that no replica is live, and skips the round as
    the runner does when none is. A turn is given only once the runner has
    published the previous one (the trainer's iteration count, or the sync
    count has moved), so every program runs on the arguments the runner
    passes it, and those are the state current at its turn. Trainers are
    told apart by their batch's seed and iteration; each batch must equal
    the reference's bit for bit. At the turns the reference reads from, only
    row gathers compiled in set-up are dispatched; every reduction to norms
    happens on the host after the run, so no turn waits on a compile and
    the shadow thread's heartbeat stays fresh."""

    def __init__(self, runner, batches: Dict[Tuple[int, int], dict], sched, touched,
                 bags: Sequence[int], timeout_s: float = 60.0):
        from repro.embeddings import shards
        from repro.embeddings.table import init_tables

        self.r = runner
        self.sched = list(sched)
        self.batches = batches
        self.n_sweeps = max(ev[2] for ev in self.sched if ev[0] == "train") + 1
        plan = runner.plan
        pad = [self.n_sweeps * runner.R * runner.B * sum(bags[f] for f in b) for b in plan.bins]
        self.parts = shard_row_index(plan, touched, pad)
        states = jax.eval_shape(lambda k: shards.shard_states(plan, init_tables(plan.spec, k)),
                                jax.random.PRNGKey(0))
        self.gather = [
            jax.jit(functools.partial(_gather_lane_rows, dim=plan.spec.dim)).lower(
                st["table"], lane, seg).compile()
            for st, (lane, seg, _) in zip(states, self.parts)
        ]
        self.timeout_s = timeout_s
        self.main = threading.current_thread()
        self.cond = threading.Condition()
        self.pos = 0  # the next turn to be taken
        self.published = lambda: True  # has the runner published the last turn?
        self.turn = threading.local()  # the (trainer, iteration) a thread steps
        self.error = None
        self._losses = {}
        self._w0 = None
        self._t0: List[jnp.ndarray] = []
        self._opt_first = None
        self._acc_first: List[jnp.ndarray] = []

    # -- turn keeping ------------------------------------------------------
    def _take(self, want, block: bool) -> bool:
        """Take the current turn if ``want(event)`` holds for it and the
        previous one is published; with ``block``, wait until then. False
        once the schedule is over, or, without ``block``, when it is not
        this caller's turn."""
        deadline = time.perf_counter() + self.timeout_s
        with self.cond:
            while True:
                if self.error is not None:
                    raise CheckAbort(self.error)
                if self.pos >= len(self.sched):
                    return False
                ev = self.sched[self.pos]
                if want(ev) and self.published():
                    self._at_turn(self.pos)
                    self.pos += 1
                    self.published = self._publication(ev)
                    self.cond.notify_all()
                    return True
                if not block:
                    return False
                if time.perf_counter() > deadline:
                    self._fail(f"turn {ev} never came")
                self.cond.wait(timeout=0.001)

    def _publication(self, ev):
        r = self.r
        if ev[0] == "train":
            i, it = ev[1], ev[2]
            return lambda: r.iter_count[i] > it
        synced = r.sync_count
        return lambda: r.sync_count > synced

    def _at_turn(self, pos: int) -> None:
        """Read what the reference compares at the start of turn ``pos``:
        the initial replicas and table rows before the first, the optimizer
        state and accumulator rows of the first step before the second."""
        r = self.r
        if pos == 0:
            self._w0 = list(r.w)
            self._t0 = [g(st["table"], *p[:2])
                        for g, st, p in zip(self.gather, r.emb.states, self.parts)]
        elif pos == 1:
            self._opt_first = r.opt_states[self.sched[0][1]]
            self._acc_first = [g(st["acc"], *p[:2])
                               for g, st, p in zip(self.gather, r.emb.states, self.parts)]

    def _fail(self, msg: str):
        with self.cond:
            if self.error is None:
                self.error = msg
            self.cond.notify_all()
        raise CheckAbort(msg)

    # -- the runner's calls the turns hang on ------------------------------
    def _gen_batch(self, orig):
        def call(cfg, teacher, seed, it, batch_size, *args, **kw):
            i = seed - self.r.seed
            if not self._take(lambda ev: ev == ("train", i, it), block=True):
                self._fail(f"trainer {i} stepped past the schedule")
            self.turn.key = (i, it)
            batch = orig(cfg, teacher, seed, it, batch_size, *args, **kw)
            ref = self.batches.get((i, it))
            if ref is None or not all(np.array_equal(np.asarray(ref[k]), np.asarray(batch[k]))
                                      for k in ("dense", "sparse", "labels")):
                self._fail(f"trainer {i}'s batch {it} differs from the reference stream")
            return batch
        return call

    def _active_ids(self, orig):
        def call(*args, **kw):
            ids = orig(*args, **kw)
            if threading.current_thread() is self.main:
                return ids
            return ids if self._take(lambda ev: ev[0] == "sync", block=False) else ids[:0]
        return call

    def _train_one(self, orig):
        def call(*args):
            out = orig(*args)
            self._losses[self.turn.key] = out[2]
            return out
        return call

    def _per_table(self, s: int, rows) -> Dict[int, np.ndarray]:
        rows = np.asarray(rows, np.float64)
        return {f: rows[at:at + n] for f, at, n in self.parts[s][2]}

    # -- the run -----------------------------------------------------------
    def run(self) -> Readings:
        from repro.data import ctr

        r = self.r
        saved = (ctr.gen_batch, r._train_one)
        ctr.gen_batch = self._gen_batch(saved[0])
        r._train_one = self._train_one(saved[1])
        r.membership.active_ids = self._active_ids(r.membership.active_ids)
        try:
            out = r.run(self.n_sweeps)
        finally:
            ctr.gen_batch, r._train_one = saved
            del r.membership.active_ids
        if self.error is not None:
            raise CheckAbort(self.error)
        if self.pos != len(self.sched):
            raise CheckAbort(f"the run ended at turn {self.pos} of {len(self.sched)}")
        rd = Readings()
        rd.losses = {k: float(v) for k, v in self._losses.items()}
        for k, v in leaf_dict(self._opt_first).items():
            rd.grad[k] = float(np.sqrt(np.sum(np.asarray(v, np.float64))))
        for s, rows in enumerate(self._acc_first):
            for f, v in self._per_table(s, rows).items():
                rd.grad[f"table.{f}"] = float(np.sqrt(np.sum(v)))
        unpack = r.flat.unpack if r.engine == "flat" else (lambda w: w)
        for i, (w, w0) in enumerate(zip(out["w"], self._w0)):
            w0 = leaf_dict(unpack(w0))
            for k, v in leaf_dict(w).items():
                rd.change[f"r{i}.{k}"] = float(np.linalg.norm(
                    np.asarray(v, np.float64) - np.asarray(w0[k], np.float64)))
        for s, st in enumerate(r.emb.states):
            t0 = self._per_table(s, self._t0[s])
            for f, v in self._per_table(s, self.gather[s](st["table"], *self.parts[s][:2])).items():
                rd.change[f"table.{f}"] = float(np.linalg.norm(v - t0[f]))
        return rd
