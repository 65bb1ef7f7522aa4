"""Training runners realizing the paper's system on laptop-scale hardware.

Two runtimes:

* ``HogwildSim`` — deterministic, jitted simulation of n trainers x m Hogwild
  worker threads over the shared embedding tables + per-trainer dense replicas.
  Hogwild staleness semantics: all m thread-grads of an iteration are computed
  from the SAME replica snapshot, then applied sequentially through the optimizer
  (lock-free interleave, quantized at iteration granularity). Background sync is
  scheduled by shadow clocks with launch-snapshot/delayed-landing semantics.
  This runtime produces the paper-quality experiments (Tables 2-3, Figs 6-7).

* ``ThreadedShadowRunner`` — the faithful host-level realization: real Python
  threads (jitted compute releases the GIL), a genuinely racing shared embedding
  state, and a shadow thread that syncs continuously in the background at
  whatever cadence it achieves — the paper's Algorithm 1 verbatim.

Both runners default to the FLAT sync engine (DESIGN.md §3): dense replicas
live in a persistent ``(R, n_rows, 128)`` fp32 buffer (core/flatspace.py) and
every background sync is one fused Pallas launch. ``SyncConfig(engine=
"pytree")`` selects the pure jax.tree.map oracle path.

Both runners also default to the fused SPARSE substrate (DESIGN.md §7):
embedding forward is the fused lookup+pool kernel and the backward is the
fused scatter-Adagrad kernel (``kernels/embedding_bag`` /
``kernels/sparse_adagrad``; compiled on TPU, interpreter elsewhere).
``HogwildSim`` keeps one packed table (the deterministic-sim semantics);
``ThreadedShadowRunner`` realizes the paper's embedding PSs: the LPT
bin-pack plan (``embeddings/shards.py``) splits the collection into
``n_emb_shards`` independent per-PS Hogwild states, lookups route by the
plan, and trainer writes to different PSs no longer serialize through one
jitted scatter.

Neither runner knows any algorithm by name: the whole sync lifecycle —
state init, launch snapshot, landing, the threaded shadow round — is owned
by the ``SyncAlgorithm`` fetched from ``core.algorithms`` (DESIGN.md §6),
so a newly registered algorithm runs here without touching this file.

Elastic membership (DESIGN.md §8): both runners consume a mutable
``core.membership.Membership`` instead of a frozen ``R``. Buffers are
capacity-padded at ``R_max`` so join/leave/fail never reallocate or retrace;
``HogwildSim`` takes a deterministic ``MembershipSchedule`` for reproducible
elasticity experiments, ``ThreadedShadowRunner`` a ``FaultSpec`` harness
(straggler slowdown, crash-at-iteration, join-at-iteration) where the shadow
thread reads membership each round and simply skips dead slots — training
never blocks on a fault. ``mode="fixed_rate"`` in the threaded runner is the
foreground contrast: every trainer blocks at the sync point, so one
straggler drags the whole cohort to its pace.

Closed-loop straggler scheduling (DESIGN.md §9): pass a
``core.scheduler.StragglerPolicy`` and the threaded runner evaluates it
every background round over per-slot busy-clock EPS meters — a slot whose
pace falls below the policy floor is demoted to ``leave`` (with provenance
in the membership event log) and re-admitted through the ordinary join
bootstrap once its probation passes. ``HogwildSim`` consumes the same
policy deterministically via ``core.scheduler.StragglerSchedule``.

Failure-domain supervision (DESIGN.md §10): the threaded runner's long-lived
threads — shadow, monitor, trainers — register heartbeats with a
``core.supervision.Supervisor``. A dead or stalled shadow thread is
restarted against the LIVE membership state (isolation makes this safe:
training never blocked on it); when the restart budget is exhausted the run
degrades gracefully — training continues locally, a ``degraded`` event with
provenance lands in the membership log, and one final foreground sync at
shutdown still converges the replicas. The embedding PSs are their own
failure domain (``embeddings/shards.py``): the shadow thread takes O(1)
background snapshots, ``FaultSpec.ps_fail_at`` kills a shard, lookups fall
back to the snapshot (bounded staleness, never a blocked trainer), updates
retry-then-drop, and the supervisor rehydrates the shard after the
provisioning delay. Trainer exceptions are captured per-thread and re-raised
with slot provenance after ``join()`` — a failed run no longer returns
partial results as if it succeeded.
"""
from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import checkpoint as ckpt
from repro.core import algorithms
from repro.core import sync as S
from repro.core.elp import EPSMeter, SlotEPS, median_eps
from repro.core.flatspace import FlatSpace
from repro.core.membership import FaultSpec, Membership, MembershipSchedule
from repro.core.modeswitch import ModeController, ModeDecision, ModeSchedule
from repro.core.pipeline import PipelineConfig, PipelineStats, StepPipeline
from repro.core.scheduler import StragglerPolicy
from repro.core.supervision import Supervisor, SupervisorConfig
from repro.data import ctr
from repro.embeddings import shards as emb_shards
from repro.embeddings import table as emb
from repro.embeddings.cache import CacheConfig, CachedStore
from repro.models import dlrm
from repro.optim import Optimizer

Pytree = Any


def _dense_flatspace(cfg) -> FlatSpace:
    """Layout of the DLRM dense replica space, from shapes only (no init)."""
    shapes = jax.eval_shape(lambda: dlrm.init_dense(cfg, jax.random.PRNGKey(0)))
    return FlatSpace.from_tree(shapes)


# ---------------------------------------------------------------------------
# Deterministic simulator
# ---------------------------------------------------------------------------

@dataclass
class SimState:
    # Dense replicas: pytree stack with leading R (engine="pytree") or a
    # persistent (R, n_rows, 128) fp32 flat buffer (engine="flat").
    w_stack: Pytree
    opt_stack: Pytree
    emb_state: Pytree  # shared {"table", "acc"}
    # Opaque, owned by the SyncAlgorithm (EASGD: the sync-PS copy; BMUF:
    # global model + block momentum; gossip: round counter; MA: None).
    algo_state: Any
    step: int


class HogwildSim:
    def __init__(
        self,
        cfg,  # DLRMConfig
        sync_cfg: S.SyncConfig,
        *,
        n_trainers: int,
        n_threads: int,
        batch_size: int,
        optimizer: Optimizer,
        emb_lr: float = 0.05,
        seed: int = 0,
        membership: Optional[Membership] = None,
        schedule: Optional[Union[MembershipSchedule, Sequence[Tuple[int, str, int]]]] = None,
        cache: Optional[CacheConfig] = None,
        pipeline: Optional[PipelineConfig] = None,
        mode_schedule: Optional[Union[ModeSchedule, Sequence[Tuple[int, str]]]] = None,
    ):
        self.cfg = cfg
        self.sync_cfg = sync_cfg.validate()
        # Runtime mode switching (DESIGN.md §14): a deterministic per-
        # iteration mode trace — scripted [(iteration, mode)] switch points
        # or a closed-loop ControllerModeSchedule — moves the whole cohort
        # between shadow and fixed_rate at iteration boundaries, with the
        # staleness-compensated handoff applied in run(). Without one, the
        # sim runs the exact legacy single-mode path (bit-identical).
        if mode_schedule is not None and not isinstance(mode_schedule, ModeSchedule):
            mode_schedule = ModeSchedule(mode_schedule, start_mode=sync_cfg.mode)
        if mode_schedule is not None and mode_schedule.start_mode != sync_cfg.mode:
            raise ValueError(
                f"mode_schedule starts in {mode_schedule.start_mode!r} but "
                f"sync_cfg.mode is {sync_cfg.mode!r}; construct them to agree")
        self.mode_schedule = mode_schedule
        # Tiered embedding cache (DESIGN.md §11): the packed table moves
        # behind a CachedStore and training runs lookup -> dense jit ->
        # fused update with only the hot tier device-resident. Deterministic:
        # the batch stream is a pure function of the iteration counter, so
        # the prefetch horizon is peeked, not raced.
        self.cache = cache.validate() if cache is not None else None
        # Step pipelining (DESIGN.md §13): a StepPipeline stages batch k+1's
        # lookup while batch k's dense jit runs, hazard-checked over the
        # peeked index stream so the trajectory stays bitwise-serial.
        self.pipeline = pipeline.validate() if pipeline is not None else None
        self.engine = sync_cfg.engine
        self.algo = algorithms.get(sync_cfg.algo)
        # Elastic membership: buffers are CAPACITY-padded at R_max; join/
        # leave/fail only flip the active mask — no reallocation, no retrace.
        # Without an explicit membership/schedule the sim runs the exact
        # legacy fixed-R path (bit-identical trajectories).
        self._elastic = membership is not None or schedule is not None
        if schedule is not None and not isinstance(schedule, MembershipSchedule):
            schedule = MembershipSchedule(schedule)
        self.schedule = schedule
        if membership is None:
            cap = n_trainers
            if schedule is not None:
                cap = max(cap, schedule.max_slot() + 1)
            membership = Membership(n_trainers, R_max=cap)
        if membership.R_max < n_trainers:
            raise ValueError(
                f"membership capacity {membership.R_max} < " f"n_trainers {n_trainers}"
            )
        self.membership = membership
        self.R, self.M, self.B = membership.R_max, n_threads, batch_size
        self.opt = optimizer
        self.emb_lr = emb_lr
        self.seed = seed
        self.spec = emb.spec_from_config(cfg)
        self.teacher = ctr.make_teacher(cfg, seed=seed + 777)
        self.flat = _dense_flatspace(cfg) if self.engine == "flat" else None
        self._build()

    # -- jitted pieces ------------------------------------------------------
    def _build(self):
        cfg, spec, opt, R, M = self.cfg, self.spec, self.opt, self.R, self.M

        def one_trainer(w, opt_state, dense, pooled, labels):
            # m thread-grads from the SAME snapshot, applied sequentially.
            loss, g_w, g_pooled = jax.vmap(
                dlrm.dense_loss_and_grads, in_axes=(None, 0, 0, 0)
            )(w, dense, pooled, labels)

            def apply_one(carry, g):
                w, st = carry
                w, st = opt.update(w, st, g)
                return (w, st), None

            (w, opt_state), _ = jax.lax.scan(apply_one, (w, opt_state), g_w)
            return w, opt_state, jnp.mean(loss), g_pooled

        def dense_core(state_w, state_opt, pooled, batch, active=None):
            # Everything downstream of the embedding lookup. Factored out of
            # train_core so the cached path can run it as its own jit with
            # ``pooled`` as an INPUT (lookup and sparse update run standalone
            # against the hot tier) — bitwise-identical to the fused program
            # (tests/test_cache.py pins this).
            w2, opt2, loss, g_pooled = jax.vmap(one_trainer)(
                state_w, state_opt, batch["dense"], pooled, batch["labels"]
            )
            if active is not None:
                # Elastic membership: dead slots are computed (shape-stable —
                # no retrace on join/leave) but contribute NOTHING: their
                # dense/optimizer updates are discarded and their embedding
                # gradients zeroed (a zero-gradient Adagrad row update is an
                # exact no-op: acc += 0, row += 0).
                def keep(new, old):
                    k = active.reshape((R,) + (1,) * (old.ndim - 1))
                    return jnp.where(k, new, old)

                w2 = jax.tree.map(keep, w2, state_w)
                opt2 = jax.tree.map(keep, opt2, state_opt)
                g_pooled = jnp.where(active.reshape((R, 1, 1, 1, 1)), g_pooled, 0.0)
            # elastic callers get the per-replica loss vector (the host masks
            # dead slots out of the reported mean and the join tests read it)
            return w2, opt2, (loss if active is not None else jnp.mean(loss)), g_pooled

        def train_core(state_w, state_opt, emb_state, batch, active=None):
            # batch leaves: (R, M, B, ...)
            idx = batch["sparse"]
            pooled = emb.lookup(
                emb_state, spec, idx.reshape(-1, cfg.n_sparse_features, cfg.multi_hot)
            )
            pooled = pooled.reshape(self.R, self.M, self.B, cfg.n_sparse_features, -1)
            w2, opt2, loss, g_pooled = dense_core(state_w, state_opt, pooled, batch, active=active)
            # Hogwild on the single embedding copy: every trainer/thread applies
            # immediately; one fused scatter-Adagrad kernel launch implements
            # the duplicate-row accumulate.
            flat_idx = idx.reshape(-1, cfg.n_sparse_features, cfg.multi_hot)
            flat_g = g_pooled.reshape(-1, cfg.n_sparse_features, cfg.embedding_dim)
            emb2 = emb.sparse_adagrad_update_fused(emb_state, spec, flat_idx, flat_g, self.emb_lr)
            return w2, opt2, emb2, loss

        sc = self.sync_cfg
        if self.engine == "flat":
            fs = self.flat

            def train_iter(w_buf, state_opt, emb_state, batch):
                # unpack -> train -> repack stays inside one jit: XLA fuses the
                # layout moves with the optimizer update, and the donated flat
                # buffer is re-emitted contiguously.
                w2, opt2, emb2, loss = train_core(
                    fs.unpack_stack(w_buf), state_opt, emb_state, batch
                )
                return fs.pack_stack(w2), opt2, emb2, loss

            def train_iter_elastic(w_buf, state_opt, emb_state, active, batch):
                w2, opt2, emb2, loss = train_core(
                    fs.unpack_stack(w_buf), state_opt, emb_state, batch, active=active
                )
                return fs.pack_stack(w2), opt2, emb2, loss

            # Sync launches/landings are owned by the algorithm (host hooks
            # dispatching fused Pallas kernels) — nothing to build here.
        else:
            train_iter = train_core

            def train_iter_elastic(state_w, state_opt, emb_state, active, batch):
                return train_core(state_w, state_opt, emb_state, batch, active=active)

            # pytree landing: one jit over the algorithm's oracle (retraces
            # only per snap/mask None-ness — a handful of structures). The
            # elastic path dispatches the algorithm's membership-aware
            # ``land_elastic`` host hook instead.
            self._land_py = jax.jit(
                lambda ws, st, snap, mask: self.algo.land(ws, st, snap, mask, sc)
            )

        self._train_iter = jax.jit(train_iter, donate_argnums=(0, 1, 2))
        self._train_iter_elastic = jax.jit(train_iter_elastic, donate_argnums=(0, 1, 2))

        # Cached-mode dense programs: pooled arrives as an input (the hot-
        # tier lookup ran standalone) and the sparse update runs standalone
        # after; the embedding state never enters this jit.
        if self.engine == "flat":
            fs = self.flat

            def dense_iter(w_buf, state_opt, pooled, batch):
                w2, opt2, loss, g = dense_core(fs.unpack_stack(w_buf), state_opt, pooled, batch)
                return fs.pack_stack(w2), opt2, loss, g

            def dense_iter_elastic(w_buf, state_opt, active, pooled, batch):
                w2, opt2, loss, g = dense_core(
                    fs.unpack_stack(w_buf), state_opt, pooled, batch, active=active
                )
                return fs.pack_stack(w2), opt2, loss, g
        else:
            def dense_iter(state_w, state_opt, pooled, batch):
                return dense_core(state_w, state_opt, pooled, batch)

            def dense_iter_elastic(state_w, state_opt, active, pooled, batch):
                return dense_core(state_w, state_opt, pooled, batch, active=active)

        self._dense_iter = jax.jit(dense_iter, donate_argnums=(0, 1))
        self._dense_iter_elastic = jax.jit(dense_iter_elastic, donate_argnums=(0, 1))

        # Pipelined-uncached programs (DESIGN.md §13): the split path's
        # standalone lookup/update, deliberately NON-donating — a staged
        # lookup holds a ref to the pre-update emb state while the update
        # for the current step produces the next one, so neither buffer may
        # be invalidated under the staging worker. Same module-jitted
        # kernels as train_core, so split == fused bitwise (the §11 cache
        # already pins the identical decomposition).
        self._lookup_iter = jax.jit(lambda emb_state, idx: emb.lookup(emb_state, spec, idx))
        self._update_iter = jax.jit(
            lambda emb_state, idx, g: emb.sparse_adagrad_update_fused(
                emb_state, spec, idx, g, self.emb_lr
            )
        )

        def eval_batch(w, emb_state, batch):
            pooled = emb.lookup(emb_state, spec, batch["sparse"])
            logits = dlrm.forward(w, batch["dense"], pooled)
            return dlrm.bce_loss(logits, batch["labels"])

        self._eval = jax.jit(eval_batch)

    # -- state --------------------------------------------------------------
    def init_state(self) -> SimState:
        key = jax.random.PRNGKey(self.seed)
        kw, ke = jax.random.split(key)
        w0 = dlrm.init_dense(self.cfg, kw)
        emb_state = emb.init_tables(self.spec, ke)
        opt0 = self.opt.init(w0)
        self._opt0 = opt0  # fresh-slot template for join bootstraps
        opt_stack = jax.tree.map(lambda x: jnp.broadcast_to(x, (self.R,) + x.shape).copy(), opt0)
        if self.engine == "flat":
            fs = self.flat
            w_stack = fs.broadcast(w0, self.R)  # packed ONCE, at capacity R_max
            algo_state = self.algo.init_state_flat(fs.pack(w0), self.sync_cfg, fs)
        else:
            w_stack = jax.tree.map(lambda x: jnp.broadcast_to(x, (self.R,) + x.shape).copy(), w0)
            algo_state = self.algo.init_state(w0, self.sync_cfg)
        return SimState(w_stack, opt_stack, emb_state, algo_state, 0)

    def make_batch(self, it: int) -> Dict[str, jnp.ndarray]:
        """One-pass stream: (R*M) distinct shards per iteration."""
        n = self.R * self.M
        b = ctr.gen_batch(self.cfg, self.teacher, self.seed, it, self.B * n)
        return jax.tree.map(lambda x: x.reshape(self.R, self.M, self.B, *x.shape[1:]), b)

    # -- sync scheduling ----------------------------------------------------
    def _shadow_schedule(self, t: int) -> np.ndarray:
        """mask[i]: replica i's shadow clock fires at iteration t (staggered)."""
        gap = self.sync_cfg.gap
        offs = (np.arange(self.R) * gap) // max(self.R, 1)
        return ((t + offs) % gap) == 0

    def _launch_snapshot(
        self, st: SimState, mask: np.ndarray, active: Optional[np.ndarray] = None
    ) -> Pytree:
        """State captured when a background sync launches (lands `delay` later).

        Flat engine: the algorithm picks its own compact form — a fired-rows
        gather (EASGD/gossip), a replica-mean plane (MA/BMUF), or a full
        buffer copy (the generic fallback). ``active`` is the membership mask
        at launch: a dead slot is never snapshotted and the decentralized
        mean divides by the live count.
        """
        if self.engine == "flat":
            return self.algo.launch_snapshot_flat(
                st.w_stack, mask, self.sync_cfg, self.flat, st.algo_state, active=active
            )
        # pytree: real deep copy (train_iter donates its buffers)
        return jax.tree.map(jnp.copy, st.w_stack)

    def _apply_membership_event(
        self, st: SimState, kind: str, slot: int, reason: str = ""
    ) -> SimState:
        """One schedule transition, at an iteration boundary. Joins bootstrap
        through the algorithm's ``on_join`` hook (live mean / PS copy) with a
        fresh optimizer slot; leaves/fails dispatch ``on_leave``. Nothing
        reallocates — the capacity-padded buffers just flip a mask bit.
        ``reason`` is provenance for the event log (e.g. a straggler-policy
        demotion — core/scheduler.py)."""
        sc, fs = self.sync_cfg, self.flat
        if kind in ("fail", "leave"):
            getattr(self.membership, kind)(slot, reason=reason)
            if self.engine == "flat":
                st.algo_state = self.algo.on_leave_flat(st.algo_state, slot, sc, fs)
            else:
                st.algo_state = self.algo.on_leave(st.algo_state, slot, sc)
            return st
        if kind != "join":
            raise ValueError(f"unknown membership event kind {kind!r}")
        donors = self.membership.active_mask()  # before the join
        self.membership.join(slot, reason=reason)
        if donors.any():  # no live donors -> keep the slot's current weights
            if self.engine == "flat":
                st.w_stack, st.algo_state = self.algo.on_join_flat(
                    st.w_stack, slot, st.algo_state, donors, sc, fs
                )
            else:
                st.w_stack, st.algo_state = self.algo.on_join(
                    st.w_stack, slot, st.algo_state, jnp.asarray(donors), sc
                )
        st.opt_stack = S.tree_set(st.opt_stack, slot, self._opt0)
        self.membership.activate(slot)
        return st

    def run(
        self,
        n_iters: int,
        *,
        log_every: int = 0,
        on_iter: Optional[Callable[[int, float], None]] = None,
        state: Optional[SimState] = None,
    ) -> Dict[str, Any]:
        """Train ``n_iters`` iterations. ``state`` resumes a prior run (e.g.
        an elastic ``load_state``): iteration numbering — and therefore the
        one-pass batch stream, the shadow-clock offsets, and the membership
        schedule — continues from ``state.step`` instead of replaying from
        zero."""
        st = self.init_state() if state is None else state
        sc = self.sync_cfg
        elastic = self._elastic
        cached = self.cache is not None
        pipelined = self.pipeline is not None
        store: Optional[CachedStore] = None
        batch_memo: Dict[int, Any] = {}
        gid_memo: Dict[int, np.ndarray] = {}
        offs = np.asarray(self.spec.offsets)
        F, m, d = (self.cfg.n_sparse_features, self.cfg.multi_hot, self.cfg.embedding_dim)
        if cached:
            # the packed table moves behind the two-tier store for the run;
            # merged() restores the canonical emb_state at the end, so
            # resume/save/eval see exactly the uncached representation
            store = CachedStore(st.emb_state, self.cache, dim=self.spec.dim)
            st.emb_state = None

        def _get_batch(it: int):
            if not cached:
                return self.make_batch(it)
            if it not in batch_memo:
                batch_memo[it] = self.make_batch(it)
            return batch_memo[it]

        def _gids(it: int) -> np.ndarray:
            # packed GLOBAL row ids of iteration ``it``'s batch — the peek:
            # the one-pass stream is a pure function of the iteration
            # counter, so "the next K queued batches" are regenerated, not
            # raced (memoized across the prefetch horizon)
            if it not in gid_memo:
                idx = np.asarray(_get_batch(it)["sparse"]).reshape(
                    -1, self.cfg.n_sparse_features, self.cfg.multi_hot
                )
                gid_memo[it] = idx + offs[None, :, None]
            return gid_memo[it]

        losses: List[float] = []
        replica_losses: List[np.ndarray] = []
        sync_count = 0
        examples = 0
        start = int(st.step)
        # Step pipelining (DESIGN.md §13): the staging worker peeks future
        # batches (pure in the iteration counter — regenerated, not shared
        # with this thread's memos) and dispatches their lookups while this
        # thread is blocked in the dense jit; the hazard check keeps the
        # trajectory bitwise-serial. The sim has ONE lookup unit (the packed
        # table), so n_shards=1.
        pipe: Optional[StepPipeline] = None
        if pipelined:

            def _prep_step(it: int) -> Dict[str, Any]:
                b = self.make_batch(it)
                idx = np.asarray(b["sparse"]).reshape(-1, F, m)
                gids = idx + offs[None, :, None]
                return {"rows": [np.unique(gids)], "batch": b, "idx": idx, "gids": gids}

            if cached:

                def _stage_lookup(s, it, prep, ctx):
                    # races only placement (promotions); values are
                    # placement-invariant and the hazard check guarantees
                    # no window update touches these rows
                    return store.lookup(prep["gids"], staged=True)

                _make_ctx = None
            else:

                def _stage_lookup(s, it, prep, ctx):
                    # ctx = the pre-update emb state captured at stage()
                    # time (immutable arrays; _update_iter does not donate)
                    return self._lookup_iter(ctx, prep["idx"])

                def _make_ctx():
                    return st.emb_state

            pipe = StepPipeline(
                self.pipeline, 1, prepare=_prep_step, stage_fn=_stage_lookup,
                make_ctx=_make_ctx, end=start + n_iters, name="sim-pipe",
            )
        # prefetch horizon composed with the pipeline depth: the prefetcher
        # must peek at least as far as lookups are staged (DESIGN.md §13)
        la = (
            self.cache.effective_lookahead(self.pipeline.depth if pipelined else 1)
            if cached
            else 0
        )
        # (land_t, snapshot, fired_mask, launch_active)
        pending: Optional[Tuple[int, Pytree, np.ndarray, Optional[np.ndarray]]] = None
        # Runtime mode switching (DESIGN.md §14). ``cur_mode`` tracks the
        # cohort's mode; the anchors realize the staleness-compensated
        # handoff: ``fr_anchor`` aligns the barrier cadence to the catch-up
        # sync that opened the fixed_rate phase, ``shadow_base`` seeds the
        # staggered shadow clocks from the last GLOBAL sync, and
        # ``last_global_sync`` remembers where that was. All stay 0 when no
        # switch ever fires, so a schedule-free run is bit-identical legacy.
        msched = self.mode_schedule
        cur_mode = sc.mode
        if msched is not None and start > 0:
            cur_mode = msched.mode_at(start - 1)  # mode already in effect
        mode_events: List[Tuple[int, str, str]] = []
        last_global_sync = 0
        fr_anchor = 0
        shadow_base = 0
        for t in range(start, start + n_iters):
            if elastic and self.schedule is not None:
                # plain schedules yield (kind, slot); a closed-loop
                # StragglerSchedule yields (kind, slot, reason) — provenance
                # rides into the membership event log
                evs = list(self.schedule.events_at(t))
                if evs and pipe is not None:
                    # in-flight stages predate the event: drain BEFORE the
                    # membership epoch advances (DESIGN.md §13)
                    pipe.drain()
                for ev in evs:
                    kind, slot = ev[0], ev[1]
                    reason = ev[2] if len(ev) > 2 else ""
                    st = self._apply_membership_event(st, kind, slot, reason)
            active = self.membership.active_mask() if elastic else None
            if msched is not None:
                mode = msched.mode_at(t)
                if mode != cur_mode:
                    # Mode handoff at the iteration boundary (DESIGN.md §14).
                    # In-flight pipeline stages predate the switch: drain on
                    # this (owning) thread before anything else moves.
                    if pipe is not None:
                        pipe.drain()
                    if mode == "fixed_rate":
                        # shadow -> fixed_rate: drop the in-flight launch
                        # (its snapshot is stale against the barrier about
                        # to arm) and run one foreground catch-up sync —
                        # GBA-style compensation, so stale replica deltas
                        # are merged before the first synchronous step.
                        pending = None
                        if active is None or active.any():
                            st = self._apply_sync(st, None, None, active=active)
                            sync_count += self.R if active is None else int(active.sum())
                        last_global_sync = t
                        fr_anchor = t
                    else:
                        # fixed_rate -> shadow: nothing in flight to drain
                        # (the sim's barrier is implicit); seed every
                        # replica's shadow clock from the LAST GLOBAL sync,
                        # so the staggered offsets resume as if the cohort
                        # had been on shadow clocks since that sync.
                        shadow_base = last_global_sync
                    mode_events.append((t, cur_mode, mode))
                    cur_mode = mode
            staged = prep = None
            if pipe is not None:
                staged, prep = pipe.consume(t)
            batch = prep["batch"] if prep is not None else _get_batch(t)
            if cached:
                if prep is not None:
                    # the worker already generated this step's batch/gids:
                    # seed the memos so the prefetch peek below reuses them
                    batch_memo.setdefault(t, batch)
                    gid_memo.setdefault(t, prep["gids"])
                # deterministic lookahead: one prefetch round covering the
                # horizon [t, t+K) at the iteration boundary — exactly what
                # the threaded shadow thread does between syncs, quantized
                if la:
                    store.prefetch([_gids(t + j) for j in range(la)])
                gids = prep["gids"] if prep is not None else _gids(t)
                if staged is not None and staged[0] is not None:
                    pooled = staged[0]  # batch t's lookup overlapped batch
                    # t-1's dense pass (bitwise: the hazard check held)
                else:
                    pooled = store.lookup(gids)
                pooled = pooled.reshape(self.R, self.M, self.B, F, -1)
                if elastic:
                    st.w_stack, st.opt_stack, loss_out, g_pooled = self._dense_iter_elastic(
                        st.w_stack, st.opt_stack, jnp.asarray(active), pooled, batch
                    )
                else:
                    st.w_stack, st.opt_stack, loss_out, g_pooled = (
                        self._dense_iter(st.w_stack, st.opt_stack, pooled, batch)
                    )
                if pipe is not None:
                    # stage AFTER the dense dispatch (the worker overlaps
                    # it) and BEFORE this step's sparse update lands
                    pipe.stage(t)
                # standalone fused scatter-Adagrad on the hot tier, same
                # (B*F, m)/(B*F, d) flattening as sparse_adagrad_update_fused
                store.update(
                    gids.reshape(-1, self.cfg.multi_hot),
                    g_pooled.reshape(-1, self.cfg.embedding_dim),
                    self.emb_lr,
                )
                for k in [k for k in gid_memo if k <= t]:
                    del gid_memo[k]
                    batch_memo.pop(k, None)
            elif pipelined:
                # uncached split path (standalone lookup -> dense jit ->
                # standalone update): bitwise-identical to the fused
                # program — same module-jitted kernels, same order (the
                # §11 cache pins the identical decomposition)
                idx = (
                    prep["idx"]
                    if prep is not None
                    else np.asarray(batch["sparse"]).reshape(-1, F, m)
                )
                if staged[0] is not None:
                    pooled = staged[0]
                else:
                    pooled = self._lookup_iter(st.emb_state, idx)
                pooled = pooled.reshape(self.R, self.M, self.B, F, -1)
                if elastic:
                    st.w_stack, st.opt_stack, loss_out, g_pooled = self._dense_iter_elastic(
                        st.w_stack, st.opt_stack, jnp.asarray(active), pooled, batch
                    )
                else:
                    st.w_stack, st.opt_stack, loss_out, g_pooled = (
                        self._dense_iter(st.w_stack, st.opt_stack, pooled, batch)
                    )
                pipe.stage(t)  # _make_ctx captures the PRE-update emb state
                st.emb_state = self._update_iter(st.emb_state, idx, g_pooled.reshape(-1, F, d))
            elif elastic:
                st.w_stack, st.opt_stack, st.emb_state, loss_out = self._train_iter_elastic(
                    st.w_stack, st.opt_stack, st.emb_state, jnp.asarray(active), batch
                )
            else:
                st.w_stack, st.opt_stack, st.emb_state, loss_out = (
                    self._train_iter(st.w_stack, st.opt_stack, st.emb_state, batch)
                )
            if elastic:
                lv = np.asarray(loss_out)
                replica_losses.append(lv)
                # an all-dead cohort trains nothing: nan, not a mean of []
                losses.append(float(lv[active].mean()) if active.any() else float("nan"))
                examples += int(active.sum()) * self.M * self.B
            else:
                losses.append(float(loss_out))
                examples += self.R * self.M * self.B
            if cur_mode == "fixed_rate":
                if (t + 1 - fr_anchor) % sc.gap == 0 and (active is None or active.any()):
                    st = self._apply_sync(st, None, None, active=active)
                    sync_count += self.R if active is None else int(active.sum())
                    last_global_sync = t + 1
            else:  # shadow
                if pending is not None and t + 1 >= pending[0]:
                    _, snap, mask, launch_active = pending
                    # landing reads the CURRENT membership — a slot that died
                    # while the sync was in flight is simply skipped (an
                    # all-dead cohort drops the landing entirely)
                    if active is None or active.any():
                        st = self._apply_sync(
                            st, snap, mask, active=active, launch_active=launch_active
                        )
                        sync_count += (int(mask.sum()) if mask is not None else self.R)
                    pending = None
                if pending is None:
                    mask = self._shadow_schedule(t + 1 - shadow_base)
                    if elastic:
                        mask = mask & active  # a dead slot's clock never fires
                    if mask.any():
                        if sc.delay == 0:
                            # Zero in-flight iterations: the sync launched at
                            # iteration t lands at iteration t, not t+1 (the
                            # landing check above has already run this round).
                            # No training step intervenes and the pytree
                            # landing doesn't donate, so skip the defensive
                            # deep copy; the flat engine still builds its
                            # compact launch form (the fused landing consumes
                            # exactly that shape).
                            snap = (self._launch_snapshot(st, mask, active)
                                    if self.engine == "flat" else st.w_stack)
                            st = self._apply_sync(
                                st, snap, mask, active=active, launch_active=active
                            )
                            sync_count += int(mask.sum())
                        else:
                            pending = (
                                t + 1 + sc.delay,
                                self._launch_snapshot(st, mask, active),
                                mask,
                                active,
                            )
            st.step = t + 1
            if on_iter:
                on_iter(t, losses[-1])
            if log_every and (t + 1) % log_every == 0:
                print(f"iter {t+1}: loss {np.mean(losses[-log_every:]):.5f}")
        if pipe is not None:
            # quiesce the staging worker before the canonical merge below
            # (a still-running staged lookup would race the hot-tier drain)
            pipe.close()
        if cached:
            # fold the hot tier back into the canonical packed state: the
            # cache is invisible to save/eval/resume (and to the caller)
            st.emb_state = store.merged()
        # replica-iterations actually trained (dead slots don't count):
        # identical to n_iters * R when membership never changes
        replica_iters = examples // (self.M * self.B)
        out = {
            "state": st,
            "train_loss": losses,
            "sync_count": sync_count,
            "avg_sync_gap": (replica_iters / max(sync_count, 1)),
            "examples": examples,
        }
        if cached:
            out["cache_stats"] = store.stats.as_dict()
        if pipe is not None:
            out["pipeline_stats"] = pipe.stats.as_dict()
        if elastic:
            out["replica_losses"] = np.stack(replica_losses)
            out["membership_events"] = list(self.membership.events)
        if msched is not None:
            # (iteration, from_mode, to_mode) handoffs this run applied —
            # the reproducibility contract: two runs of the same schedule
            # produce identical mode_events AND identical trajectories
            out["mode_events"] = mode_events
            out["mode"] = cur_mode
        return out

    def _apply_sync(self, st: SimState, snap, mask, active=None, launch_active=None) -> SimState:
        """Land one background sync: the algorithm owns the semantics (one
        fused kernel launch on the flat engine; the jitted pytree oracle
        otherwise). ``snap=None`` means fixed-rate — sync against the current
        state; ``mask=None`` means every replica fired; ``active`` /
        ``launch_active`` are the membership masks at landing / launch time
        (None == not elastic)."""
        if self.engine == "flat":
            st.w_stack, st.algo_state = self.algo.land_flat(
                st.w_stack, st.algo_state, snap, mask, self.sync_cfg, self.flat, active=active
            )
        elif active is None:
            mask_arr = None if mask is None else jnp.asarray(mask)
            st.w_stack, st.algo_state = self._land_py(st.w_stack, st.algo_state, snap, mask_arr)
        else:
            st.w_stack, st.algo_state = self.algo.land_elastic(
                st.w_stack,
                st.algo_state,
                snap,
                mask,
                active,
                self.sync_cfg,
                launch_active=launch_active,
            )
        return st

    def replica_params(self, st: SimState, i: int) -> Pytree:
        """Replica i's dense weights as a pytree, whatever the engine."""
        if self.engine == "flat":
            return self.flat.unpack_replica(st.w_stack, i)
        return S.tree_slice(st.w_stack, i)

    def dense_stack(self, st: SimState) -> Pytree:
        """The dense replica stack as an engine-independent pytree (leading R)
        — the stable on-disk / external representation."""
        if self.engine == "flat":
            return self.flat.unpack_stack(st.w_stack)
        return st.w_stack

    # -- elastic checkpointing (DESIGN.md §8.5) ------------------------------
    def _state_tree(self, st: SimState) -> Dict[str, Any]:
        """Engine-independent on-disk form: dense replicas as the named
        pytree stack, the embedding collection in its logical (rows, dim)
        view, optimizer + opaque algorithm state."""
        return {
            "w": self.dense_stack(st),
            "opt": st.opt_stack,
            "emb": emb.to_logical(st.emb_state, self.spec),
            "algo": st.algo_state,
        }

    def save_state(
        self, path: str, st: SimState, metadata: Optional[Dict[str, Any]] = None
    ) -> None:
        meta = {
            "step": st.step,
            "algo": self.sync_cfg.algo,
            "engine": self.engine,
            "R": self.R,
            "active_mask": [bool(b) for b in self.membership.active_mask()],
        }
        meta.update(metadata or {})
        ckpt.save(path, self._state_tree(st), metadata=meta)

    def load_state(self, path: str) -> SimState:
        """Elastic restore: the checkpoint's replica count may differ from
        this sim's capacity. Shrink truncates the replica axis; every slot
        that is active NOW but was not live at save time — grown slots AND
        slots that were dead when saved (their rows are stale) — is
        bootstrapped through the algorithm's ``on_join`` hook (live mean /
        PS copy) with a fresh optimizer state, so resuming a run saved at
        R=4 with R=6 just works and a dead-at-save slot is never silently
        resurrected from stale weights."""
        meta0 = ckpt.read_metadata(path)
        for field in ("engine", "algo"):
            want = getattr(self, field) if field == "engine" else self.sync_cfg.algo
            if field in meta0 and meta0[field] != want:
                raise ValueError(
                    f"checkpoint at {path!r} was saved with {field}="
                    f"{meta0[field]!r} but this sim runs {field}={want!r}; "
                    f"construct the sim to match (the algo_state layout is "
                    f"{field}-specific)")
        template = self.init_state()
        like = self._state_tree(template)
        # only the replica-stacked trees may resize; a mismatch anywhere
        # else (e.g. embedding rows from a different config) must raise
        replica_stacked = lambda k: k == "w" or k.startswith("w/") \
            or k == "opt" or k.startswith("opt/")
        tree, meta, resized = ckpt.restore_elastic(path, like, may_resize=replica_stacked)
        w_stack = (self.flat.pack_stack(tree["w"]) if self.engine == "flat" else tree["w"])
        st = SimState(
            w_stack,
            tree["opt"],
            emb.from_logical(tree["emb"], self.spec),
            tree["algo"],
            int(meta.get("step", 0)),
        )
        saved_R = int(meta.get("R", self.R))
        # donors = the restored cohort: rows live at SAVE time (and present
        # after any truncation)
        donors = np.zeros((self.R,), bool)
        k = min(saved_R, self.R)
        saved_active = meta.get("active_mask")
        if saved_active is None:
            donors[:k] = True
        else:
            donors[:k] = np.asarray(saved_active, bool)[:k]
        need = self.membership.active_mask() & ~donors
        sc, fs = self.sync_cfg, self.flat
        for slot in np.flatnonzero(need):
            slot = int(slot)
            if donors.any():
                if self.engine == "flat":
                    st.w_stack, st.algo_state = self.algo.on_join_flat(
                        st.w_stack, slot, st.algo_state, donors, sc, fs
                    )
                else:
                    st.w_stack, st.algo_state = self.algo.on_join(
                        st.w_stack, slot, st.algo_state, jnp.asarray(donors), sc
                    )
            st.opt_stack = S.tree_set(st.opt_stack, slot, self._opt0)
        return st

    def evaluate(
        self, st: SimState, n_batches: int = 20, batch_size: int = 4096, replica: int = 0
    ) -> float:
        """Paper protocol: evaluate the FIRST trainer's replica."""
        w = self.replica_params(st, replica)
        tot = 0.0
        for i in range(n_batches):
            b = ctr.gen_batch(self.cfg, self.teacher, self.seed + 10_000_000, i, batch_size)
            tot += float(self._eval(w, st.emb_state, b))
        return tot / n_batches


# ---------------------------------------------------------------------------
# Real-thread runner (faithful Algorithm 1)
# ---------------------------------------------------------------------------

class ThreadedShadowRunner:
    """Trainer threads + a background shadow thread over genuinely shared state.

    The embedding state is read-modify-written WITHOUT a lock (Hogwild: concurrent
    trainers can lose updates — that is the point). Dense replicas are owned by
    their trainer; the shadow thread interpolates them in the background.

    The embedding collection is plan-sharded (``embeddings/shards.py``): the
    LPT bin-pack plan splits the packed tables into ``n_emb_shards``
    independent per-PS Hogwild states. Lookups route by the plan (one fused
    lookup+pool kernel launch per shard) and each trainer's backward is one
    fused scatter-Adagrad launch per shard — writes to different PSs are
    independent jitted calls on independent arrays, so they no longer
    serialize through a single scatter over one packed table.

    Flat engine: each replica is one contiguous (n_rows, 128) fp32 plane and
    the shadow thread's exchange is a handful of fused kernel launches per
    round. The round itself is built by the SyncAlgorithm
    (``make_shadow_round``), so this runner hosts any registered algorithm:
    EASGD pairs against the PS plane, slice-free decentralized mean +
    pull-backs (MA), the full block-momentum global step (BMUF), or rotating
    pairwise exchanges (gossip).

    ``run()`` counts the dense updates this lock-free interleaving erases,
    per slot, by identity of the replica objects. ``overwritten_steps``: a
    round's publish replaced a plane that a trainer step had reassigned
    after the round captured it. ``overwritten_landings``: a trainer's
    assignment replaced a plane that a publish had landed after the trainer
    read it; this count is slot-owned and taken without a lock, so a publish
    that lands between its check and the assignment (two bytecodes) goes
    uncounted. Each trainer iteration and each shadow phase is a
    ``jax.profiler.TraceAnnotation`` (``trainer.*``, ``shadow.*``), so a
    profiler trace names what the host was doing on the device's clock."""

    def __init__(
        self,
        cfg,
        sync_cfg: S.SyncConfig,
        *,
        n_trainers: int,
        batch_size: int,
        optimizer: Optimizer,
        emb_lr: float = 0.05,
        seed: int = 0,
        sync_sleep_s: float = 0.0,
        n_emb_shards: Optional[int] = None,
        fault_spec: Optional[FaultSpec] = None,
        membership: Optional[Membership] = None,
        eps_window_s: float = 2.0,
        straggler_policy: Optional[StragglerPolicy] = None,
        supervise: bool = True,
        supervisor_config: Optional[SupervisorConfig] = None,
        ps_snapshot_every: int = 2,
        shard_retry: Optional[emb_shards.ShardRetryPolicy] = None,
        cache: Optional[CacheConfig] = None,
        pipeline: Optional[PipelineConfig] = None,
        mode_controller: Optional[ModeController] = None,
    ):
        self.cfg, self.sync_cfg = cfg, sync_cfg.validate()
        # Tuning-free mode switching (DESIGN.md §14): when a ModeController
        # is supplied, the run starts in sync_cfg.mode but the controller —
        # evaluated every shadow round over live busy-EPS dispersion (plus
        # the loss-divergence quality skew) — may move the WHOLE cohort
        # between shadow and fixed_rate mid-run, with the staleness-
        # compensated handoff applied in run().
        self.mode_ctl = mode_controller
        if mode_controller is not None and mode_controller.mode != sync_cfg.mode:
            raise ValueError(
                f"mode_controller starts in {mode_controller.mode!r} but "
                f"sync_cfg.mode is {sync_cfg.mode!r}; construct them to agree")
        # Tiered embedding cache (DESIGN.md §11): each PS fronts its table
        # with a two-tier store; the shadow thread (already the background
        # worker) runs the lookahead prefetcher between syncs.
        self.cache = cache.validate() if cache is not None else None
        # Step pipelining (DESIGN.md §13): each trainer owns a StepPipeline
        # that stages hazard-free per-shard lookups one-plus steps ahead.
        self.pipeline = pipeline.validate() if pipeline is not None else None
        self.engine = sync_cfg.engine
        self.algo = algorithms.get(sync_cfg.algo)
        self.R, self.B = n_trainers, batch_size
        self.opt = optimizer
        self.emb_lr = emb_lr
        self.seed = seed
        self.sync_sleep_s = sync_sleep_s
        # Fault-injection harness + elastic membership (DESIGN.md §8.4):
        # slots with a join_at schedule start dead and bootstrap mid-run.
        self.fault = (fault_spec or FaultSpec()).validate(n_trainers)
        # Closed-loop straggler controller (DESIGN.md §9): evaluated in the
        # shadow thread each round (mode="shadow") or by a lightweight
        # monitor thread (mode="fixed_rate", which has no shadow thread).
        if straggler_policy is not None and straggler_policy.n_slots != n_trainers:
            raise ValueError(
                f"straggler_policy watches "
                f"{straggler_policy.n_slots} slots, runner has "
                f"{n_trainers} trainers"
            )
        self.policy = straggler_policy
        if membership is None:
            membership = Membership.from_mask(
                [i not in self.fault.join_at for i in range(n_trainers)]
            )
        if membership.R_max != n_trainers:
            raise ValueError(
                f"membership capacity {membership.R_max} != " f"n_trainers {n_trainers}"
            )
        self.membership = membership
        self.eps_window_s = eps_window_s
        self.spec = emb.spec_from_config(cfg)
        self.teacher = ctr.make_teacher(cfg, seed=seed + 777)
        self.flat = _dense_flatspace(cfg) if self.engine == "flat" else None
        if n_emb_shards is None:
            n_emb_shards = min(4, cfg.n_sparse_features)
        # The LPT bin_pack plan assigns tables to embedding PSs (paper §3.1);
        # lookups and sparse updates route by it below.
        self.plan = emb_shards.plan_shards(self.spec, n_emb_shards, batch_size)
        self.n_emb_shards = self.plan.n_shards
        # Failure-domain supervision (DESIGN.md §10): heartbeats over every
        # long-lived thread, bounded shadow restarts, PS fail/recover
        # orchestration. Chaos injection (sync_crash_at / sync_stall_at /
        # ps_fail_at) rides the supervisor's watch loop, so a FaultSpec that
        # kills the sync thread or a PS requires supervise=True.
        self.supervise = bool(supervise)
        self.supervisor_config = (supervisor_config or SupervisorConfig()).validate()
        if ps_snapshot_every < 1:
            raise ValueError(f"ps_snapshot_every must be >= 1, got " f"{ps_snapshot_every}")
        self.ps_snapshot_every = int(ps_snapshot_every)
        self.shard_retry = shard_retry
        for s in self.fault.ps_fail_at:
            if not 0 <= s < self.n_emb_shards:
                raise ValueError(
                    f"ps_fail_at names shard {s}, but the plan "
                    f"has {self.n_emb_shards} embedding shards"
                )
        sync_chaos = (self.fault.sync_crash_at is not None or self.fault.sync_stall_at is not None)
        if sync_chaos and self.sync_cfg.mode == "fixed_rate" and self.mode_ctl is None:
            raise ValueError(
                "sync_crash_at / sync_stall_at target the shadow thread; "
                "static mode='fixed_rate' has none (auto-mode runs — with a "
                "mode_controller — always keep one)"
            )
        if (sync_chaos or self.fault.ps_fail_at) and not self.supervise:
            raise ValueError(
                "FaultSpec injects sync/PS chaos, but "
                "supervise=False — the supervisor is both the "
                "injection clock and the recovery path"
            )
        self.supervisor: Optional[Supervisor] = None
        plan = self.plan

        def dense_one(w, opt_state, pooled, batch):
            # downstream of the lookup — the cached path's jit (pooled came
            # off the hot tiers via cached_lookup)
            loss, g_w, g_pooled = dlrm.dense_loss_and_grads(
                w, batch["dense"], pooled, batch["labels"]
            )
            w, opt_state = optimizer.update(w, opt_state, g_w)
            return w, opt_state, loss, g_pooled

        def train_one(w, opt_state, shard_tables, batch):
            pooled = emb_shards.shard_lookup(plan, shard_tables, batch["sparse"])
            return dense_one(w, opt_state, pooled, batch)

        def _make_shard_update(s: int):
            return jax.jit(lambda st, idx, g: emb_shards.shard_update(plan, s, st, idx, g, emb_lr))

        self._emb_updates = [_make_shard_update(s) for s in range(self.n_emb_shards)]

        if self.engine == "flat":
            fs = self.flat

            def train_one_flat(w_plane, opt_state, emb_table, batch):
                w, opt_state, loss, g_pooled = train_one(
                    fs.unpack(w_plane), opt_state, emb_table, batch
                )
                return fs.pack(w), opt_state, loss, g_pooled

            def dense_one_flat(w_plane, opt_state, pooled, batch):
                w, opt_state, loss, g_pooled = dense_one(
                    fs.unpack(w_plane), opt_state, pooled, batch
                )
                return fs.pack(w), opt_state, loss, g_pooled

            self._train_one = jax.jit(train_one_flat)
            self._train_dense = jax.jit(dense_one_flat)
        else:
            self._train_one = jax.jit(train_one)
            self._train_dense = jax.jit(dense_one)
        # The background round: a host callable from the algorithm that
        # mutates the per-trainer planes/pytrees in place (Algorithm 1).
        self._shadow_round = self.algo.make_shadow_round(self.sync_cfg, self.flat)

    def warmup(self, iters: int = 1) -> None:
        """Trace/compile this runner's jitted programs on throwaway state.

        Each runner instance owns fresh ``jax.jit`` wrappers, so its first
        training iteration pays tracing (~0.5-2 s on a loaded box) — enough
        to dominate a short benchmark run and to blind the straggler
        controller's meters during exactly the window it should be
        detecting in. Warming up touches no membership, meters, or
        measured state."""
        key = jax.random.PRNGKey(self.seed)
        kw, ke = jax.random.split(key)
        w0 = dlrm.init_dense(self.cfg, kw)
        plane = self.flat.pack(w0) if self.engine == "flat" else w0
        opt0 = self.opt.init(w0)
        embs = emb_shards.EmbeddingShards.init(self.plan, ke, cache=self.cache)
        for it in range(iters):
            batch = ctr.gen_batch(self.cfg, self.teacher, self.seed, it, self.B)
            if self.cache is not None:
                sparse_np = np.asarray(batch["sparse"])
                pooled = embs.cached_lookup(sparse_np)
                plane, opt0, _, g_pooled = self._train_dense(plane, opt0, pooled, batch)
                for s in range(self.n_emb_shards):
                    embs.cached_update(s, sparse_np, g_pooled, self.emb_lr)
            else:
                plane, opt0, _, g_pooled = self._train_one(plane, opt0, embs.tables(), batch)
                for s in range(self.n_emb_shards):
                    embs.states[s] = self._emb_updates[s](embs.states[s], batch["sparse"], g_pooled)
        # the background/foreground sync round is its own jitted program
        # (retraced per live count): warm it at the initial cohort size on
        # throwaway state, or the FIRST measured round pays the trace —
        # inside the controller's detection window
        n_live = max(int(self.membership.active_ids().size), 1)
        if self.engine == "flat":
            algo_state = self.algo.init_state_flat(plane, self.sync_cfg, self.flat)
        else:
            algo_state = self.algo.init_state(w0, self.sync_cfg)
        # Also warm every cohort size the FaultSpec/policy can retrace to
        # mid-run: each crash/raise (and a straggler demotion) shrinks the
        # cohort by one, each scheduled join grows it by one. Without this
        # the first round AFTER an elastic event pays the trace — exactly
        # when the membership epoch just advanced and the controller is
        # re-baselining (the PR 5 fix warmed only the initial size).
        shrinks = (
            len(self.fault.crash_at)
            + len(self.fault.raise_at)
            + (1 if self.policy is not None else 0)
        )
        grows = len(self.fault.join_at)
        sizes = {n_live}
        sizes.update(max(n_live - k, 1) for k in range(1, shrinks + 1))
        sizes.update(min(n_live + k, self.R) for k in range(1, grows + 1))
        for n in sorted(sizes):
            self._shadow_round([plane] * n, algo_state)

    # holds-lock: _state_lock
    def _dispatch_on_leave(self, slot: int) -> None:
        """Engine-dispatched algorithm hook for a departing slot. Caller
        holds ``_state_lock``."""
        if self.engine == "flat":
            self.algo_state = self.algo.on_leave_flat(
                self.algo_state, slot, self.sync_cfg, self.flat
            )
        else:
            self.algo_state = self.algo.on_leave(self.algo_state, slot, self.sync_cfg)

    # holds-lock: _state_lock
    def _admit_slot(self, slot: int, reason: str = "") -> None:
        """join -> bootstrap -> activate, the one admission sequence (used
        by the join_at fault path and policy re-admission). Caller holds
        ``_state_lock``."""
        self.membership.join(slot, reason=reason)
        self._bootstrap_join(slot)
        self.membership.activate(slot)

    # holds-lock: _state_lock; lock-blocking: ok — admission must be atomic
    # with the membership transition; joins are rare and bounded (one stack
    # + on_join hook over the live cohort)
    def _bootstrap_join(self, i: int) -> None:
        """Bootstrap a joining slot through the algorithm's ``on_join`` hook
        (live mean / PS copy) with a fresh optimizer state. Called between
        ``membership.join`` and ``membership.activate`` — the joiner is not
        yet in the active mask, so the donors are exactly the live cohort.
        The hook sees a COMPACT stack of [donor planes..., joiner plane]
        (joiner last) rather than a copy of the whole replica space — this
        runs under ``_state_lock``, so the copy is kept to the data a donor
        mean actually needs."""
        donor_ids = [int(j) for j in self.membership.active_ids()]
        if not donor_ids:  # no live donors: keep the slot's current weights
            self.opt_states[i] = self.opt.init(self._w0)
            return
        slot = len(donor_ids)  # joiner's position in the compact stack
        active = np.asarray([True] * slot + [False])
        if self.engine == "flat":
            buf = jnp.stack([self.w[j] for j in donor_ids] + [self.w[i]])
            buf, self.algo_state = self.algo.on_join_flat(
                buf, slot, self.algo_state, active, self.sync_cfg, self.flat
            )
            self.w[i] = buf[slot]
        else:
            trees = [self.w[j] for j in donor_ids] + [self.w[i]]
            stack = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
            stack, self.algo_state = self.algo.on_join(
                stack, slot, self.algo_state, jnp.asarray(active), self.sync_cfg
            )
            self.w[i] = S.tree_slice(stack, slot)
        self.opt_states[i] = self.opt.init(self._w0)

    def _merged_pipe_stats(self) -> Dict[str, Any]:
        """Sum the per-trainer pipeline counters (harvested in each
        trainer's finally, read here post-join)."""
        total = PipelineStats()
        for st in self._pipe_stats:
            if st is not None:
                total.add(st)
        return total.as_dict()

    def run(self, iters_per_trainer: int) -> Dict[str, Any]:
        key = jax.random.PRNGKey(self.seed)
        kw, ke = jax.random.split(key)
        w0 = dlrm.init_dense(self.cfg, kw)
        self._w0 = w0  # hogwild-race: ok — written once pre-spawn, read-only after
        if self.engine == "flat":
            plane0 = self.flat.pack(w0)
            # swap-published: elements — slot planes rebound wholesale
            # (trainer i publishes w[i]; the sync round republishes the cohort)
            self.w: List[Pytree] = [plane0.copy() for _ in range(self.R)]
            # guarded-by: _state_lock
            self.algo_state = self.algo.init_state_flat(plane0, self.sync_cfg, self.flat)
        else:
            # swap-published: elements
            self.w = [jax.tree.map(lambda x: x.copy(), w0) for _ in range(self.R)]
            self.algo_state = self.algo.init_state(w0, self.sync_cfg)  # guarded-by: _state_lock
        # swap-published: elements — fresh optimizer state per publish
        self.opt_states = [self.opt.init(w0) for _ in range(self.R)]
        # Per-PS Hogwild states, seed-identical to the packed single table.
        # hogwild-race: ok — bound once pre-spawn; rebinding after spawn is a bug
        self.emb = emb_shards.EmbeddingShards.init(
            self.plan, ke, retry=self.shard_retry, cache=self.cache
        )
        self.done = threading.Event()
        self.examples = 0  # guarded-by-writes: ex_lock — post-join reads are lock-free
        self.sync_count = 0  # guarded-by-writes: _sync_lock — post-join reads are lock-free
        # erased dense updates per slot (class docstring)
        self.overwritten_steps = [0] * self.R  # guarded-by-writes: _state_lock
        # hogwild-race: ok — slot-owned counters
        self.overwritten_landings = [0] * self.R
        # Failure-domain bookkeeping (DESIGN.md §10): captured trainer
        # exceptions (re-raised with slot provenance after join), dead sync-
        # thread incarnations, restart/degradation state, PS chaos tracking.
        self._trainer_excs: List[Tuple[int, BaseException]] = []  # guarded-by-writes: _state_lock
        # hogwild-race: ok — append-only post-mortem log, atomic under the GIL
        self._sync_excs: List[BaseException] = []
        # hogwild-race: ok — single logical writer (generation-fenced shadow)
        self._shadow_rounds = 0
        self._sync_degraded = False  # hogwild-race: ok — single store, read post-join
        self._sync_stalled = False  # hogwild-race: ok — same contract
        self._sync_crash_t: Optional[float] = None  # hogwild-race: ok — same contract
        # hogwild-race: ok — restart hook appends; read post-join
        self._sync_count_at_restart: List[int] = []
        self._ps_injected: set = set()  # hogwild-race: ok — supervision tick owns it
        self._tick_count = 0  # hogwild-race: ok — supervision tick owns it
        self._sync_lock = threading.Lock()  # shadow/trainer threads both add
        # serializes algo_state transitions: the shadow round vs the rare
        # crash/join handlers (an unguarded read-modify-write could revert a
        # round's PS/consensus update with a stale copy)
        self._state_lock = threading.Lock()
        # guarded-by-writes: ex_lock — adds share examples' lock; eps reads are lock-free
        self.eps_meter = EPSMeter(window_s=self.eps_window_s)
        # Per-slot meters on each trainer's BUSY clock (compute + injected
        # degradation, excluding barrier waits): under fixed_rate the barrier
        # equalizes everyone's wall-clock rate, so busy-time is the only
        # signal that identifies the straggler (core/scheduler.py).
        self.slot_eps = SlotEPS(self.R, window_s=self.eps_window_s)  # hogwild-race: ok — slot-owned
        # thread-alive flags: the controller must not judge a trainer that
        # merely FINISHED (its rate decays to zero) nor re-admit a ghost
        # guarded-by-writes: _state_lock — cleared on trainer exit under the
        # lock so _readmit's alive check is race-free; reads are advisory
        self._alive = [True] * self.R
        self.iter_count = [0] * self.R  # hogwild-race: ok — slot-owned counters
        trainer_wall = [0.0] * self.R  # hogwild-race: ok — slot-owned cells, read post-join
        # Per-trainer step pipelines (DESIGN.md §13): each slot owns one
        # StepPipeline staging its own hazard-free per-shard lookups.
        # hogwild-race: ok — slot-owned cells
        self._pipes: List[Optional[StepPipeline]] = [None] * self.R
        # hogwild-race: ok — slot-owned cells, merged post-join
        self._pipe_stats: List[Optional[PipelineStats]] = [None] * self.R
        # hogwild-race: ok — slot-owned lists, merged post-join
        losses: List[List[float]] = [[] for _ in range(self.R)]
        # Quality signals (DESIGN.md §14): per-slot loss EMA feeds the
        # policy's loss-divergence demotion and the controller's quality
        # skew.
        # hogwild-race: ok — slot-owned cells (each trainer writes only its
        # own; reader threads see a coherent latest float)
        self._loss_ema = [float("nan")] * self.R
        # perf_counter stamp of each slot's last LANDED sync — the gradient
        # staleness age the policy judges. Written in the round's publish
        # step.
        # guarded-by-writes: _state_lock — lock-free reads see a coherent
        # latest stamp
        self._last_sync_t = [time.perf_counter()] * self.R
        ex_lock = threading.Lock()
        auto = self.mode_ctl is not None
        # static fixed_rate: no shadow thread exists, the monitor thread
        # carries the policy. Auto-mode runs ALWAYS keep the shadow thread
        # (it is the mode/policy evaluator even while the barrier owns the
        # rounds), so fr_static gates the no-shadow-thread paths.
        fr_static = (not auto) and self.sync_cfg.mode == "fixed_rate"
        has_fr = fr_static or auto
        # The cohort's CURRENT mode. Static runs pin it forever; auto runs
        # move it in _apply_mode_switch.
        # guarded-by-writes: _fr_cond — trainers read it lock-free each
        # iteration; a stale read is bounded-safe (an unregistered waiter
        # returns immediately from the sync point, and a trainer that
        # misses one barrier boundary re-arrives at its next gap — the
        # barrier waits, never deadlocks)
        self._mode = self.sync_cfg.mode
        # bumped on every handoff: trainers drain their own (owner-
        # confined) step pipelines when they observe it moved
        self._mode_gen = 0  # guarded-by-writes: _fr_cond
        if has_fr:
            # Foreground sync point: a Condition-based barrier whose party
            # count tracks membership, so a crash shrinks it instead of
            # deadlocking — but a straggler still drags EVERYONE (the paper's
            # fixed-rate failure mode, restated as fault tolerance) until the
            # straggler policy (if any) demotes it out of the barrier.
            self._fr_cond = threading.Condition()
            # guarded-by: _fr_cond
            self._fr_registered = [bool(b) for b in self.membership.active_mask()]
            # per-slot arrival flags, not a counter: the barrier fires only
            # when every REGISTERED slot has arrived, so demoting a slot
            # that is already waiting cannot leave a stale arrival that
            # releases the round before the rest of the cohort shows up
            self._fr_arrived = [False] * self.R  # guarded-by: _fr_cond
            self._fr_gen = 0  # guarded-by: _fr_cond
            # slot id of the thread elected to run the current round, while
            # it runs OUTSIDE the condition; None when no round is in flight
            self._fr_leader: Optional[int] = None  # guarded-by: _fr_cond
        initial_active = set(int(j) for j in self.membership.active_ids())
        # guarded-by-writes: ex_lock — late joiners poll it lock-free
        self._initial_running = len(initial_active)

        def _progress() -> int:
            return max((self.iter_count[j] for j in initial_active), default=iters_per_trainer)

        def _add_syncs(n: int) -> None:
            with self._sync_lock:
                self.sync_count += n

        def _beat(name: str) -> None:
            # liveness heartbeat; `sup` is bound later in run() — closures
            # resolve it at call time, after the threads have started
            if sup is not None:
                sup.beat(name)

        # Lookahead prefetch (DESIGN.md §11): each trainer's stream is a pure
        # function of (seed + slot, iteration), so the next K queued batches
        # per live trainer are PEEKED — regenerated on the host, memoized
        # across rounds — and their per-shard miss sets staged cold->hot by
        # the background worker between syncs. A trainer that outruns the
        # horizon pays a counted synchronous promotion, never a stall of
        # anyone else.
        _peek_memo: Dict[Tuple[int, int], np.ndarray] = {}  # guarded-by: _prefetch_gate
        _prefetch_gate = threading.Lock()

        def _prefetch_step() -> None:
            if self.cache is None or self.cache.lookahead == 0:
                return
            # the pipeline stages lookups up to depth-1 steps ahead of the
            # trainer's clock; the prefetch horizon must cover at least that
            # far or staged lookups systematically miss (DESIGN.md §13)
            la = self.cache.effective_lookahead(
                self.pipeline.depth if self.pipeline is not None else 1
            )
            if not _prefetch_gate.acquire(blocking=False):
                return  # another incarnation (restart race) is mid-round
            try:
                horizons: List[List[np.ndarray]] = [[] for _ in range(self.n_emb_shards)]
                for i in range(self.R):
                    if not self._alive[i]:
                        continue
                    base = self.iter_count[i]
                    for j in range(la):
                        it = base + j
                        if it >= iters_per_trainer:
                            break
                        idx = _peek_memo.get((i, it))
                        if idx is None:
                            idx = np.asarray(ctr.gen_batch(
                                self.cfg, self.teacher, self.seed + i, it, self.B
                            )["sparse"])
                            _peek_memo[(i, it)] = idx
                        for s in range(self.n_emb_shards):
                            horizons[s].append(emb_shards._route_np(self.plan, s, idx))
                for k in [k for k in _peek_memo if k[1] < self.iter_count[k[0]]]:
                    del _peek_memo[k]  # trained past it: peek no longer queued
                for s in range(self.n_emb_shards):
                    store = self.emb.stores[s]
                    if store is not None and self.emb.health[s]:
                        # lock-blocking: ok — the non-blocking gate IS the
                        # round's mutual exclusion; no thread ever waits on it
                        store.prefetch(horizons[s])
            finally:
                _prefetch_gate.release()

        def _round_over_active() -> int:
            # The round runs over the LIVE planes only: the matching/mean/PS
            # exchange is drawn over membership.active_ids() — dead slots are
            # simply skipped, training never blocks on them.
            #
            # The round itself is kernel dispatch wholesale, so it must not
            # run under _state_lock (no-blocking-under-lock, DESIGN.md §12):
            # capture the cohort + algorithm state under the lock, run the
            # round outside it, then publish only if neither moved in the
            # meantime. A discarded round is harmless — by the isolation
            # property the next round simply syncs strictly fresher planes.
            with TraceAnnotation("shadow.capture"), self._state_lock:
                epoch = self.membership.epoch
                ids = self.membership.active_ids()
                if ids.size == 0:
                    return 0
                state_in = self.algo_state
                sub = [self.w[j] for j in ids]
            captured = list(sub)  # the round replaces sub's entries in place
            with TraceAnnotation("shadow.round"):
                new_state, n = self._shadow_round(sub, state_in)
            with TraceAnnotation("shadow.publish"), self._state_lock:
                if (self.membership.epoch != epoch or self.algo_state is not state_in):
                    return 0  # membership/algo state moved under the round
                self.algo_state = new_state
                now_sync = time.perf_counter()
                for k, j in enumerate(ids):
                    if self.w[j] is not captured[k]:
                        self.overwritten_steps[j] += 1  # a step landed since the capture
                    self.w[j] = sub[k]
                    # the slot's deltas just landed: its staleness age resets
                    self._last_sync_t[j] = now_sync
                return n

        def _fr_ready_locked() -> bool:  # holds-lock: _fr_cond
            regs = [j for j in range(self.R) if self._fr_registered[j]]
            return bool(regs) and all(self._fr_arrived[j] for j in regs)

        def _fr_deregister(i: int) -> None:
            # idempotent; waiters re-evaluate readiness over the slots that
            # remain registered (a stale arrival flag of a deregistered
            # slot is simply ignored)
            with self._fr_cond:
                self._fr_registered[i] = False
                self._fr_cond.notify_all()

        def _fr_register(i: int) -> None:
            # re-admission: only a live thread may rejoin the barrier — a
            # party that never arrives would deadlock the whole cohort
            # (atomic with the trainer's exit path, which deregisters under
            # this same condition)
            with self._fr_cond:
                if self._alive[i] and not self._fr_registered[i]:
                    self._fr_registered[i] = True
                    self._fr_arrived[i] = False
                self._fr_cond.notify_all()

        def _fr_sync_point(i: int) -> None:
            run_round = False
            with self._fr_cond:
                if not self._fr_registered[i]:
                    return  # demoted: train on, but never block the cohort
                gen = self._fr_gen
                self._fr_arrived[i] = True
                # wait until every REGISTERED slot arrived (a crash or
                # demotion clears a registration and notifies, so the
                # barrier re-evaluates over the remaining cohort) AND no
                # elected leader is still mid-round for this generation
                while (
                    self._fr_gen == gen
                    and self._fr_registered[i]
                    and not (_fr_ready_locked() and self._fr_leader is None)
                ):
                    self._fr_cond.wait(timeout=0.05)
                    # parked at the barrier is intentional waiting, not a
                    # stall — keep the heartbeat fresh
                    _beat(f"trainer-{i}")
                    if self._fr_gen == gen and self._fr_registered[i]:
                        # a demote -> readmit cycle while we were parked
                        # cleared our arrival flag; we ARE at the sync
                        # point, so re-assert it or the barrier starves
                        self._fr_arrived[i] = True
                if self._fr_gen == gen and not self._fr_registered[i]:
                    # demoted while waiting: clear the (now ignored) arrival
                    # and leave the barrier to the remaining cohort
                    self._fr_arrived[i] = False
                    self._fr_cond.notify_all()
                    return
                if self._fr_gen == gen:
                    # every registered slot is here: this thread is elected
                    # leader and runs the round for the whole cohort. The
                    # election happens under the condition (single leader),
                    # the round does NOT (no-blocking-under-lock) — the
                    # leader flag keeps the cohort parked meanwhile.
                    self._fr_leader = i
                    run_round = True
            if not run_round:
                return
            n = 0
            try:
                n = _round_over_active()
            finally:
                # the generation MUST advance even if the round raised,
                # or the parked cohort would wait on a dead leader forever
                with self._fr_cond:
                    for j in range(self.R):
                        self._fr_arrived[j] = False
                    self._fr_leader = None
                    self._fr_gen += 1
                    self._fr_cond.notify_all()
            if n:
                _add_syncs(n)

        def _demote(slot: int, reason: str) -> None:
            """Policy demotion: active -> dead ("leave", with provenance).
            The trainer thread keeps running — its continued local iterations
            ARE the probe stream the policy watches for re-admission — but
            its replica leaves the sync set, its shared-embedding writes are
            suppressed (the trainer checks membership per iteration), and
            (fixed_rate) it leaves the barrier."""
            with self._state_lock:
                if not self.membership.active_mask()[slot]:
                    return  # crashed/left between observation and action
                self.membership.leave(slot, reason=reason)
                self._dispatch_on_leave(slot)
            if has_fr:
                _fr_deregister(slot)

        def _readmit(slot: int, reason: str) -> None:
            """Policy re-admission after probation: dead -> joining ->
            active, bootstrapped from the live cohort exactly like a fresh
            join. The trainer may finish an in-flight iteration concurrently
            and overwrite the bootstrap with its own plane — the same
            landing-into-moving-state race every shadow round tolerates by
            design; the next sync pulls it to consensus either way."""
            with self._state_lock:
                # alive is cleared under this lock on trainer exit, so a
                # finished trainer can no longer be resurrected here
                if not self._alive[slot]:
                    return
                if self.membership.status(slot) != "dead":
                    return
                self._admit_slot(slot, reason=reason)
            if has_fr:
                _fr_register(slot)

        def _policy_step() -> None:
            policy = self.policy
            if policy is None:
                return
            now = time.perf_counter()
            pcfg = policy.config
            # quality observations (DESIGN.md §14) only when the matching
            # knob is armed — the default policy stays pace-only
            loss_by = (
                {i: self._loss_ema[i] for i in range(self.R)}
                if pcfg.loss_div_frac is not None else None)
            stale_by = (
                {i: now - self._last_sync_t[i] for i in range(self.R)}
                if pcfg.staleness_max is not None else None)
            actions = policy.observe(
                now,
                self.slot_eps.eps_by_slot(),
                self.membership.active_mask(),
                list(self._alive),
                loss_by_slot=loss_by,
                staleness_by_slot=stale_by,
            )
            for a in actions:
                if a.kind == "demote":
                    _demote(a.slot, a.reason)
                else:
                    _readmit(a.slot, a.reason)

        def _quality_skew() -> float:
            # loss-EMA divergence over the live cohort: max slot EMA over
            # the cohort median — a replica whose TRAJECTORY diverges
            # pushes the controller toward shadow even at healthy pace
            active = self.membership.active_mask()
            vals = [
                self._loss_ema[i]
                for i in range(self.R)
                if active[i] and self._alive[i]
            ]
            vals = [v for v in vals if v == v and v > 0.0]
            if len(vals) < 2:
                return 0.0
            med = median_eps(vals)
            return max(vals) / med if med > 0.0 else 0.0

        def _apply_mode_switch(dec: ModeDecision, gen: Optional[int]) -> None:
            # One whole-cohort mode handoff (DESIGN.md §14), fenced by the
            # supervisor's generation token: a stalled shadow incarnation
            # that was already replaced must not run a handoff concurrently
            # with its replacement's (the supervisor's own backup tick
            # passes gen=None — it is always current).
            if (gen is not None and sup is not None
                    and sup.generation("shadow") != gen):
                return
            if dec.target == "fixed_rate":
                # shadow -> fixed_rate: one foreground catch-up sync —
                # GBA-style compensation — BEFORE arming the barrier, so
                # stale replica deltas are merged and the first synchronous
                # step starts from consensus, not from whatever the last
                # background landing happened to leave behind
                n = _round_over_active()
                if n:
                    _add_syncs(n)
            with self._fr_cond:
                if self._mode == dec.target:
                    return  # raced another switcher: handoff already done
                active = self.membership.active_mask()
                arm = dec.target == "fixed_rate"
                for j in range(self.R):
                    self._fr_arrived[j] = False
                    self._fr_registered[j] = bool(arm and self._alive[j] and active[j])
                # fixed_rate -> shadow: bumping the generation DRAINS the
                # barrier — every parked waiter re-checks, sees its
                # generation gone, and trains on without a round; the next
                # background round then syncs from the last barrier state
                # (the shadow cadence re-seeds itself from the live planes)
                self._fr_leader = None
                self._fr_gen += 1
                self._mode = dec.target
                # trainers drain their own (owner-confined) pipelines when
                # they observe the bump: staged lookups predate the handoff
                self._mode_gen += 1
                self._fr_cond.notify_all()
            self.membership.note("mode_switch", -1, f"-> {dec.target}: {dec.reason}")

        def _mode_step(gen: Optional[int]) -> None:
            ctl = self.mode_ctl
            if ctl is None:
                return
            disp = ModeController.dispersion(
                self.slot_eps.eps_by_slot(),
                self.membership.active_mask(),
                list(self._alive),
            )
            dec = ctl.observe(time.perf_counter(), disp, quality_skew=_quality_skew())
            if dec is not None:
                _apply_mode_switch(dec, gen)

        def trainer(i: int):
            try:
                _trainer_body(i)
            except BaseException as e:
                # A dying trainer thread must not die SILENTLY (the old
                # behavior: join() succeeds, partial results look complete).
                # Capture with slot provenance — run() re-raises the first
                # after join — and record the failure in the membership log
                # so the cohort (and the sync set) sees the slot leave.
                with self._state_lock:
                    self._trainer_excs.append((i, e))
                    if self.membership.status(i) != "dead":
                        self.membership.fail(i, reason=f"exception: {type(e).__name__}: {e}")
                        self._dispatch_on_leave(i)
            finally:
                # stop the slot's stager thread (idempotent) and harvest its
                # stats before the thread object dies — crash/raise exits
                # included, or the stager would outlive its trainer
                pipe = self._pipes[i]
                if pipe is not None:
                    pipe.close()
                    self._pipe_stats[i] = pipe.stats
                # under _state_lock so _readmit's alive check is race-free
                # (a finished trainer must never be resurrected into the
                # sync set); then drop out of the barrier
                with self._state_lock:
                    self._alive[i] = False
                if has_fr:
                    _fr_deregister(i)
                if sup is not None:
                    # clean exit (or captured failure): stop watching before
                    # the thread object dies, or the supervisor would read
                    # the natural end of the run as a death
                    sup.deregister(f"trainer-{i}")
                if i in initial_active:
                    with ex_lock:
                        self._initial_running -= 1

        def _trainer_body(i: int):
            n_iters = iters_per_trainer
            if i in self.fault.join_at:
                target = self.fault.join_at[i]
                while _progress() < target:
                    if (_progress() >= iters_per_trainer or self._initial_running == 0):
                        return  # cohort finished (or all crashed) before the
                        # join point — never block run() on an unreachable join
                    _beat(f"trainer-{i}")  # waiting to join is not a stall
                    time.sleep(0.001)
                with self._state_lock:
                    self._admit_slot(i)
                if has_fr:
                    _fr_register(i)
                n_iters = max(iters_per_trainer - target, 1)
            pipe: Optional[StepPipeline] = None
            if self.pipeline is not None:
                # Per-trainer step pipeline (DESIGN.md §13): the slot's own
                # batch stream is pure in (seed + slot, iteration), so the
                # stager peeks it deterministically. The hazard check is
                # SELF-read-after-write only — interleaving with the other
                # trainers' updates is the permitted Hogwild race, exactly
                # as in the serial path.
                def _prep(it2: int) -> Dict[str, Any]:
                    b = ctr.gen_batch(self.cfg, self.teacher, self.seed + i, it2, self.B)
                    sp = np.asarray(b["sparse"])
                    rows = [
                        np.unique(emb_shards._route_np(self.plan, s, sp))
                        for s in range(self.n_emb_shards)
                    ]
                    return {"rows": rows, "batch": b, "sparse": sp}

                def _stage(s: int, it2: int, prep: Dict[str, Any], ctx: Any) -> Any:
                    return self.emb.lookup_shard(s, prep["sparse"], staged=True)

                pipe = StepPipeline(
                    self.pipeline,
                    self.n_emb_shards,
                    prepare=_prep,
                    stage_fn=_stage,
                    # any membership transition (join/crash/demote) or PS
                    # fail/recover between staging and consumption drains
                    # the staged value — the lookup reruns serially
                    epoch=lambda: self.membership.epoch,
                    shard_token=self.emb.incarnation,
                    end=n_iters,
                    name=f"pipe-{i}",
                )
                self._pipes[i] = pipe
            t_start = time.perf_counter()
            sleep_s = self.fault.straggler_sleep_s.get(i, 0.0)
            sleep_until = self.fault.straggler_until.get(i)
            crash = self.fault.crash_at.get(i)
            boom = self.fault.raise_at.get(i)
            seen_mode_gen = self._mode_gen
            for it in range(n_iters):
                _beat(f"trainer-{i}")
                if pipe is not None and self._mode_gen != seen_mode_gen:
                    # a mode handoff happened since the last check: staged
                    # lookups predate it — drain on THIS thread (stage/
                    # consume/drain are owner-confined, core/pipeline.py §13)
                    seen_mode_gen = self._mode_gen
                    pipe.drain()
                if boom is not None and it >= boom:
                    # injected software fault: an actual raise, exercising the
                    # capture -> membership.fail -> re-raise-after-join path
                    raise RuntimeError(f"injected trainer fault at iteration {it}")
                if crash is not None and it >= crash:
                    with self._state_lock:
                        # a slot the policy already demoted is dead in the
                        # membership table — its host dying is a no-op there
                        if self.membership.status(i) != "dead":
                            self.membership.fail(i)
                            self._dispatch_on_leave(i)
                    if has_fr:
                        _fr_deregister(i)
                    break
                t_busy = time.perf_counter()
                if sleep_s and (sleep_until is None or it < sleep_until):
                    time.sleep(sleep_s)  # injected degradation
                with TraceAnnotation("trainer.batch"):
                    staged = prep = None
                    if pipe is not None:
                        staged, prep = pipe.consume(it)
                    batch = (
                        prep["batch"]
                        if prep is not None
                        else ctr.gen_batch(self.cfg, self.teacher, self.seed + i, it, self.B)
                    )
                with TraceAnnotation("trainer.step"):
                    w_in = self.w[i]
                    if pipe is not None:
                        # pipelined: per-shard planes staged ahead where the
                        # hazard check allowed it; hazarded/drained shards rerun
                        # serially right here — bitwise the same either way
                        sparse_np = (
                            prep["sparse"] if prep is not None else np.asarray(batch["sparse"])
                        )
                        outs = [
                            staged[s]
                            if staged is not None and staged[s] is not None
                            else self.emb.lookup_shard(s, sparse_np)
                            for s in range(self.n_emb_shards)
                        ]
                        pooled = self.emb.assemble(outs)
                        w, opt_state, loss, g_pooled = self._train_dense(
                            w_in, self.opt_states[i], pooled, batch
                        )
                        # stage batch it+1.. while THIS step's dense compute and
                        # sparse updates land (the overlap window)
                        pipe.stage(it)
                    elif self.cache is not None:
                        # hot-tier lookup through the per-PS caches (a miss that
                        # beat the prefetch horizon promotes synchronously —
                        # counted, never a stall of another trainer)
                        sparse_np = np.asarray(batch["sparse"])
                        pooled = self.emb.cached_lookup(sparse_np)
                        w, opt_state, loss, g_pooled = self._train_dense(
                            w_in, self.opt_states[i], pooled, batch
                        )
                    else:
                        # Lock-free read of the shared per-PS tables (Hogwild).
                        w, opt_state, loss, g_pooled = self._train_one(
                            w_in, self.opt_states[i], self.emb.tables(), batch
                        )
                    if self.w[i] is not w_in:
                        # a publish landed since the read: this assignment erases it
                        self.overwritten_landings[i] += 1
                    self.w[i], self.opt_states[i] = w, opt_state
                with TraceAnnotation("trainer.update"):
                    # Lock-free read-modify-write PER SHARD: concurrent writers to
                    # different PSs proceed independently; writers to the same PS
                    # can interleave and lose updates (the Hogwild property).
                    # A slot membership holds dead — policy-demoted — keeps
                    # training PRIVATE state (its iterations are the probe
                    # stream re-admission watches) but must not land its
                    # degraded gradients in the SHARED embedding state: same
                    # dead-slot no-op invariant as HogwildSim (DESIGN.md §8.2).
                    is_member = self.membership.status(i) == "active"
                    if is_member:
                        for s in range(self.n_emb_shards):
                            # routed through the PS failure domain: a healthy
                            # shard takes the plain lock-free swap; a failed one
                            # retries with backoff then DROPS the update (counted)
                            # — training never blocks on a dead PS
                            if self.cache is not None:
                                self.emb.cached_update(s, sparse_np, g_pooled, self.emb_lr)
                            else:
                                self.emb.try_update(
                                    s, self._emb_updates[s], batch["sparse"], g_pooled
                                )
                with TraceAnnotation("trainer.loss"):
                    lv = float(loss)
                losses[i].append(lv)
                # slot-owned loss EMA (quality signal, DESIGN.md §14)
                prev = self._loss_ema[i]
                self._loss_ema[i] = lv if prev != prev else 0.9 * prev + 0.1 * lv
                self.iter_count[i] = it + 1
                # busy time stops HERE, before any barrier wait: the per-slot
                # meter reads the trainer's intrinsic pace in both modes
                # (probe iterations of a demoted slot included — that is the
                # signal re-admission watches)
                self.slot_eps.tick(i, time.perf_counter() - t_busy)
                self.slot_eps.add(i, self.B)
                if is_member:
                    # headline eps/eps_window count COHORT work only: a
                    # demoted slot's probe iterations are discarded work
                    with ex_lock:
                        self.examples += self.B
                        self.eps_meter.add(self.B)
                if (has_fr and (it + 1) % self.sync_cfg.gap == 0
                        and self._mode == "fixed_rate"):
                    _fr_sync_point(i)
            trainer_wall[i] = time.perf_counter() - t_start

        def _shadow_body(gen: int):
            # One incarnation of the shadow loop. A restarted incarnation
            # resumes rounds against the LIVE membership state — safe because
            # training never blocked on the sync engine (the isolation
            # property, paper §3.3). ``gen`` is the supervisor's generation
            # token at spawn: a stalled-but-alive zombie whose replacement is
            # already running sees itself superseded and stands down.
            while not self.done.is_set():
                if sup is not None and sup.generation("shadow") != gen:
                    return  # fenced out: a replacement owns the rounds now
                r = self._shadow_rounds
                if (self.fault.sync_crash_at is not None
                        and r >= self.fault.sync_crash_at
                        and self._sync_crash_t is None):
                    self._sync_crash_t = time.perf_counter()
                    raise RuntimeError(f"injected sync-thread crash at round {r}")
                if (self.fault.sync_stall_at is not None
                        and r >= self.fault.sync_stall_at
                        and not self._sync_stalled):
                    # wedge WITHOUT beating: the supervisor must detect the
                    # stale heartbeat, fence this incarnation, and restart
                    self._sync_stalled = True
                    t_end = time.perf_counter() + self.fault.sync_stall_s
                    while (time.perf_counter() < t_end and not self.done.is_set()):
                        time.sleep(0.01)
                    continue  # generation check above retires the zombie
                _beat("shadow")
                if auto and self._mode == "fixed_rate":
                    # the barrier's elected leaders own the rounds in
                    # fixed_rate; this thread idles as the mode/policy
                    # evaluator (and keeps the prefetch + snapshot cadence
                    # below alive) until the controller switches back
                    time.sleep(0.001)
                else:
                    # One algorithm-owned background round over the live
                    # replica planes — landings interpolate into the CURRENT
                    # state while trainers keep moving (paper §3.3).
                    n = _round_over_active()
                    if n:
                        _add_syncs(n)
                    else:
                        time.sleep(0.001)
                self._shadow_rounds = r + 1
                # the shadow thread is already the background worker: the
                # cache's lookahead prefetch rides BETWEEN the sync rounds
                # (stage promotions/evictions while trainers compute), and
                # PS snapshots ride its cadence (O(1) reference grabs;
                # O(hot_rows) merged() drains in cached mode)
                with TraceAnnotation("shadow.upkeep"):
                    _prefetch_step()
                    if self._shadow_rounds % self.ps_snapshot_every == 0:
                        self.emb.snapshot_all()
                    # the controllers ride the shadow cadence: membership AND
                    # the cohort mode are re-evaluated every background round,
                    # training never blocks on either
                    _policy_step()
                    _mode_step(gen)
                if self.sync_sleep_s:
                    time.sleep(self.sync_sleep_s)

        def shadow(gen: int = 0):
            try:
                _shadow_body(gen)
            except BaseException as e:
                # die quietly: the supervisor's death detection (and the
                # restart it triggers) IS the recovery path; the exception is
                # kept for the output record
                self._sync_excs.append(e)

        def _restart_shadow() -> threading.Thread:
            # Called by the supervisor (outside its lock) after backoff. The
            # generation token was already bumped, fencing any stalled
            # zombie; record where sync_count stood so the bench can assert
            # post-restart progress.
            with self._sync_lock:
                self._sync_count_at_restart.append(self.sync_count)
            gen = sup.generation("shadow")
            self.membership.note(
                "sync_restart", -1,
                f"shadow thread restarted (attempt "
                f"{len(self._sync_count_at_restart)}, generation {gen})")
            t = threading.Thread(target=shadow, args=(gen,), daemon=True)
            t.start()
            return t

        def _sync_give_up(name: str) -> None:
            # Degradation ladder, last rung (DESIGN.md §10.2): training keeps
            # running locally (isolation means nothing breaks), the event log
            # records the degradation with provenance, and run() forces one
            # final FOREGROUND sync at shutdown so the run still converges.
            self._sync_degraded = True
            self.membership.note(
                "degraded", -1,
                "sync engine degraded: restart budget exhausted; training "
                "continues locally, final foreground sync at shutdown")

        def _supervision_tick() -> None:
            # PS chaos injection + timed recovery ride the supervisor's
            # watch loop (its clock domain is the policy's: perf_counter).
            if fr_static:
                # no shadow thread to ride: the lookahead prefetch and the
                # background PS snapshots take the watch-loop cadence instead
                self._tick_count += 1
                _prefetch_step()
                if self._tick_count % 10 == 0:
                    self.emb.snapshot_all()
            for s, at in self.fault.ps_fail_at.items():
                if s not in self._ps_injected and _progress() >= at:
                    self._ps_injected.add(s)
                    self.emb.fail_shard(s, reason=f"injected PS failure at iteration {at}")
                    self.membership.note(
                        "ps_fail", -1,
                        f"embedding shard {s} down: live state lost, serving "
                        f"snapshot reads, dropping writes after retry")
            now = time.perf_counter()
            for s in list(self.emb.failed_at):
                t_fail = self.emb.failed_at.get(s)
                if (t_fail is not None and now - t_fail >= self.fault.ps_recover_after_s):
                    self.emb.recover_shard(
                        s, reason=f"rehydrated from snapshot after " f"{now - t_fail:.2f}s down"
                    )
                    self.membership.note(
                        "ps_recover", -1, f"embedding shard {s} rejoined the routing plan"
                    )
            # backup policy/mode clock: membership AND mode decisions keep
            # flowing even while the thread that normally evaluates them
            # (the shadow thread) is the thing being restarted (gen=None:
            # the supervisor's own tick is always the current incarnation)
            _policy_step()
            _mode_step(None)

        def monitor():
            # fixed_rate has no shadow thread, so the controller gets its own
            # (otherwise a demotion decision could only happen at a barrier —
            # exactly the place the straggler is blocking everyone)
            while not self.done.is_set():
                _beat("monitor")
                _policy_step()
                time.sleep(0.02)

        sup = (
            Supervisor(self.supervisor_config, tick=_supervision_tick) if self.supervise else None
        )
        self.supervisor = sup
        threads = [threading.Thread(target=trainer, args=(i,)) for i in range(self.R)]
        shadow_t = None if fr_static else threading.Thread(target=shadow, args=(0,), daemon=True)
        monitor_t = (
            threading.Thread(target=monitor, daemon=True)
            if fr_static and self.policy is not None
            else None
        )
        # register BEFORE starting anything: a fast-finishing thread must
        # never race its own registration (it deregisters itself on exit)
        if sup is not None:
            for i, t in enumerate(threads):
                sup.register(f"trainer-{i}", t)  # watch-only
            if shadow_t is not None:
                sup.register("shadow", shadow_t, restart=_restart_shadow, on_give_up=_sync_give_up)
            if monitor_t is not None:
                sup.register("monitor", monitor_t)
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        if shadow_t is not None:
            shadow_t.start()
        if monitor_t is not None:
            monitor_t.start()
        if sup is not None:
            sup.start()
        for t in threads:
            t.join()
        self.done.set()
        sync_restarts = 0
        supervisor_errors: Dict[str, Any] = {"count": 0, "last": None}
        if sup is not None:
            sync_restarts = sup.restarts("shadow")
            cur = sup.thread("shadow")
            if cur is not None:
                shadow_t = cur  # join the CURRENT incarnation, not gen 0
            # clean shutdown: done is set and the loops exit on their own —
            # stop watching first, or the supervisor would read those clean
            # exits as deaths and spin up doomed replacements
            sup.deregister("shadow")
            sup.deregister("monitor")
            sup.stop()
            supervisor_errors = sup.swallowed_errors()
        if shadow_t is not None:
            shadow_t.join(timeout=5.0)
            if shadow_t.is_alive():
                warnings.warn(
                    "shadow thread failed to exit within 5s at shutdown "
                    "(sync engine wedged?); proceeding — the returned state "
                    "may race one final background round", RuntimeWarning)
        if monitor_t is not None:
            monitor_t.join(timeout=5.0)
            if monitor_t.is_alive():
                warnings.warn(
                    "monitor thread failed to exit within 5s at " "shutdown", RuntimeWarning
                )
        # rehydrate any still-down PS so the returned packed state is the
        # best surviving copy and a subsequent run starts healthy
        for s in self.emb.down_shards():
            self.emb.recover_shard(s, reason="shutdown rehydrate")
            self.membership.note("ps_recover", -1, f"embedding shard {s} rehydrated at shutdown")
        final_fg_sync = False
        if self._sync_degraded and self.membership.active_ids().size > 0:
            # degradation ladder's last rung: one FOREGROUND sync so the run
            # still converges to a synchronized model
            n = _round_over_active()
            if n:
                _add_syncs(n)
                final_fg_sync = True
        wall = time.perf_counter() - t0
        if self._trainer_excs:
            i, e = self._trainer_excs[0]
            others = len(self._trainer_excs) - 1
            raise RuntimeError(
                f"trainer thread (slot {i}) died with "
                f"{type(e).__name__}: {e}"
                + (f"; {others} more trainer exception(s) captured" if others else "")
            ) from e
        total_iters = sum(self.iter_count)
        if self.engine == "flat":
            w_out = [self.flat.unpack(p) for p in self.w]
        else:
            w_out = self.w
        return {
            "eps": self.examples / wall,
            # rate over the trailing window — after a crash this is the
            # SURVIVORS' pace, not an average diluted by the dead trainer
            "eps_window": self.eps_meter.eps,
            "wall_s": wall,
            "train_loss": [float(np.mean(l[-50:])) if l else float("nan") for l in losses],
            "sync_count": self.sync_count,
            "overwritten_steps": list(self.overwritten_steps),
            "overwritten_landings": list(self.overwritten_landings),
            "avg_sync_gap": total_iters / max(self.sync_count, 1),
            "per_trainer_eps": [
                self.B * self.iter_count[i] / trainer_wall[i]
                if trainer_wall[i] > 0 and self.iter_count[i] > 0 else 0.0
                for i in range(self.R)],
            # intrinsic (busy-clock) pace per slot: what the straggler
            # controller saw; barrier waits excluded
            "per_trainer_eps_busy": [
                self.B * self.iter_count[i] / self.slot_eps.busy(i)
                if self.slot_eps.busy(i) > 0 else 0.0
                for i in range(self.R)],
            "iter_count": list(self.iter_count),
            "membership_events": list(self.membership.events),
            "policy_transitions": (
                list(self.policy.transitions) if self.policy is not None else []
            ),
            # failure-domain telemetry (DESIGN.md §10)
            "supervision_events": (list(sup.events) if sup is not None else []),
            "shard_events": list(self.emb.events),
            "dropped_updates": list(self.emb.dropped_updates),
            "stale_lookups": list(self.emb.stale_lookups),
            # tiered-cache telemetry (DESIGN.md §11; {} when cache is off)
            "cache_stats": (self.emb.cache_stats() if self.cache is not None else {}),
            # step-pipeline telemetry (DESIGN.md §13; {} when pipelining is
            # off): per-trainer stats merged post-join
            "pipeline_stats": (self._merged_pipe_stats() if self.pipeline is not None else {}),
            # mode-switching telemetry (DESIGN.md §14): the final mode and
            # the controller's decision log (empty when auto-mode is off)
            "mode": self._mode,
            "mode_transitions": (
                list(self.mode_ctl.transitions) if self.mode_ctl is not None else []
            ),
            "sync_rounds": self._shadow_rounds,
            "sync_restarts": sync_restarts,
            # exceptions the supervisor's watch loop caught and survived
            "supervisor_errors": supervisor_errors,
            "sync_count_at_restart": list(self._sync_count_at_restart),
            "sync_degraded": self._sync_degraded,
            "final_foreground_sync": final_fg_sync,
            "t_start": t0,
            "w": w_out,
            # Engine-independent packed view of the per-PS states.
            "emb_state": self.emb.to_packed(),
        }
