"""Tiered frequency-aware embedding cache with lookahead prefetch.

Production CTR tables run to hundreds of GB while an accelerator box holds
tens; the classical fix is a software-managed cache in front of each
embedding PS (DESIGN.md §11). ``CachedStore`` fronts ONE contiguous
table+accumulator pair — a PS shard (``embeddings/shards.py``) or
``HogwildSim``'s packed collection — with two tiers:

* a **device-resident hot tier**: a fixed-budget contiguous pair of H rows,
  lane-dense like the table it fronts (``kernels/layout.py``), so the
  existing fused ``embedding_bag`` / ``sparse_adagrad`` kernels run on it
  UNCHANGED (only row ids are remapped to hot slots);
* a **host-resident cold store**: plain numpy arrays holding the canonical
  values of every non-resident row (entries for hot rows are stale until
  eviction writes them back).

A **routing table** maps every global row id to (tier, slot). Placement
state is published atomically: ``(hot arrays, routing)`` travel together in
one immutable ``TierState`` swapped under a lock, and because jnp arrays
are immutable a reader that grabbed a state keeps a self-consistent view no
matter what migrations land after — the same wholesale-swap discipline the
PS shards already use (DESIGN.md §10.3).

The cache is a **pure placement optimization**: a lookup/update routed
through the hot tier is bitwise-identical to the same kernel launch on the
full table (same row values, same per-row occurrence order — the kernels'
duplicate-accumulate sorts are stable and rows are independent), and
``merged()`` reconstructs the canonical table exactly, so checkpoints, the
sync oracle, and every consumer of the packed view are cache-invisible.
``tests/test_cache.py`` pins both properties.

``LookaheadPrefetcher`` is the BagPipe move (PAPERS.md): the training
stream is a pure function of the iteration counter, so the next K queued
batches can be *peeked* — the shadow thread (already the background worker,
PRs 1-6) computes their miss sets and stages cold->hot promotions plus
frequency-aware (decayed-LFU) evictions as batched row copies between
syncs. A cold row that beats the prefetch horizon falls back to a
synchronous host gather inside ``lookup`` — counted (``stall_lookups``),
never fatal, and never a blocked *other* trainer.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.kernels import layout
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op
from repro.models.layers import Params

HOT, COLD = 0, 1


@dataclass(frozen=True)
class CacheConfig:
    """Two-tier cache policy. Exactly one of ``hot_rows`` (absolute row
    budget per store) / ``hot_frac`` (fraction of the store's rows) must be
    set. ``lookahead`` is the number of queued batches the prefetcher
    peeks (0 = no prefetch: every cold row is a counted synchronous
    stall — still exact). ``decay`` ages the LFU frequency counters once
    per prefetch round so yesterday's hot rows can leave the device."""

    hot_rows: Optional[int] = None
    hot_frac: Optional[float] = None
    lookahead: int = 2
    decay: float = 0.8
    update_retries: int = 3  # optimistic-swap retries when a migration races

    def validate(self) -> "CacheConfig":
        if (self.hot_rows is None) == (self.hot_frac is None):
            raise ValueError(
                f"exactly one of hot_rows/hot_frac must be set, got "
                f"hot_rows={self.hot_rows}, hot_frac={self.hot_frac}")
        if self.hot_rows is not None and self.hot_rows < 1:
            raise ValueError(f"hot_rows must be >= 1, got {self.hot_rows}")
        if self.hot_frac is not None and not 0.0 < self.hot_frac <= 1.0:
            raise ValueError(f"hot_frac must be in (0, 1], got {self.hot_frac}")
        if self.lookahead < 0:
            raise ValueError(f"lookahead must be >= 0, got {self.lookahead}")
        if not 0.0 < self.decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {self.decay}")
        if self.update_retries < 0:
            raise ValueError(f"update_retries must be >= 0, got {self.update_retries}")
        return self

    def resolve_hot_rows(self, n_rows: int) -> int:
        h = (
            self.hot_rows
            if self.hot_rows is not None
            else max(1, int(round(self.hot_frac * n_rows)))
        )
        return min(h, n_rows)

    def effective_lookahead(self, pipeline_depth: int = 1) -> int:
        """Prefetch horizon composed with the step pipeline (DESIGN.md §13):
        the prefetcher must peek at least as far ahead as lookups are
        staged, or every staged lookup beyond the horizon pays exactly the
        synchronous-promotion stall the pipeline was meant to hide.
        ``lookahead=0`` stays 0 — prefetch explicitly off is respected
        (staged cold rows become counted stalls, still exact)."""
        if self.lookahead == 0:
            return 0
        return max(self.lookahead, pipeline_depth)


@dataclass(frozen=True)
class Routing:
    """Immutable row -> (tier, slot) map, the atomic publish unit. ``slot``
    holds the hot-tier slot of each row (-1 = cold); ``hot_row`` is the
    inverse (-1 = free slot). ``version`` bumps only on MIGRATION — trainer
    updates swap hot arrays without touching routing, so Hogwild lost
    updates between trainers stay possible (the preserved property) while
    an update computed against a superseded placement is detected and
    retried instead of corrupting the tier."""

    slot: np.ndarray  # (n_rows,) int32, -1 = cold
    hot_row: np.ndarray  # (H,) int32, -1 = free
    version: int

    def tier(self, row: int) -> int:
        return HOT if self.slot[row] >= 0 else COLD


@dataclass(frozen=True)
class TierState:
    """What a reader needs for one consistent lookup/update: the hot arrays
    and the routing that indexes them, published together."""

    hot: Params  # {"table", "acc"}: H slots, lane-dense device arrays
    routing: Routing


@dataclass
class CacheStats:
    lookups: int = 0
    hit_rows: int = 0  # unique rows already resident at lookup
    miss_rows: int = 0  # unique rows promoted synchronously (stall path)
    stall_lookups: int = 0  # lookups that paid >= 1 synchronous promotion
    prefetch_rows: int = 0  # rows promoted ahead of need by the prefetcher
    evict_rows: int = 0
    writeback_rows: int = 0  # evictions that drained table+acc to the cold store
    bytes_h2d: int = 0
    bytes_d2h: int = 0
    update_conflicts: int = 0  # optimistic update swaps retried after a migration
    dropped_updates: int = 0  # retries exhausted (bounded, counted — never a stall)
    staged_lookups: int = 0  # lookups dispatched ahead of need by the step pipeline

    def as_dict(self) -> Dict[str, int]:
        return dict(self.__dict__)


@dataclass
class _Plan:
    """A staged migration: decided from a snapshot WITHOUT the lock, applied
    under it (bounded row copies + one routing publish)."""

    promote: np.ndarray  # global rows to bring hot
    dst: np.ndarray  # hot slots they land in
    evict_rows: np.ndarray  # global rows leaving the hot tier (writeback)
    evict_slots: np.ndarray  # their slots (a prefix of dst)
    free_slots: np.ndarray = field(default_factory=lambda: np.empty(0, np.int32))


class CachedStore:
    """Two-tier store over one contiguous table. All row ids are LOCAL to
    this store (the caller routes shard-local ids; ``HogwildSim`` passes
    packed global ids). ``state`` is lane-dense with rows of ``dim``
    (default: its width, one row per lane row); every lane-row slot is a
    row here, so ``merged()`` returns the input's layout. Thread model:
    ``lookup``/``update`` are called by trainer threads, ``prefetch`` by the
    background worker; every placement change happens under ``_lock`` and
    lands as a fresh ``TierState``."""

    def __init__(
        self, state: Params, cfg: CacheConfig, *, dim: Optional[int] = None, eps: float = 1e-8
    ):
        self.cfg = cfg.validate()
        self.width = state["table"].shape[-1]
        self.dim = dim or self.width
        self.eps = eps
        # Host-resident cold store, one row per row (the lane-dense state's
        # row view): canonical for cold rows; hot rows' entries go stale
        # until eviction writes them back.
        # guarded-by: _lock — migrations write back evicted rows under _lock;
        # merged() copies under it too, so readers never see a torn writeback
        self.cold: Dict[str, np.ndarray] = {
            k: np.array(layout.row_view(np.asarray(state[k]), self.dim), dtype=np.float32)
            for k in state
        }
        self.n_rows, self._row_w = self.cold["table"].shape
        H = cfg.resolve_hot_rows(self.n_rows)
        self.hot_budget = H
        # Initial placement: rows [0, H) hot (the data's skew concentrates
        # on low ids; the prefetcher re-derives placement within a round).
        slot = np.full(self.n_rows, -1, np.int32)
        slot[:H] = np.arange(H, dtype=np.int32)
        hot_row = np.full(H, -1, np.int32)
        hot_row[:min(H, self.n_rows)] = np.arange(min(H, self.n_rows), dtype=np.int32)
        hot = {k: jnp.asarray(layout.pack_rows(self.cold[k][:H], self.width)) for k in self.cold}
        # swap-published; guarded-by-writes: _lock — every placement change
        # lands as a fresh immutable TierState; trainers read lock-free
        self._st = TierState(hot, Routing(slot, hot_row, 0))
        # hogwild-race: ok — LFU ranking signal; lost increments shift ranks only
        self.freq = np.zeros(self.n_rows, np.float64)
        # swap-published; hogwild-race: ok — prefetcher rebinds a fresh mask
        self._pinned = np.zeros(self.n_rows, bool)  # prefetch-horizon rows
        self.stats = CacheStats()  # hogwild-race: ok — diagnostic counters
        self._lock = threading.Lock()
        self._row_bytes = 4 * self.dim * len(self.cold)  # f32 table + acc

    # -- reads ---------------------------------------------------------------
    @property
    def state(self) -> TierState:
        return self._st

    def resident(self, rows: np.ndarray) -> np.ndarray:
        """Mask of ``rows`` currently in the hot tier."""
        return self._st.routing.slot[rows] >= 0

    def merged(self) -> Params:
        """The canonical full state, in the input's lane-dense layout — cold
        store overlaid with the live hot tier. Bitwise-exact: hot rows come
        straight off the device, cold rows were written back exactly on
        eviction. This is what
        snapshots, checkpoints, and ``to_packed`` consume: the cache is
        invisible above this line.

        The cold copy and the TierState capture happen atomically under the
        lock (migrations mutate ``cold`` under it); the device gathers run
        OUTSIDE it (no-blocking-under-lock, DESIGN.md §12) against the
        captured immutable TierState — the result is an exact snapshot as
        of capture time."""
        with self._lock:
            st = self._st
            out = {k: self.cold[k].copy() for k in self.cold}
        occ = st.routing.hot_row >= 0
        rows = st.routing.hot_row[occ]
        slots = jnp.asarray(np.flatnonzero(occ))
        for k in out:
            out[k][rows] = np.asarray(layout.take_rows(st.hot[k], slots, self._row_w))
        return {k: jnp.asarray(layout.pack_rows(v, self.width)) for k, v in out.items()}

    def check_invariants(self) -> None:
        """Every row routed to exactly one (tier, slot); slot<->row maps are
        mutually inverse; no slot holds two rows (tests/test_cache.py)."""
        st = self._st
        slot, hot_row = st.routing.slot, st.routing.hot_row
        hot_rows = np.flatnonzero(slot >= 0)
        if len(np.unique(slot[hot_rows])) != len(hot_rows):
            raise AssertionError("two rows share a hot slot")
        if not np.array_equal(hot_row[slot[hot_rows]], hot_rows):
            raise AssertionError("slot/hot_row maps disagree")
        occupied = np.flatnonzero(hot_row >= 0)
        if not np.array_equal(np.sort(slot[hot_row[occupied]]), np.sort(occupied)):
            raise AssertionError("occupied slot not routed back")
        if len(hot_rows) != len(occupied):
            raise AssertionError("tier population mismatch")

    # -- hot path ------------------------------------------------------------
    def lookup(self, idx: np.ndarray, *, staged: bool = False) -> jnp.ndarray:
        """Sum-pooled lookup, idx (..., m) local row ids -> (..., d). Runs
        the unchanged fused kernel over the hot tier with ids remapped to
        slots; any cold row is promoted synchronously first (the counted
        stall path — a miss that beat the prefetch horizon). ``staged``
        marks a lookup the step pipeline (core/pipeline.py) dispatched
        ahead of consumption — same semantics, separately counted."""
        idx = np.asarray(idx)
        rows, counts = np.unique(idx, return_counts=True)
        self.freq[rows] += counts
        st = self._st
        missing = rows[st.routing.slot[rows] < 0]
        self.stats.lookups += 1
        if staged:
            self.stats.staged_lookups += 1
        self.stats.hit_rows += len(rows) - len(missing)
        if len(missing):
            self.stats.miss_rows += len(missing)
            self.stats.stall_lookups += 1
        # loop, not a single promote: a concurrent prefetch can evict a row
        # that WAS resident at capture time — residency must be re-checked
        # against the exact state the kernel will read
        while len(missing):
            st = self._promote_sync(missing, keep=rows)
            missing = rows[st.routing.slot[rows] < 0]
        slots = st.routing.slot[idx]
        return embedding_bag_op(st.hot["table"], jnp.asarray(slots), dim=self.dim)

    def update(self, idx: np.ndarray, g_pooled: jnp.ndarray, lr: float) -> bool:
        """Fused row-sparse Adagrad on the hot tier: idx (..., m) local row
        ids, g_pooled (..., d). The batch's rows are already resident
        (lookup ran this batch; a direct call promotes first). The new hot
        arrays land via optimistic swap: publication fails only if a
        MIGRATION republished routing mid-kernel, in which case the update
        recomputes against the new placement (bounded retries, then a
        counted drop — trainer-vs-trainer interleaving stays lock-free and
        lossy, the preserved Hogwild property)."""
        idx = np.asarray(idx)
        rows = np.unique(idx)
        for _ in range(self.cfg.update_retries + 1):
            st = self._st
            while True:  # see lookup: re-check against the state we'll use
                missing = rows[st.routing.slot[rows] < 0]
                if not len(missing):
                    break
                st = self._promote_sync(missing, keep=rows)
            slots = st.routing.slot[idx]
            table, acc = sparse_adagrad_op(
                st.hot["table"], st.hot["acc"], jnp.asarray(slots), g_pooled,
                lr=lr, eps=self.eps, dim=self.dim)
            with self._lock:
                if self._st.routing is st.routing:
                    self._st = TierState({"table": table, "acc": acc}, st.routing)
                    return True
            self.stats.update_conflicts += 1
        self.stats.dropped_updates += 1
        return False

    # -- migration -----------------------------------------------------------
    def _plan_migration(
        self, need: np.ndarray, keep: np.ndarray, routing: Routing
    ) -> Optional[_Plan]:
        """Stage promotions for ``need`` (cold rows, deduped) evicting the
        lowest-frequency unpinned hot rows not in ``keep``. Pure decision —
        no copies, no lock."""
        need = need[routing.slot[need] < 0]
        if not len(need):
            return None
        free = np.flatnonzero(routing.hot_row < 0).astype(np.int32)
        n_evict = max(0, len(need) - len(free))
        evict_rows = np.empty(0, np.int64)
        if n_evict:
            protect = np.zeros(self.n_rows, bool)
            protect[keep] = True
            protect[need] = True
            cand = routing.hot_row[routing.hot_row >= 0]
            cand = cand[~protect[cand]]
            if len(cand) < n_evict:
                raise ValueError(
                    f"hot tier too small: need {len(need)} promotions but "
                    f"only {len(cand)} evictable of {self.hot_budget} slots "
                    f"— raise hot_rows above the per-batch working set")
            # frequency-aware (decayed-LFU) victims; prefer rows the
            # prefetch horizon has NOT pinned. lexsort is stable, so ties
            # break by row id — deterministic for the sim.
            order = np.lexsort((cand, self.freq[cand], self._pinned[cand].astype(np.int8)))
            evict_rows = cand[order[:n_evict]]
        evict_slots = routing.slot[evict_rows].astype(np.int32)
        dst = np.concatenate([free[:len(need)], evict_slots])[:len(need)]
        return _Plan(need, dst.astype(np.int32), evict_rows, evict_slots, free[:len(need)])

    # holds-lock: _lock; lock-blocking: ok — bounded row scatters; doing them
    # optimistically would break eviction-writeback-before-slot-reuse exactness
    def _apply_migration(self, plan: _Plan) -> TierState:
        """Apply a staged migration under the lock against the CURRENT state
        (which may have advanced past the one the plan was computed from —
        slots/rows are re-validated implicitly by planning from routing,
        which only this method changes). Evicted rows drain table+acc to
        the cold store BEFORE their slot is reused, so no pending Adagrad
        update is ever dropped; then promotions land as one batched
        device scatter per array and the new routing publishes atomically."""
        st = self._st
        hot = dict(st.hot)
        if len(plan.evict_rows):
            ev = jnp.asarray(plan.evict_slots)
            for k in hot:
                self.cold[k][plan.evict_rows] = np.asarray(
                    layout.take_rows(hot[k], ev, self._row_w)
                )
            self.stats.evict_rows += len(plan.evict_rows)
            self.stats.writeback_rows += len(plan.evict_rows)
            self.stats.bytes_d2h += len(plan.evict_rows) * self._row_bytes
        dst = jnp.asarray(plan.dst)
        for k in hot:
            hot[k] = layout.set_rows(
                hot[k], dst, jnp.asarray(self.cold[k][plan.promote]), self._row_w
            )
        self.stats.bytes_h2d += len(plan.promote) * self._row_bytes
        slot = st.routing.slot.copy()
        hot_row = st.routing.hot_row.copy()
        slot[plan.evict_rows] = -1
        slot[plan.promote] = plan.dst
        hot_row[plan.dst] = plan.promote
        new = TierState(hot, Routing(slot, hot_row, st.routing.version + 1))
        self._st = new
        return new

    def _promote_sync(self, missing: np.ndarray, keep: np.ndarray) -> TierState:
        """The stall path: a cold row reached ``lookup``/``update`` before
        the prefetcher did. Promote synchronously (bounded host gather +
        one device scatter) so the fused kernel still runs over a single
        contiguous tier — exactness is never traded for speed."""
        with self._lock:
            plan = self._plan_migration(np.asarray(missing), keep, self._st.routing)
            return self._apply_migration(plan) if plan else self._st

    def prefetch(self, horizon: List[np.ndarray]) -> Dict[str, int]:
        """One background prefetch round over the peeked batches' row sets
        (earliest first). Ages the LFU counters, pins the horizon against
        eviction, promotes the misses the hot budget can take, and evicts
        cold-bound victims — all between syncs, off the training path."""
        self.freq *= self.cfg.decay
        want: List[np.ndarray] = []
        seen = np.zeros(self.n_rows, bool)
        budget = self.hot_budget
        for rows in horizon:
            if rows is None or not len(rows):
                continue
            rows = np.unique(rows)
            fresh = rows[~seen[rows]]
            take = fresh[:max(0, budget - int(seen.sum()))]
            seen[take] = True
            want.append(take)
        self._pinned = seen
        if not want:
            return {"promoted": 0}
        need = np.concatenate(want)
        with self._lock:
            routing = self._st.routing
            plan = self._plan_migration(need, need, routing)
            if plan is None:
                return {"promoted": 0}
            self._apply_migration(plan)
            self.stats.prefetch_rows += len(plan.promote)
            return {"promoted": int(len(plan.promote))}


class LookaheadPrefetcher:
    """BagPipe-style lookahead for one store: ``feed(j)`` returns the local
    row ids of the j-th QUEUED batch (0 = the next batch to train, None =
    end of stream). ``step()`` peeks the next ``cfg.lookahead`` batches and
    runs one prefetch round — the shadow thread calls it between syncs; the
    deterministic sim calls it at iteration boundaries."""

    def __init__(
        self,
        store: CachedStore,
        feed: Callable[[int], Optional[np.ndarray]],
        lookahead: Optional[int] = None,
    ):
        self.store = store
        self.feed = feed
        self.lookahead = (store.cfg.lookahead if lookahead is None else lookahead)

    def step(self) -> Dict[str, int]:
        if self.lookahead == 0:
            return {"promoted": 0}
        horizon = [self.feed(j) for j in range(self.lookahead)]
        return self.store.prefetch([r for r in horizon if r is not None])
