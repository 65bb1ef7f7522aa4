"""Plan-sharded embedding engine: the paper's embedding parameter servers.

``plan_shards`` runs the greedy LPT planner (``table.bin_pack`` over
``table.lookup_costs``) to assign whole categorical tables to ``n_shards``
embedding PSs — the paper's load balancing (§3.1). ``ShardPlan`` freezes that
assignment plus the derived routing arrays; the packed collection of
``total_rows`` rows splits into one contiguous store of ``shard_rows`` rows
per PS, each with its co-located Adagrad accumulator, each lane-dense
(``kernels/layout.py``) like the collection it came from.

Lookups route by the plan: each shard answers one fused lookup+pool kernel
launch over its own features, and the pooled planes reassemble in feature
order. Backward routes the same way through the fused sparse-Adagrad scatter
kernel — one launch per shard, touching only that PS's rows.

``EmbeddingShards`` is the stateful host-side holder ``ThreadedShadowRunner``
uses: ``states[s]`` are genuinely independent per-PS Hogwild states, so
concurrent trainers writing to different PSs no longer serialize through one
jitted scatter over a single packed array (DESIGN.md §7).

Each PS is also a real *failure domain* (DESIGN.md §10.3): per-shard health
state, background snapshots, and fail/recover transitions. Because every
update replaces ``states[s]`` wholesale with fresh immutable arrays, a
snapshot is an O(1) reference grab — the shadow thread (already the
background worker) snapshots every few rounds for free. When a shard fails
(``fail_shard``, injected via ``FaultSpec.ps_fail_at``), its live state is
lost; lookups transparently fall back to the latest snapshot (a bounded-
staleness read — training on surviving shards never blocks) and updates
routed at it retry with backoff under ``ShardRetryPolicy`` and are then
*dropped* (counted — the measured staleness cost). ``recover_shard``
rehydrates the shard from its snapshot and it rejoins the routing plan.

Tiered cache (DESIGN.md §11): pass a ``CacheConfig`` and each PS fronts its
contiguous table with a ``embeddings/cache.py`` two-tier store — a device-
resident hot-row tier the unchanged fused kernels run on, a host-resident
cold store, and an atomically published routing table. Lookups go through
``cached_lookup`` (per-shard hot-tier kernel launches, bitwise-identical to
the full-table path), updates through ``cached_update`` (same health/retry/
drop ladder as ``try_update``). The cache is invisible above the canonical
view: ``snapshot_all`` and ``to_packed`` merge hot+cold back into the full
table, so PS failure, recovery, checkpoints, and the sync oracle see
exactly what they saw before — at the price that a snapshot drains the hot
tier (O(hot_rows) device reads) instead of being an O(1) reference grab."""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.embeddings.cache import CacheConfig, CachedStore
from repro.embeddings.table import (
    TableSpec,
    bin_pack,
    init_tables,
    lookup_costs,
)
from repro.kernels import layout
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op
from repro.models.layers import Params


@dataclass(frozen=True)
class ShardPlan:
    """A frozen table->PS assignment plus the derived routing arrays."""
    spec: TableSpec
    bins: Tuple[Tuple[int, ...], ...]  # feature/table ids per shard (LPT order)
    feature_shard: Tuple[int, ...]  # (F,) shard owning each feature
    feature_local_offset: Tuple[int, ...]  # (F,) row offset inside its shard
    shard_rows: Tuple[int, ...]  # packed rows per shard

    @property
    def n_shards(self) -> int:
        return len(self.bins)

    @property
    def feature_order(self) -> Tuple[int, ...]:
        """Features in shard-concatenation order (bins flattened)."""
        return tuple(f for feats in self.bins for f in feats)


def plan_shards(spec: TableSpec, n_shards: int, batch_size: int) -> ShardPlan:
    """LPT bin-pack the tables' profiled lookup costs across the PSs."""
    n_shards = max(1, min(n_shards, len(spec.sizes)))
    bins = tuple(tuple(b) for b in bin_pack(lookup_costs(spec, batch_size), n_shards))
    feature_shard = [0] * len(spec.sizes)
    feature_local_offset = [0] * len(spec.sizes)
    shard_rows = []
    for s, feats in enumerate(bins):
        off = 0
        for f in feats:
            feature_shard[f] = s
            feature_local_offset[f] = off
            off += spec.sizes[f]
        shard_rows.append(off)
    return ShardPlan(
        spec, bins, tuple(feature_shard), tuple(feature_local_offset), tuple(shard_rows)
    )


@functools.partial(jax.jit, static_argnums=0)
def shard_states(plan: ShardPlan, state: Params) -> List[Params]:
    """Split a lane-dense {"table", "acc"} collection into lane-dense
    per-shard states (each shard concatenates its tables' global row ranges
    in bin order). When every table starts and ends on a lane-row boundary
    (``dlrm-ctr``'s even sizes at d=64) the split copies whole lane rows;
    otherwise it goes through the logical rows, in one program."""
    d, goff, sizes = plan.spec.dim, plan.spec.offsets, plan.spec.sizes
    p = layout.rows_per_lane_row(state["table"].shape[-1], d)
    ranges = [[(int(goff[f]), int(goff[f]) + sizes[f]) for f in feats] for feats in plan.bins]
    if all(int(o) % p == 0 and n % p == 0 for o, n in zip(goff, sizes)):
        return [
            {k: jnp.concatenate([v[a // p : b // p] for a, b in parts]) for k, v in state.items()}
            for parts in ranges
        ]
    rows = {k: layout.unpack(v, plan.spec.total_rows, d) for k, v in state.items()}
    return [
        {k: layout.pack(jnp.concatenate([v[a:b] for a, b in parts])) for k, v in rows.items()}
        for parts in ranges
    ]


def packed_state(plan: ShardPlan, states: List[Params]) -> Params:
    """Inverse of ``shard_states``, in the logical view: the global
    (total_rows, dim) {"table", "acc"} as host numpy arrays — what
    checkpoints and run outputs carry."""
    d = plan.spec.dim
    logical = [
        {k: layout.unpack(np.asarray(v), plan.shard_rows[s], d) for k, v in st.items()}
        for s, st in enumerate(states)
    ]
    parts = {k: [None] * len(plan.spec.sizes) for k in states[0]}
    for f in range(len(plan.spec.sizes)):
        s, loff = plan.feature_shard[f], plan.feature_local_offset[f]
        for k in parts:
            parts[k][f] = logical[s][k][loff:loff + plan.spec.sizes[f]]
    return {k: np.concatenate(v) for k, v in parts.items()}


def _route(plan: ShardPlan, s: int, idx: jnp.ndarray) -> jnp.ndarray:
    """Shard s's slice of a (B, F, m) index batch, in LOCAL row ids."""
    feats = plan.bins[s]
    loc = jnp.take(idx, jnp.asarray(feats), axis=1)
    offs = jnp.asarray([plan.feature_local_offset[f] for f in feats], jnp.int32)
    return loc + offs[None, :, None]


def _route_np(plan: ShardPlan, s: int, idx: np.ndarray) -> np.ndarray:
    """Host-side ``_route``: the cache layer and the prefetcher index numpy
    routing tables, so the remap must not round-trip through the device."""
    feats = np.asarray(plan.bins[s])
    offs = np.asarray([plan.feature_local_offset[f] for f in plan.bins[s]], np.int32)
    return np.take(idx, feats, axis=1) + offs[None, :, None]


def shard_lookup(
    plan: ShardPlan,
    tables: Tuple[jnp.ndarray, ...],
    idx: jnp.ndarray,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Plan-routed sum-pooled lookup. idx: (B, F, m) LOCAL-per-feature ids
    (as produced by the data pipeline) -> (B, F, dim). One fused kernel
    launch per shard."""
    outs = [
        embedding_bag_op(
            tables[s],
            _route(plan, s, idx),
            dim=plan.spec.dim,
            use_pallas=use_pallas,
            interpret=interpret,
        )
        for s in range(plan.n_shards)
    ]
    pooled = jnp.concatenate(outs, axis=1)  # features in bins order
    inv = np.argsort(np.asarray(plan.feature_order))
    return jnp.take(pooled, jnp.asarray(inv), axis=1)


def shard_update(
    plan: ShardPlan,
    s: int,
    state_s: Params,
    idx: jnp.ndarray,
    g_pooled: jnp.ndarray,
    lr: float,
    eps: float = 1e-8,
    *,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Params:
    """Fused sparse-Adagrad backward for ONE shard: touches only this PS's
    rows, so per-shard updates are independent Hogwild writes."""
    m, d = idx.shape[-1], g_pooled.shape[-1]
    loc = _route(plan, s, idx).reshape(-1, m)
    g = jnp.take(g_pooled, jnp.asarray(plan.bins[s]), axis=1).reshape(-1, d)
    table, acc = sparse_adagrad_op(
        state_s["table"],
        state_s["acc"],
        loc,
        g,
        lr=lr,
        eps=eps,
        dim=d,
        use_pallas=use_pallas, interpret=interpret)
    return {"table": table, "acc": acc}


@dataclass(frozen=True)
class ShardRetryPolicy:
    """Routing policy for updates aimed at a failed shard: retry with
    backoff inside a bounded budget, then drop (bounded staleness beats an
    unbounded stall — the surviving shards must never wait)."""

    retries: int = 2          # attempts AFTER the first
    backoff_s: float = 0.005  # sleep before each retry (doubles per retry)
    timeout_s: float = 0.05   # hard wall-clock budget for the whole attempt

    def validate(self) -> "ShardRetryPolicy":
        if self.retries < 0 or self.backoff_s < 0 or self.timeout_s <= 0:
            raise ValueError(
                f"need retries >= 0, backoff_s >= 0, timeout_s > 0; got "
                f"retries={self.retries}, backoff_s={self.backoff_s}, "
                f"timeout_s={self.timeout_s}")
        return self


@dataclass(frozen=True)
class ShardEvent:
    """One PS failure-domain transition (``EmbeddingShards.events``)."""

    kind: str  # "ps_snapshot" | "ps_fail" | "ps_recover"
    shard: int
    t: float  # time.perf_counter domain (same clock as supervision events)
    reason: str = ""


class EmbeddingShards:
    """Host-side holder of the per-PS Hogwild states (ThreadedShadowRunner's
    embedding substrate). ``states[s]`` is replaced wholesale per update —
    concurrent trainers can interleave per shard (lost updates included:
    that is the preserved Hogwild property, DESIGN.md §2).

    Failure domain (DESIGN.md §10.3): ``health[s]`` marks a live shard;
    ``fail_shard`` discards the live state (a lost PS), after which
    ``tables()`` serves the latest background snapshot for that shard (a
    stale read, counted in ``stale_lookups``) and ``try_update`` retries
    then drops writes (counted in ``dropped_updates``). ``recover_shard``
    rehydrates from the snapshot and the shard rejoins the plan.

    Thread model: trainers call ``tables``/``try_update`` lock-free (list
    reads are atomic under the GIL; states are immutable jnp arrays swapped
    wholesale); health/snapshot transitions take ``_lock``. ``init`` seeds
    generation-0 snapshots, so recovery is always possible.

    Cached mode (``cache`` set, DESIGN.md §11): each healthy shard is
    fronted by a ``CachedStore`` and the hot path moves to
    ``cached_lookup``/``cached_update`` (``tables``/``try_update`` raise —
    mixing the two views would fork the shard state). Everything above the
    hot path is unchanged: snapshots, failure, recovery, and ``to_packed``
    all go through the store's ``merged()`` canonical view, so the failure
    domain and checkpoints cannot tell the cache exists."""

    def __init__(
        self,
        plan: ShardPlan,
        states: List[Params],
        retry: Optional[ShardRetryPolicy] = None,
        cache: Optional[CacheConfig] = None,
    ):
        self.plan = plan
        self.retry = (retry or ShardRetryPolicy()).validate()
        self.cache = cache.validate() if cache is not None else None
        n = plan.n_shards
        self.health: List[bool] = [True] * n  # guarded-by-writes: _lock
        # snapshots are reference grabs of the immutable per-shard states —
        # O(1), taken by the background worker (see snapshot_all). In cached
        # mode a snapshot instead drains the hot tier (merged(), O(hot_rows)).
        # swap-published: elements; guarded-by-writes: _lock
        self.snapshots: List[Params] = list(states)
        self.snapshot_t: List[float] = [time.perf_counter()] * n  # guarded-by-writes: _lock
        # hogwild-race: ok — lossy-by-design failure counters (under-count only)
        self.dropped_updates: List[int] = [0] * n
        self.stale_lookups: List[int] = [0] * n  # hogwild-race: ok — same lossy contract
        self.events: List[ShardEvent] = []  # guarded-by-writes: _lock
        self.failed_at: Dict[int, float] = {}  # guarded-by-writes: _lock — shard -> fail time
        # per-shard failure-domain incarnation: bumped on BOTH fail and
        # recover, so a lookup staged ahead of need (core/pipeline.py) can
        # detect ANY transition between dispatch and consumption and drain
        self.incarnations: List[int] = [0] * n  # guarded-by-writes: _lock
        self._lock = threading.Lock()
        if self.cache is None:
            # swap-published: elements; hogwild-race: ok — lock-free Hogwild
            # element swap with post-dispatch health re-check (try_update)
            self.states: List[Optional[Params]] = list(states)
            # swap-published: elements; guarded-by-writes: _lock — whole-store
            # incarnations swapped on fail/recover; lock-free reads
            self.stores: List[Optional[CachedStore]] = [None] * n
        else:
            # The stores OWN the live values; states[] stays None so any
            # uncached-path access fails loudly instead of reading a fork.
            self.states = [None] * n
            self.stores = [CachedStore(st, self.cache, dim=plan.spec.dim) for st in states]

    @classmethod
    def init(
        cls,
        plan: ShardPlan,
        key: jax.Array,
        retry: Optional[ShardRetryPolicy] = None,
        cache: Optional[CacheConfig] = None,
    ) -> "EmbeddingShards":
        # Seed-identical to the single-table engine: init the packed
        # collection once, then split by the plan.
        return cls(plan, shard_states(plan, init_tables(plan.spec, key)), retry=retry, cache=cache)

    # -- hot-path routing ----------------------------------------------------
    def tables(self) -> Tuple[jnp.ndarray, ...]:
        """Lock-free snapshot of the per-shard tables (Hogwild read). A
        failed shard serves its latest background snapshot — a bounded-
        staleness read instead of a blocked trainer."""
        if self.cache is not None:
            raise RuntimeError(
                "cached mode: use cached_lookup (tables() would read the "
                "stale full-table copy, not the live hot tier)")
        out = []
        for s in range(self.plan.n_shards):
            st = self.states[s]
            # health is the authority, not just ``states[s] is None``: an
            # in-flight try_update that started before fail_shard can land
            # its swap just after, leaving a non-None state on a dead shard
            if st is None or not self.health[s]:
                st = self.snapshots[s]
                self.stale_lookups[s] += 1
            out.append(st["table"])
        return tuple(out)

    def try_update(self, s: int, fn, *args) -> bool:
        """Route one Hogwild write at shard ``s``: ``fn(state, *args)`` maps
        the current state to the new one. Against a healthy shard this is
        the plain lock-free swap. Against a failed shard it retries with
        exponential backoff inside ``ShardRetryPolicy``'s budget, then drops
        the update (returns False; the drop is the measured staleness cost —
        a trainer must never block unboundedly on a dead PS)."""
        if self.cache is not None:
            raise RuntimeError(
                "cached mode: use cached_update (try_update would write the "
                "stale full-table copy, not the live hot tier)")
        retry = self.retry
        deadline = time.perf_counter() + retry.timeout_s
        backoff = retry.backoff_s
        for attempt in range(retry.retries + 1):
            st = self.states[s]
            if self.health[s] and st is not None:
                new = fn(st, *args)
                # re-check AFTER the (milliseconds-long) kernel dispatch:
                # if the shard died mid-flight, landing the swap would
                # resurrect a non-None state on a dead PS — that write is
                # lost with the shard, exactly like a drop
                if self.health[s]:
                    self.states[s] = new
                    return True
            if attempt == retry.retries or time.perf_counter() >= deadline:
                break
            time.sleep(min(backoff, max(deadline - time.perf_counter(), 0.0)))
            backoff *= 2.0
        self.dropped_updates[s] += 1
        return False

    # -- per-shard staged lookup entry points (DESIGN.md §11/§13) ------------
    def incarnation(self, s: int) -> int:
        """Failure-domain token for shard ``s`` — the step pipeline captures
        it at staging and drains the staged value on any mismatch."""
        return self.incarnations[s]

    def lookup_shard(self, s: int, idx: np.ndarray, *, staged: bool = False) -> jnp.ndarray:
        """ONE shard's pooled plane for the full (B, F, m) batch — the
        per-shard half of ``cached_lookup``/``shard_lookup``, independently
        callable so the step pipeline (core/pipeline.py) can stage single
        shards ahead of consumption. A healthy cached shard answers from
        its hot tier, a healthy uncached shard from the live Hogwild state,
        and a failed shard from its snapshot's full table (the bounded-
        staleness read, counted in ``stale_lookups``)."""
        idx = np.asarray(idx)
        if self.cache is not None:
            store = self.stores[s]
            if store is not None and self.health[s]:
                return store.lookup(_route_np(self.plan, s, idx), staged=staged)
        else:
            st = self.states[s]
            # health is the authority, not just None-ness (see tables())
            if st is not None and self.health[s]:
                return self._lookup_table(st["table"], s, idx)
        self.stale_lookups[s] += 1
        return self._lookup_table(self.snapshots[s]["table"], s, idx)

    def _lookup_table(self, table: jnp.ndarray, s: int, idx: np.ndarray) -> jnp.ndarray:
        return embedding_bag_op(
            table, _route(self.plan, s, jnp.asarray(idx)), dim=self.plan.spec.dim
        )

    def assemble(self, outs: List[jnp.ndarray]) -> jnp.ndarray:
        """Reassemble the per-shard pooled planes (bins order) into the
        (B, F, dim) feature-order result — the concat half of the lookup,
        split out so staged and serial shard planes compose freely."""
        pooled = jnp.concatenate(outs, axis=1)  # features in bins order
        inv = np.argsort(np.asarray(self.plan.feature_order))
        return jnp.take(pooled, jnp.asarray(inv), axis=1)

    # -- cached hot path (DESIGN.md §11) -------------------------------------
    def cached_lookup(self, idx: np.ndarray) -> jnp.ndarray:
        """Plan-routed sum-pooled lookup through the per-shard tiered
        caches: idx (B, F, m) LOCAL-per-feature ids -> (B, F, dim), the
        exact ``shard_lookup`` contract (bitwise, tests/test_cache.py). One
        fused hot-tier launch per healthy shard; a failed shard answers
        from its snapshot's full table (the same bounded-staleness read as
        ``tables()``, counted in ``stale_lookups``)."""
        if self.cache is None:
            raise RuntimeError("cached_lookup requires cache= at init")
        idx = np.asarray(idx)
        return self.assemble([self.lookup_shard(s, idx) for s in range(self.plan.n_shards)])

    def cached_update(
        self, s: int, idx: np.ndarray, g_pooled: jnp.ndarray, lr: float, eps: float = 1e-8
    ) -> bool:
        """Route one Hogwild write at shard ``s`` through its tiered cache:
        same health ladder as ``try_update`` (retry with backoff against a
        failed shard, then a counted drop), with the inner write landing on
        the hot tier via the store's optimistic swap. idx is the full
        (B, F, m) batch; this routes shard ``s``'s features and gradient
        planes exactly like ``shard_update``."""
        if self.cache is None:
            raise RuntimeError("cached_update requires cache= at init")
        idx = np.asarray(idx)
        m, d = idx.shape[-1], g_pooled.shape[-1]
        loc = _route_np(self.plan, s, idx).reshape(-1, m)
        g = jnp.take(g_pooled, jnp.asarray(self.plan.bins[s]), axis=1).reshape(-1, d)
        retry = self.retry
        deadline = time.perf_counter() + retry.timeout_s
        backoff = retry.backoff_s
        for attempt in range(retry.retries + 1):
            store = self.stores[s]
            if self.health[s] and store is not None:
                # the store's own bounded retry handles migration races; a
                # False here is already counted in its dropped_updates
                return store.update(loc, g, lr)
            if attempt == retry.retries or time.perf_counter() >= deadline:
                break
            time.sleep(min(backoff, max(deadline - time.perf_counter(), 0.0)))
            backoff *= 2.0
        self.dropped_updates[s] += 1
        return False

    def cache_stats(self) -> Dict[str, int]:
        """Summed ``CacheStats`` across the live per-shard stores."""
        total: Dict[str, int] = {}
        for store in self.stores:
            if store is None:
                continue
            for k, v in store.stats.as_dict().items():
                total[k] = total.get(k, 0) + v
        return total

    # -- failure-domain transitions ------------------------------------------
    def snapshot_all(self, reason: str = "") -> None:
        """Background snapshot of every healthy shard (reference grabs of
        the immutable states — O(n_shards), no copies). The shadow thread
        calls this every few rounds; the snapshot is what a failed shard
        serves and what recovery rehydrates from.

        Cached mode: the snapshot is ``stores[s].merged()`` — hot+cold
        folded back into the canonical table, so recovery and checkpoints
        stay cache-invisible. That costs O(hot_rows) device reads per shard
        instead of an O(1) reference grab; still the background worker's
        bill, never a trainer's."""
        now = time.perf_counter()
        if self.cache is not None:
            # Capture the live store refs under the lock; fold hot+cold
            # OUTSIDE it (merged() is device work — no-blocking-under-lock,
            # DESIGN.md §12); publish each snapshot only if the same store
            # incarnation is still live (a fail/recover mid-merge would
            # make it a snapshot of a dead incarnation).
            with self._lock:
                live = [(s, self.stores[s])
                        for s in range(self.plan.n_shards)
                        if self.health[s] and self.stores[s] is not None]
            for s, store in live:
                snap = store.merged()
                with self._lock:
                    if self.health[s] and self.stores[s] is store:
                        self.snapshots[s] = snap
                        self.snapshot_t[s] = now
            return
        with self._lock:
            for s in range(self.plan.n_shards):
                st = self.states[s]
                if self.health[s] and st is not None:
                    self.snapshots[s] = st
                    self.snapshot_t[s] = now

    def fail_shard(self, s: int, reason: str = "") -> None:
        """PS ``s`` dies: its live state is LOST (not quietly kept). Lookups
        fall back to the snapshot, updates start dropping after retries."""
        with self._lock:
            if not self.health[s]:
                return  # already down
            self.health[s] = False
            self.states[s] = None
            self.stores[s] = None  # cached mode: both tiers die with the PS
            self.incarnations[s] += 1  # drain any in-flight staged lookups
            self.failed_at[s] = time.perf_counter()
            self.events.append(ShardEvent("ps_fail", s, self.failed_at[s], reason))

    def recover_shard(self, s: int, reason: str = "") -> None:
        """Rehydrate shard ``s`` from its latest snapshot and rejoin the
        routing plan. The delta between the snapshot and the pre-failure
        live state (plus the updates dropped while down) is the bounded-
        staleness cost the bench measures."""
        with self._lock:
            if self.health[s]:
                return  # already up
            snap = self.snapshots[s]
        store = None
        if self.cache is not None:
            # rebuild the tiered store from the canonical snapshot — a
            # background cache-warm migration (placement restarts from the
            # default; the prefetcher re-derives it within a round). The
            # build moves whole tables host->device, so it runs OUTSIDE
            # the lock; a down shard's snapshot cannot advance meanwhile.
            store = CachedStore(snap, self.cache, dim=self.plan.spec.dim)
        with self._lock:
            if self.health[s]:
                return  # a concurrent recovery beat us to it
            if self.cache is not None:
                self.stores[s] = store
            else:
                self.states[s] = self.snapshots[s]
            self.health[s] = True
            self.incarnations[s] += 1  # staged-during-outage lookups drain
            self.failed_at.pop(s, None)
            self.events.append(ShardEvent("ps_recover", s, time.perf_counter(), reason))

    def down_shards(self) -> List[int]:
        return [s for s in range(self.plan.n_shards) if not self.health[s]]

    def to_packed(self) -> Params:
        """The engine-independent packed {"table", "acc"} view: host numpy
        in the logical (total_rows, dim) layout (``packed_state``). A failed
        shard contributes its snapshot (the best surviving copy). Cached
        shards contribute ``merged()`` — the cache-invisibility contract:
        checkpoints and the sync oracle see the canonical full tables."""
        if self.cache is not None:
            states = [
                store.merged() if store is not None else self.snapshots[s]
                for s, store in enumerate(self.stores)
            ]
        else:
            states = [
                st if st is not None else self.snapshots[s] for s, st in enumerate(self.states)
            ]
        return packed_state(self.plan, states)
