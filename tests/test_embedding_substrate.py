"""Sparse embedding substrate: fused kernels, shard plan, runner wiring.

Covers DESIGN.md §7: kernel-vs-pytree-oracle parity for the fused
sparse-Adagrad backward (duplicate-row accumulate semantics, both grid
strategies), the bag-blocked lookup kernel, `EmbeddingShards` routing
invariants (every global row on exactly one shard; plan == bin_pack output),
the runners' fused/sharded defaults, the `delay=0` same-iteration landing
regression, and `SyncConfig.validate` input hardening."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import dlrm_ctr
from repro.core.runners import HogwildSim, ThreadedShadowRunner
from repro.core.sync import SyncConfig
from repro.embeddings import shards
from repro.embeddings import table as emb
from repro.kernels import layout
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op
from repro.kernels.sparse_adagrad.ref import sparse_adagrad_ref

# real-thread suites must never wedge CI: pytest-timeout (see
# requirements-ci.txt) enforces this per-test wall ceiling
pytestmark = pytest.mark.timeout(300)


CFG = dlrm_ctr.tiny()
SPEC = emb.spec_from_config(CFG)


# ---------------------------------------------------------------------------
# Fused sparse-Adagrad kernel vs oracle
# ---------------------------------------------------------------------------

class TestSparseAdagradKernel:
    @pytest.mark.parametrize("strategy", ["rows", "block"])
    @pytest.mark.parametrize("n_rows,d,n_bags,m", [
        (100, 16, 32, 4), (57, 48, 7, 3), (513, 128, 19, 1),
    ])
    def test_parity_random(self, strategy, n_rows, d, n_bags, m):
        key = jax.random.PRNGKey(n_rows + d)
        table = jax.random.normal(key, (n_rows, d))
        acc = jax.random.uniform(jax.random.fold_in(key, 1), (n_rows, d))
        idx = jax.random.randint(jax.random.fold_in(key, 2), (n_bags, m), 0, n_rows)
        g = jax.random.normal(jax.random.fold_in(key, 3), (n_bags, d))
        t2, a2 = sparse_adagrad_op(table, acc, idx, g, lr=0.05, strategy=strategy)
        rt, ra = sparse_adagrad_ref(table, acc, idx, g, 0.05)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(rt), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(ra), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("strategy", ["rows", "block"])
    def test_duplicate_rows_accumulate(self, strategy):
        """Duplicates in a batch scatter-ADD (Hogwild accumulate), and the row
        step is scaled by the FINAL accumulator — tiny row range forces heavy
        collision."""
        key = jax.random.PRNGKey(7)
        table = jax.random.normal(key, (5, 16))
        acc = jnp.zeros((5, 16))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (64, 4), 0, 5)
        g = jax.random.normal(jax.random.fold_in(key, 2), (64, 16))
        t2, a2 = sparse_adagrad_op(table, acc, idx, g, lr=0.1, strategy=strategy)
        rt, ra = sparse_adagrad_ref(table, acc, idx, g, 0.1)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(ra), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(rt), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("strategy", ["rows", "block"])
    def test_all_indices_identical_worst_case(self, strategy):
        """Every occurrence hits ONE row: the longest possible duplicate run
        (rows strategy) / maximal in-block collision (blocked strategy)."""
        key = jax.random.PRNGKey(11)
        table = jax.random.normal(key, (9, 32))
        acc = jnp.ones((9, 32)) * 0.5
        idx = jnp.full((16, 4), 3, jnp.int32)
        g = jax.random.normal(jax.random.fold_in(key, 1), (16, 32))
        t2, a2 = sparse_adagrad_op(table, acc, idx, g, lr=0.2, strategy=strategy)
        rt, ra = sparse_adagrad_ref(table, acc, idx, g, 0.2)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(ra), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(rt), rtol=1e-4, atol=1e-4)
        # untouched rows bit-identical (aliased in/out, never streamed)
        touched = {3}
        for r in range(9):
            if r not in touched:
                np.testing.assert_array_equal(np.asarray(t2[r]), np.asarray(table[r]))
                np.testing.assert_array_equal(np.asarray(a2[r]), np.asarray(acc[r]))

    def test_fused_update_vs_pytree_oracle(self):
        """The table-level entry point: fused kernel vs emb.sparse_adagrad_update
        on a real (B, F, m) batch, duplicates included."""
        key = jax.random.PRNGKey(3)
        state = emb.init_tables(SPEC, key)
        idx = jax.random.randint(
            jax.random.fold_in(key, 1), (8, CFG.n_sparse_features, CFG.multi_hot),
            0, 1 << 30) % jnp.asarray(SPEC.sizes)[None, :, None]
        g = jax.random.normal(
            jax.random.fold_in(key, 2), (8, CFG.n_sparse_features, CFG.embedding_dim))
        fused = emb.sparse_adagrad_update_fused(state, SPEC, idx, g, 0.05)
        oracle = emb.sparse_adagrad_update(state, SPEC, idx, g, 0.05)
        for k in oracle:
            np.testing.assert_allclose(
                np.asarray(fused[k]), np.asarray(oracle[k]), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("strategy", ["rows", "block"])
    def test_bf16_table(self, strategy):
        key = jax.random.PRNGKey(5)
        table = jax.random.normal(key, (32, 16)).astype(jnp.bfloat16)
        acc = jnp.zeros((32, 16))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (8, 2), 0, 32)
        g = jax.random.normal(jax.random.fold_in(key, 2), (8, 16))
        t2, a2 = sparse_adagrad_op(table, acc, idx, g, lr=0.1, strategy=strategy)
        rt, ra = sparse_adagrad_ref(table, acc, idx, g, 0.1)
        assert t2.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(t2, np.float32),
                                   np.asarray(rt, np.float32), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(ra), rtol=1e-4, atol=1e-4)


class TestEmbeddingBagBlocked:
    """The bag-blocked grid strategy (the off-TPU interpret path)."""

    @pytest.mark.parametrize("rows,d,n_bags,m", [
        (64, 128, 8, 1), (100, 16, 37, 4), (512, 48, 1025, 3),
    ])
    def test_parity_both_strategies(self, rows, d, n_bags, m):
        key = jax.random.PRNGKey(rows + n_bags)
        table = jax.random.normal(key, (rows, d))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (n_bags, m), 0, rows)
        ref = embedding_bag_ref(table, idx)
        for strategy in ("stream", "block"):
            out = embedding_bag_op(table, idx, strategy=strategy)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5, err_msg=strategy)

    def test_lookup_dispatch_matches_ref(self):
        state = emb.init_tables(SPEC, jax.random.PRNGKey(0))
        idx = jax.random.randint(
            jax.random.PRNGKey(1), (6, CFG.n_sparse_features, CFG.multi_hot),
            0, 1 << 30) % jnp.asarray(SPEC.sizes)[None, :, None]
        np.testing.assert_allclose(
            np.asarray(emb.lookup(state, SPEC, idx)),
            np.asarray(emb.lookup_ref(state, SPEC, idx)), rtol=1e-5, atol=1e-5)


class TestLaneDenseLayout:
    """Tables stored lane-dense (kernels/layout.py) through both grid
    strategies of both kernels, in interpret mode, against the oracles on the
    plain (rows, d) view — duplicates included, and both logical rows of one
    lane row in one batch (an update of one must not lose the other)."""

    @pytest.mark.parametrize("n_rows,d", [(50, 16), (37, 64), (21, 48), (9, 128)])
    def test_pack_roundtrip_and_row_access(self, n_rows, d):
        x = jax.random.normal(jax.random.PRNGKey(d), (n_rows, d))
        packed = layout.pack(x)
        assert packed.shape == (layout.lane_rows(n_rows, d), layout.lane_width(d))
        assert packed.shape[-1] % 128 == 0
        np.testing.assert_array_equal(np.asarray(layout.unpack(packed, n_rows, d)), x)
        rows = jnp.asarray([n_rows - 1, 0, 1, 0])
        np.testing.assert_array_equal(np.asarray(layout.take_rows(packed, rows, d)),
                                      np.asarray(x[rows]))
        vals = -jnp.ones((2, d))
        out = layout.unpack(layout.set_rows(packed, jnp.asarray([0, 1]), vals, d), n_rows, d)
        np.testing.assert_array_equal(np.asarray(out[:2]), np.asarray(vals))
        np.testing.assert_array_equal(np.asarray(out[2:]), np.asarray(x[2:]))

    @pytest.mark.parametrize("strategy", ["stream", "block"])
    @pytest.mark.parametrize("n_rows,d,m", [(50, 16, 4), (37, 64, 3), (21, 48, 2)])
    def test_lookup_parity(self, strategy, n_rows, d, m):
        key = jax.random.PRNGKey(n_rows)
        x = jax.random.normal(key, (n_rows, d))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (11, m), 0, n_rows)
        idx = idx.at[0].set(jnp.asarray([0, 1, 0, 1][:m]))  # one lane row, both halves
        out = embedding_bag_op(layout.pack(x), idx, dim=d, strategy=strategy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(embedding_bag_ref(x, idx)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("strategy", ["rows", "block"])
    @pytest.mark.parametrize("n_rows,d", [(50, 16), (37, 64), (21, 48)])
    def test_update_parity_shared_lane_rows(self, strategy, n_rows, d):
        key = jax.random.PRNGKey(n_rows + d)
        t = jax.random.normal(key, (n_rows, d))
        a = jax.random.uniform(jax.random.fold_in(key, 1), (n_rows, d))
        # rows 0/1 (and 2/3) share a lane row whenever d < 128: both halves
        # of one lane row, each duplicated, in one batch; plus random rows
        idx = jnp.concatenate([
            jnp.asarray([[0, 1, 1, 0], [2, 2, 0, 5]]),
            jax.random.randint(jax.random.fold_in(key, 2), (14, 4), 0, n_rows),
        ])
        g = jax.random.normal(jax.random.fold_in(key, 3), (idx.shape[0], d))
        t2, a2 = sparse_adagrad_op(layout.pack(t), layout.pack(a), idx, g, lr=0.1,
                                   dim=d, strategy=strategy)
        rt, ra = sparse_adagrad_ref(t, a, idx, g, 0.1)
        t2, a2 = layout.unpack(t2, n_rows, d), layout.unpack(a2, n_rows, d)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(rt), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(a2), np.asarray(ra), rtol=2e-5, atol=2e-5)
        untouched = np.setdiff1d(np.arange(n_rows), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(t2)[untouched], np.asarray(t)[untouched])
        np.testing.assert_array_equal(np.asarray(a2)[untouched], np.asarray(a)[untouched])


# ---------------------------------------------------------------------------
# Shard plan + EmbeddingShards routing invariants
# ---------------------------------------------------------------------------

class TestEmbeddingShards:
    @pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
    def test_plan_matches_bin_pack(self, n_shards):
        plan = shards.plan_shards(SPEC, n_shards, 64)
        expect = emb.bin_pack(emb.lookup_costs(SPEC, 64), min(n_shards, len(SPEC.sizes)))
        assert [list(b) for b in plan.bins] == expect

    @pytest.mark.parametrize("n_shards", [1, 3, 4, 8])
    def test_every_row_on_exactly_one_shard(self, n_shards):
        """The shard layouts partition the global packed row space."""
        plan = shards.plan_shards(SPEC, n_shards, 64)
        seen = {}
        goff = SPEC.offsets
        for f in range(len(SPEC.sizes)):
            s, loff = plan.feature_shard[f], plan.feature_local_offset[f]
            assert f in plan.bins[s]
            for r in range(SPEC.sizes[f]):
                key = (s, loff + r)
                assert key not in seen, f"shard row claimed twice: {key}"
                seen[key] = int(goff[f]) + r
        assert sorted(seen.values()) == list(range(SPEC.total_rows))
        assert sum(plan.shard_rows) == SPEC.total_rows

    def test_split_roundtrip_and_seed_parity(self):
        state = emb.init_tables(SPEC, jax.random.PRNGKey(0))
        plan = shards.plan_shards(SPEC, 4, 64)
        es = shards.EmbeddingShards.init(plan, jax.random.PRNGKey(0))
        packed = es.to_packed()
        logical = emb.to_logical(state, SPEC)
        for k in state:
            np.testing.assert_array_equal(np.asarray(packed[k]), logical[k])

    def test_sharded_cycle_matches_single_table(self):
        """Plan-routed lookup + per-shard fused backward == the packed
        single-table oracle."""
        key = jax.random.PRNGKey(9)
        state = emb.init_tables(SPEC, key)
        plan = shards.plan_shards(SPEC, 3, 16)
        es = shards.EmbeddingShards(plan, shards.shard_states(plan, state))
        idx = jax.random.randint(
            jax.random.fold_in(key, 1), (16, CFG.n_sparse_features, CFG.multi_hot),
            0, 1 << 30) % jnp.asarray(SPEC.sizes)[None, :, None]
        g = jax.random.normal(
            jax.random.fold_in(key, 2), (16, CFG.n_sparse_features, CFG.embedding_dim))
        np.testing.assert_allclose(
            np.asarray(shards.shard_lookup(plan, es.tables(), idx)),
            np.asarray(emb.lookup_ref(state, SPEC, idx)), rtol=1e-5, atol=1e-5)
        for s in range(plan.n_shards):
            es.states[s] = shards.shard_update(plan, s, es.states[s], idx, g, 0.05)
        oracle = emb.to_logical(emb.sparse_adagrad_update(state, SPEC, idx, g, 0.05), SPEC)
        packed = es.to_packed()
        for k in oracle:
            np.testing.assert_allclose(np.asarray(packed[k]), np.asarray(oracle[k]),
                                       rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Runner wiring
# ---------------------------------------------------------------------------

class TestRunnerWiring:
    def test_threaded_runner_consumes_plan(self):
        """The LPT plan is a runner-path input, not test-only: the runner's
        shard assignment IS the bin_pack output, and training produces finite
        losses through the per-PS states."""
        r = ThreadedShadowRunner(
            CFG, SyncConfig(algo="ma", alpha=0.5), n_trainers=2, batch_size=16,
            optimizer=optim.adagrad(0.02), n_emb_shards=3)
        assert [list(b) for b in r.plan.bins] == emb.bin_pack(
            emb.lookup_costs(SPEC, 16), 3)
        out = r.run(4)
        assert all(np.isfinite(l) for l in out["train_loss"])
        assert out["emb_state"]["table"].shape == (SPEC.total_rows, CFG.embedding_dim)
        # the packed table moved away from init: updates landed through shards
        init = emb.to_logical(emb.init_tables(SPEC, jax.random.PRNGKey(0)), SPEC)
        assert not np.allclose(np.asarray(out["emb_state"]["acc"]), init["acc"])

    def test_hogwild_sim_step_matches_manual_oracle(self):
        """One _train_iter of the sim (fused kernels by default) produces the
        same embedding state and loss as an independently written oracle
        chain (lookup_ref -> dense grads -> sparse_adagrad_update) — this
        pins train_core's reshuffle/wiring, which kernel-level parity tests
        never exercise."""
        from repro.models import dlrm

        sim = HogwildSim(CFG, SyncConfig(algo="easgd"), n_trainers=1,
                         n_threads=1, batch_size=8,
                         optimizer=optim.adagrad(0.02), seed=5)
        st = sim.init_state()
        batch = sim.make_batch(0)
        # _train_iter donates its buffers: keep pre-step copies for the oracle.
        emb0 = jax.tree.map(jnp.copy, st.emb_state)
        w0 = sim.replica_params(st, 0)
        _, _, emb2, loss = sim._train_iter(
            st.w_stack, st.opt_stack, st.emb_state, batch)

        idx = batch["sparse"][0, 0]  # (B, F, m)
        pooled = emb.lookup_ref(emb0, SPEC, idx)
        loss_ref, _, g_pooled = dlrm.dense_loss_and_grads(
            w0, batch["dense"][0, 0], pooled, batch["labels"][0, 0])
        emb_oracle = emb.sparse_adagrad_update(emb0, SPEC, idx, g_pooled,
                                               sim.emb_lr)
        np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
        for k in emb_oracle:
            np.testing.assert_allclose(np.asarray(emb2[k]),
                                       np.asarray(emb_oracle[k]),
                                       rtol=2e-5, atol=2e-5, err_msg=k)


# ---------------------------------------------------------------------------
# delay=0 same-iteration landing (regression)
# ---------------------------------------------------------------------------

class TestDelayZero:
    @staticmethod
    def _losses(delay, iters=10):
        sim = HogwildSim(
            CFG, SyncConfig(algo="easgd", gap=2, delay=delay), n_trainers=2,
            n_threads=1, batch_size=16, optimizer=optim.adagrad(0.02), seed=0)
        return sim.run(iters)["train_loss"]

    def test_delay0_distinct_from_delay1(self):
        """Pre-fix, delay=0 behaved identically to delay=1 (the landing check
        ran before the launch, so a snapshot with land_t == launch_t was only
        seen one iteration later). Same-iteration landing must change the
        trajectory."""
        l0, l1 = self._losses(0), self._losses(1)
        assert l0 != l1, "delay=0 trajectory identical to delay=1"
        assert all(np.isfinite(l) for l in l0 + l1)

    def test_delay0_sync_counts(self):
        """With delay=0 every launched sync lands in the SAME run() loop pass,
        so nothing is pending at exit and counts match the schedule exactly."""
        sim = HogwildSim(
            CFG, SyncConfig(algo="easgd", gap=2, delay=0), n_trainers=2,
            n_threads=1, batch_size=16, optimizer=optim.adagrad(0.02), seed=0)
        out = sim.run(8)
        expect = sum(int(sim._shadow_schedule(t + 1).sum()) for t in range(8))
        assert out["sync_count"] == expect


# ---------------------------------------------------------------------------
# SyncConfig.validate hardening
# ---------------------------------------------------------------------------

class TestSyncConfigValidate:
    def test_rejects_gap_zero(self):
        with pytest.raises(ValueError, match="gap"):
            SyncConfig(gap=0).validate()

    def test_rejects_negative_gap(self):
        with pytest.raises(ValueError, match="gap"):
            SyncConfig(gap=-3).validate()

    def test_rejects_negative_delay(self):
        with pytest.raises(ValueError, match="delay"):
            SyncConfig(delay=-1).validate()

    @pytest.mark.parametrize("alpha", [-0.1, 1.5])
    def test_rejects_alpha_outside_unit_interval(self, alpha):
        with pytest.raises(ValueError, match="alpha"):
            SyncConfig(alpha=alpha).validate()

    def test_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="mode"):
            SyncConfig(mode="sometimes").validate()

    def test_accepts_valid_edge_values(self):
        SyncConfig(gap=1, delay=0, alpha=0.0).validate()
        SyncConfig(gap=10 ** 9, delay=7, alpha=1.0).validate()
