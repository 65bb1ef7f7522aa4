"""Substrate tests: optimizers, data pipeline, embeddings, checkpoint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import checkpoint as ckpt
from repro import optim
from repro.configs import dlrm_ctr
from repro.data import ctr, tokens
from repro.data.loader import PrefetchLoader
from repro.embeddings import table as emb


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

class TestOptim:
    @pytest.mark.parametrize("name,lr", [
        ("sgd", 0.1), ("momentum", 0.05), ("adagrad", 0.8),
        ("rmsprop", 0.05), ("adam", 0.1),
    ])
    def test_quadratic_convergence(self, name, lr):
        """min 0.5*||x - c||^2: every optimizer converges on a convex bowl."""
        c = jnp.asarray([1.0, -2.0, 3.0])
        opt = optim.make(name, lr)
        params = {"x": jnp.zeros(3)}
        state = opt.init(params)
        for _ in range(300):
            g = {"x": params["x"] - c}
            params, state = opt.update(params, state, g)
        np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(c), atol=0.05)

    def test_sgd_closed_form(self):
        opt = optim.sgd(0.5)
        p, _ = opt.update({"x": jnp.asarray([2.0])}, (), {"x": jnp.asarray([1.0])})
        np.testing.assert_allclose(p["x"], [1.5])

    def test_adagrad_scales_by_accumulator(self):
        opt = optim.adagrad(1.0)
        params = {"x": jnp.asarray([0.0])}
        st_ = opt.init(params)
        p1, st_ = opt.update(params, st_, {"x": jnp.asarray([2.0])})
        # first step: -lr * g / sqrt(g^2) = -1
        np.testing.assert_allclose(p1["x"], [-1.0], atol=1e-4)

    def test_momentum_nesterov_differs(self):
        g = {"x": jnp.asarray([1.0])}
        p0 = {"x": jnp.asarray([0.0])}
        o1, o2 = optim.momentum(0.1, 0.9), optim.momentum(0.1, 0.9, nesterov=True)
        p1, s1 = o1.update(p0, o1.init(p0), g)
        p1, _ = o1.update(p1, s1, g)
        p2, s2 = o2.update(p0, o2.init(p0), g)
        p2, _ = o2.update(p2, s2, g)
        assert float(p1["x"][0]) != pytest.approx(float(p2["x"][0]))

    def test_wsd_schedule_shape(self):
        lr = optim.wsd_schedule(1.0, warmup=10, stable=20, decay=10)
        assert float(lr(jnp.asarray(0))) == 0.0
        assert float(lr(jnp.asarray(10))) == pytest.approx(1.0)
        assert float(lr(jnp.asarray(25))) == pytest.approx(1.0)
        assert float(lr(jnp.asarray(40))) == pytest.approx(0.1, abs=1e-5)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

class TestCTRData:
    def test_deterministic_one_pass(self):
        cfg = dlrm_ctr.tiny()
        teacher = ctr.make_teacher(cfg, 0)
        b1 = ctr.gen_batch(cfg, teacher, seed=1, batch_idx=5, batch_size=32)
        b2 = ctr.gen_batch(cfg, teacher, seed=1, batch_idx=5, batch_size=32)
        b3 = ctr.gen_batch(cfg, teacher, seed=1, batch_idx=6, batch_size=32)
        np.testing.assert_array_equal(np.asarray(b1["sparse"]), np.asarray(b2["sparse"]))
        assert not np.array_equal(np.asarray(b1["sparse"]), np.asarray(b3["sparse"]))

    def test_indices_in_range(self):
        cfg = dlrm_ctr.tiny()
        teacher = ctr.make_teacher(cfg, 0)
        b = ctr.gen_batch(cfg, teacher, 0, 0, 256)
        idx = np.asarray(b["sparse"])
        sizes = np.asarray(cfg.table_sizes)
        assert (idx >= 0).all()
        assert (idx < sizes[None, :, None]).all()

    def test_labels_learnable_structure(self):
        """Click rate reflects the hidden teacher: base CTR well below 0.5 and
        the Bayes-optimal loss is below the base-rate entropy."""
        cfg = dlrm_ctr.tiny()
        teacher = ctr.make_teacher(cfg, 0)
        b = ctr.gen_batch(cfg, teacher, 0, 0, 8192)
        rate = float(np.mean(np.asarray(b["labels"])))
        assert 0.03 < rate < 0.45

    def test_normalized_entropy(self):
        assert ctr.normalized_entropy(0.3, 0.2) == pytest.approx(0.3 / 0.5004, rel=1e-3)


class TestTokenData:
    def test_markov_stream_deterministic(self):
        trans = tokens.make_transition(64, 0)
        b1 = tokens.gen_batch(trans, 0, 3, 4, 32)
        b2 = tokens.gen_batch(trans, 0, 3, 4, 32)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
        assert b1["tokens"].shape == (4, 32)

    def test_prefetch_loader_order_and_bound(self):
        loader = PrefetchLoader(lambda i: i * i, n_batches=10, prefetch=2)
        assert list(loader) == [i * i for i in range(10)]


# ---------------------------------------------------------------------------
# Embedding tables (the paper's embedding-PS substrate)
# ---------------------------------------------------------------------------

class TestEmbeddings:
    def setup_method(self):
        self.cfg = dlrm_ctr.tiny()
        self.spec = emb.spec_from_config(self.cfg)

    def test_lookup_matches_manual(self):
        state = emb.init_tables(self.spec, jax.random.PRNGKey(0))
        idx = jnp.asarray([[[0, 1]] * self.cfg.n_sparse_features])  # (1, F, 2)
        out = emb.lookup(state, self.spec, idx)
        offs = self.spec.offsets
        table = emb.to_logical(state, self.spec)["table"]
        for f in range(self.cfg.n_sparse_features):
            manual = table[offs[f] + 0] + table[offs[f] + 1]
            np.testing.assert_allclose(np.asarray(out[0, f]), np.asarray(manual), rtol=1e-6)

    def test_sparse_adagrad_only_touches_rows(self):
        state = emb.init_tables(self.spec, jax.random.PRNGKey(1))
        before = emb.to_logical(state, self.spec)["table"]
        idx = jnp.zeros((2, self.cfg.n_sparse_features, self.cfg.multi_hot), jnp.int32)
        g = jnp.ones((2, self.cfg.n_sparse_features, self.cfg.embedding_dim))
        new = emb.sparse_adagrad_update(state, self.spec, idx, g, lr=0.1)
        after = emb.to_logical(new, self.spec)["table"]
        touched = set(np.asarray(emb.global_row_ids(self.spec, idx)).reshape(-1).tolist())
        for r in range(before.shape[0]):
            if r in touched:
                assert not np.allclose(after[r], before[r])
            else:
                np.testing.assert_array_equal(after[r], before[r])

    def test_adagrad_accumulator_grows(self):
        state = emb.init_tables(self.spec, jax.random.PRNGKey(2))
        idx = jnp.zeros((1, self.cfg.n_sparse_features, self.cfg.multi_hot), jnp.int32)
        g = jnp.ones((1, self.cfg.n_sparse_features, self.cfg.embedding_dim))
        s1 = emb.sparse_adagrad_update(state, self.spec, idx, g, lr=0.1)
        s2 = emb.sparse_adagrad_update(s1, self.spec, idx, g, lr=0.1)
        assert float(jnp.sum(s2["acc"])) > float(jnp.sum(s1["acc"]))

    @settings(max_examples=25, deadline=None)
    @given(n_bins=st.integers(1, 8), seed=st.integers(0, 1000))
    def test_bin_pack_properties(self, n_bins, seed):
        """Every table lands in exactly one bin; LPT load <= 4/3 OPT + max."""
        rng = np.random.RandomState(seed)
        costs = rng.exponential(10.0, size=12)
        bins = emb.bin_pack(costs, n_bins)
        flat = sorted(i for b in bins for i in b)
        assert flat == list(range(12))
        loads = [sum(costs[i] for i in b) for b in bins]
        lower = max(costs.max(), costs.sum() / n_bins)
        assert max(loads) <= (4.0 / 3.0) * lower + 1e-9

    def test_lookup_costs_monotone_in_batch(self):
        c1 = emb.lookup_costs(self.spec, 100)
        c2 = emb.lookup_costs(self.spec, 200)
        assert (c2 > c1).all()


# ---------------------------------------------------------------------------
# Checkpoint
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {
            "w": {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.asarray(7),
        }
        ckpt.save(str(tmp_path / "c"), tree, metadata={"algo": "easgd"})
        like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
        restored, meta = ckpt.restore(str(tmp_path / "c"), like)
        assert meta["algo"] == "easgd"
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))

    def test_resume_mid_stream(self, tmp_path):
        """Save/restore the full HogwildSim state and continue the one-pass stream."""
        from repro.core.runners import HogwildSim
        from repro.core.sync import SyncConfig

        cfg = dlrm_ctr.tiny()
        sim = HogwildSim(cfg, SyncConfig(algo="ma"), n_trainers=2, n_threads=1,
                         batch_size=32, optimizer=optim.adagrad(0.02))
        out = sim.run(10)
        st = out["state"]
        ckpt.save(str(tmp_path / "c"), {"w": st.w_stack, "emb": st.emb_state},
                  metadata={"step": st.step})
        like = {"w": jax.tree.map(jnp.zeros_like, st.w_stack),
                "emb": jax.tree.map(jnp.zeros_like, st.emb_state)}
        restored, meta = ckpt.restore(str(tmp_path / "c"), like)
        assert meta["step"] == 10
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(restored["w"])[0]),
            np.asarray(jax.tree.leaves(st.w_stack)[0]))
