"""The benchmark's plain reference agrees with the program at ``dlrm_ctr.tiny()`` on the CPU."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import ctr as rctr
from bench.reference import dlrm as rdlrm
from bench.reference.shapes import Shapes
from bench.tests.conftest import DATA


@pytest.fixture(scope="module")
def shapes():
    return Shapes.from_config(json.loads((DATA / "dlrm-tiny.json").read_text()))


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import dlrm_ctr

    return dlrm_ctr.tiny()


def test_tiny_file_is_the_programs_tiny_config(shapes, cfg):
    assert shapes.table_sizes == cfg.table_sizes
    assert (shapes.dim, shapes.multi_hot, shapes.n_dense) == (
        cfg.embedding_dim, cfg.multi_hot, cfg.n_dense_features)
    assert (shapes.bottom_mlp, shapes.top_mlp) == (cfg.bottom_mlp, cfg.top_mlp)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_stream_is_the_programs_bit_for_bit(shapes, cfg, seed):
    from repro.data import ctr

    t = ctr.make_teacher(cfg, seed=seed + 777)
    rt = rctr.make_teacher(shapes, seed + 777)
    for i in (0, 5):
        a = ctr.gen_batch(cfg, t, seed, i, 64)
        b = rctr.gen_batch(shapes, rt, seed, i, 64)
        for k in ("dense", "sparse", "labels"):
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        np.testing.assert_array_equal(np.asarray(rctr.gen_ids(shapes, seed, i, 64)),
                                      np.asarray(b["sparse"]))


def test_initial_weights_are_the_programs(shapes, cfg):
    from bench.program import leaf_dict
    from repro.embeddings import table as emb
    from repro.models import dlrm

    kw, ke = jax.random.split(jax.random.PRNGKey(9))
    prog = leaf_dict(dlrm.init_dense(cfg, kw))
    ref = rdlrm.init_dense(shapes, kw)
    assert set(prog) == set(ref) == set(rdlrm.leaf_names(shapes))
    for k in ref:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]))
    spec = emb.spec_from_config(cfg)
    logical = emb.to_logical(emb.init_tables(spec, ke), spec)
    np.testing.assert_allclose(logical["table"], np.asarray(rdlrm.init_table(shapes, ke)),
                               rtol=1e-6, atol=1e-7)


def test_dense_step_matches_the_program(shapes, cfg):
    from bench.program import leaf_dict
    from repro import optim
    from repro.models import dlrm

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    w = dlrm.init_dense(cfg, k1)
    pooled = jax.random.normal(k2, (32, shapes.n_tables, shapes.dim)) * 0.3
    batch = rctr.gen_batch(shapes, rctr.make_teacher(shapes, 1), 7, 0, 32)
    loss, g_w, g_pooled = dlrm.dense_loss_and_grads(w, batch["dense"], pooled, batch["labels"])
    opt = optim.adagrad(0.02)
    w2, acc2 = opt.update(w, opt.init(w), g_w)
    with jax.default_matmul_precision("highest"):
        p = leaf_dict(w)
        rloss, rg, rgp = rdlrm.loss_and_grads(shapes, p, batch, pooled)
        rp2, racc2 = rdlrm.adagrad(p, {k: jnp.zeros_like(v) for k, v in p.items()}, rg, 0.02, 1e-8)
    np.testing.assert_allclose(float(loss), float(rloss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_pooled), np.asarray(rgp), rtol=1e-4, atol=1e-6)
    for k, v in leaf_dict(w2).items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(rp2[k]), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(leaf_dict(acc2)[k]), np.asarray(racc2[k]),
                                   rtol=1e-4, atol=1e-9)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sparse_adagrad_matches_the_program(shapes, cfg, use_pallas):
    from repro.embeddings import table as emb

    spec = emb.spec_from_config(cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    state = emb.init_tables(spec, k1)
    state = {"table": state["table"], "acc": state["acc"] + 0.5}
    ids = rctr.gen_ids(shapes, 3, 0, 48)  # u^2 skew: repeated rows in the batch
    g = jax.random.normal(k2, (48, shapes.n_tables, shapes.dim))
    new = emb.sparse_adagrad_update_fused(state, spec, ids, g, 0.05, use_pallas=use_pallas)
    logical = emb.to_logical(state, spec)
    rows = rdlrm.global_rows(shapes, ids)
    t, a = rdlrm.sparse_adagrad(jnp.asarray(logical["table"]), jnp.asarray(logical["acc"]),
                                rows, g, 0.05, 1e-8)
    out = emb.to_logical(new, spec)
    np.testing.assert_allclose(out["acc"], np.asarray(a), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out["table"], np.asarray(t), rtol=1e-5, atol=1e-6)
    pooled = emb.lookup(new, spec, ids)
    np.testing.assert_allclose(np.asarray(pooled), np.asarray(rdlrm.pooled_lookup(t, rows)),
                               rtol=1e-5, atol=1e-5)


def test_easgd_exchange_matches_the_program(shapes, cfg):
    from bench.program import leaf_dict
    from repro.core import sync
    from repro.models import dlrm

    ka, kb = jax.random.split(jax.random.PRNGKey(6))
    c, w = dlrm.init_dense(cfg, ka), dlrm.init_dense(cfg, kb)
    c2, w2 = sync.easgd_pair_update(c, w, 0.5)
    rc, rw = rdlrm.easgd_pair(leaf_dict(c), leaf_dict(w), 0.5)
    for k, v in leaf_dict(c2).items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(rc[k]), rtol=1e-6, atol=1e-7)
    for k, v in leaf_dict(w2).items():
        np.testing.assert_allclose(np.asarray(v), np.asarray(rw[k]), rtol=1e-6, atol=1e-7)
