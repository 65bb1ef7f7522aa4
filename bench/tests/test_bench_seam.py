"""The harness's seam for a new DLRM architecture, on the CPU: bag sizes per
table (``multi_hot`` as a list), and an interaction loaded by name from its
file (``bench/tests/data/concat.py``, which no program runs)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import bytes as nbytes
from bench.cell import BENCH
from bench.flops import forward_flops, train_flops_per_example
from bench.program import pooled_reader, program_config
from bench.reference import ctr as rctr
from bench.reference import dlrm as rdlrm
from bench.reference import shapes as rshapes
from bench.reference.shapes import Shapes, interaction
from bench.reference.train import table_segments, touched_ids
from bench.run import CheckInputs, check_reference
from bench.tests.conftest import DATA

BAGS = [1, 3, 2, 1, 4, 2, 1, 3]  # one bag size per table of the tiny config


def tiny(**kw) -> dict:
    c = json.loads((DATA / "dlrm-tiny.json").read_text())
    c.update(kw)
    return c


@pytest.fixture
def test_interactions(monkeypatch):
    """Interactions are looked up among the test data's files."""
    monkeypatch.setattr(rshapes, "INTERACTIONS", DATA)


def readings(shapes, tr, seed):
    inputs = CheckInputs(shapes, tr, rctr.make_teacher(shapes, tr["task_seed"]), seed)
    return check_reference(shapes, tr, seed, inputs)


@pytest.mark.parametrize("seed", [4, 2**31 + 29])
def test_per_table_bags_ids_and_touched_rows(seed):
    s = Shapes.from_config(tiny(multi_hot=BAGS))
    assert s.bags == tuple(BAGS) and s.n_ids == sum(BAGS)
    batches = [rctr.gen_batch(s, rctr.make_teacher(s, 1), seed, i, 64) for i in range(3)]
    ids = np.concatenate([np.asarray(b["sparse"]) for b in batches])
    assert ids.shape == (192, sum(BAGS))
    col = [f for f, m in enumerate(BAGS) for _ in range(m)]  # the table of each column
    touched = touched_ids(s, batches)
    for f, size in enumerate(s.table_sizes):
        mine = ids[:, [c for c, g in enumerate(col) if g == f]]
        assert 0 <= mine.min() and mine.max() < size
        np.testing.assert_array_equal(touched[f], np.unique(mine))
    rows, seg = table_segments(s, touched, pad_to=ids.size)
    n = sum(len(t) for t in touched)
    np.testing.assert_array_equal(
        np.asarray(rows[:n]), np.concatenate([s.offsets[f] + t for f, t in enumerate(touched)]))
    np.testing.assert_array_equal(np.asarray(seg[:n]),
                                  np.repeat(np.arange(8), [len(t) for t in touched]))
    assert set(np.asarray(seg[n:])) <= {s.n_tables}


def test_pooled_lookup_by_hand():
    table = jnp.arange(12.0).reshape(6, 2)
    rows = jnp.asarray([[5, 1, 2], [0, 3, 3]])  # table 0 one id, table 1 two
    np.testing.assert_array_equal(
        np.asarray(rdlrm.pooled_lookup(table, rows, (1, 2))),
        [[[10, 11], [6, 8]], [[0, 1], [12, 14]]])


@pytest.mark.parametrize("seed", [6, 2**31 + 7])
def test_per_table_bags_sparse_adagrad_is_jax_grad(seed):
    """Rows step by the loss's gradient over the whole table, each over the
    root of its accumulator, which takes every occurrence's gradient squared."""
    s = Shapes.from_config(tiny(multi_hot=BAGS))
    b = rctr.gen_batch(s, rctr.make_teacher(s, 1), seed, 0, 48)
    kp, kt = jax.random.split(jax.random.PRNGKey(seed))
    p, table = rdlrm.init_dense(s, kp), rdlrm.init_table(s, kt)
    rows = rdlrm.global_rows(s, b["sparse"])
    lr, eps = 0.05, 1e-8

    def loss(pooled):
        return rdlrm.bce(rdlrm.forward(s, p, b["dense"], pooled), b["labels"])

    with jax.default_matmul_precision("highest"):
        grad = jax.grad(lambda t: loss(rdlrm.pooled_lookup(t, rows, s.bags)))(table)
        per_id = jax.grad(lambda x: loss(rdlrm.pool(x, s.bags)))(jnp.take(table, rows, axis=0))
        _, _, g_pooled = rdlrm.loss_and_grads(s, p, b, rdlrm.pooled_lookup(table, rows, s.bags))
    acc = jnp.zeros_like(table).at[rows.reshape(-1)].add(per_id.reshape(-1, s.dim) ** 2)
    t2, a2 = rdlrm.sparse_adagrad(table, jnp.zeros_like(table), rows, g_pooled, lr, eps, s.bags)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(acc), rtol=1e-5, atol=1e-12)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(table - lr * grad / jnp.sqrt(acc + eps)),
                               rtol=1e-5, atol=1e-7)
    moved = np.any(np.asarray(t2) != np.asarray(table), axis=1)
    assert moved.sum() > 0 and not moved[np.setdiff1d(np.arange(s.n_rows), np.asarray(rows))].any()


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_one_bag_size_and_a_list_of_it_agree(tiny_cell, seed):
    one, listed = Shapes.from_config(tiny()), Shapes.from_config(tiny(multi_hot=[2] * 8))
    a, b = rctr.gen_ids(one, seed, 4, 64), rctr.gen_ids(listed, seed, 4, 64)
    assert a.shape == (64, 8, 2) and b.shape == (64, 16)
    np.testing.assert_array_equal(np.asarray(a).reshape(64, 16), np.asarray(b))
    ta, tb = rctr.make_teacher(one, 1), rctr.make_teacher(listed, 1)
    ba, bb = rctr.gen_batch(one, ta, seed, 2, 64), rctr.gen_batch(listed, tb, seed, 2, 64)
    for k in ("dense", "labels"):
        np.testing.assert_array_equal(np.asarray(ba[k]), np.asarray(bb[k]))
    ra, rb = readings(one, tiny_cell.traffic, seed), readings(listed, tiny_cell.traffic, seed)
    assert (ra.losses, ra.grad, ra.change) == (rb.losses, rb.grad, rb.change)


def test_bytes_by_hand_per_table_bags():
    # (B, 1 + 2) ids, bags (1, 2): table 0 holds 0, 0 (1 distinct), table 1
    # 1, 2, 2, 2 (2 distinct); 6 rows read, 2 x 2 bags written
    ids = np.array([[0, 1, 2], [0, 2, 2]])
    assert nbytes.lookup_bytes(ids, 4, (1, 2)) == (6 + 4) * 4 * 4
    assert nbytes.distinct_rows(ids, (1, 2)) == 3
    assert nbytes.update_bytes(ids, 4, (1, 2)) == (6 + 4 * 3) * 4 * 4
    assert nbytes.total([ids, ids], 4, nbytes.lookup_bytes, (1, 2)) == 2 * 10 * 16
    # (B, F, m) ids read the same with their bags given
    three = np.array([[[0, 0], [1, 2]], [[0, 3], [2, 2]]])
    for fn in (nbytes.lookup_bytes, nbytes.update_bytes):
        assert fn(three, 4, (2, 2)) == fn(three, 4)


def test_interaction_by_name_trains_through_replay(tiny_cell, test_interactions):
    s = Shapes.from_config(tiny(interaction="concat", multi_hot=BAGS))
    assert s.interact.__file__ == str(DATA / "concat.py")
    names = rdlrm.leaf_names(s)
    p = rdlrm.init_dense(s, jax.random.PRNGKey(2))
    assert names[-1] == "concat.gate" and set(p) == set(names)
    assert p["top.0.w"].shape == (16 + 8 * 16, 64)
    assert train_flops_per_example(s) == 3 * forward_flops(s)
    rd = readings(s, tiny_cell.traffic, 2**31 + 41)
    assert len(rd.losses) == 12 and all(np.isfinite(v) for v in rd.losses.values())
    assert rd.grad["concat.gate"] > 0
    assert all(rd.change[f"r{i}.concat.gate"] > 0 for i in range(4))
    assert all(rd.change[f"table.{f}"] > 0 for f in range(8))


def test_unknown_interaction_fails_naming_its_file():
    with pytest.raises(FileNotFoundError, match="no_such_interaction.py"):
        Shapes.from_config(tiny(interaction="no_such_interaction"))
    with pytest.raises(FileNotFoundError, match="no_such_interaction.py"):
        program_config(tiny(interaction="no_such_interaction"))


def test_bag_sizes_must_match_the_tables():
    with pytest.raises(ValueError, match="7 bag sizes for 8 tables"):
        Shapes.from_config(tiny(multi_hot=BAGS[:7]))


@pytest.mark.parametrize("path,flops", [(DATA / "dlrm-ctr.json", 3_267_456),
                                        (BENCH / "configs" / "dlrm-criteo-kaggle.json", 2_916_192)])
def test_dot_file_gives_the_config_flops(path, flops):
    s = Shapes.from_config(json.loads(path.read_text()))
    dot = interaction("dot")
    assert s.interact is dot
    n = s.n_tables + 1
    assert dot.out_dim(s) == s.bottom_mlp[-1] + n * (n - 1) // 2
    mlp = 0
    for dims in ((s.n_dense,) + s.bottom_mlp, (dot.out_dim(s),) + s.top_mlp):
        mlp += sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    assert 3 * (mlp + dot.forward_flops(s)) == flops


def test_program_keys_pass_to_the_program(test_interactions, monkeypatch):
    mod = interaction("concat")
    monkeypatch.setattr(mod, "PROGRAM_KEYS", ("source",))
    assert program_config(tiny(interaction="concat", source=["a", "b"])).source == ("a", "b")
    monkeypatch.setattr(mod, "PROGRAM_KEYS", ("cross_layers",))
    with pytest.raises(KeyError, match="cross_layers"):
        program_config(tiny(interaction="concat", cross_layers=3))


def test_pooled_reader_per_table_bags():
    """The harness's reader of the program's sharded, lane-dense tables sums
    each table's columns, as the reference does over the logical table."""
    from repro.embeddings import shards
    from repro.embeddings import table as emb

    s = Shapes.from_config(tiny(multi_hot=BAGS))
    spec = emb.TableSpec(s.table_sizes, s.dim, 2)
    plan = shards.plan_shards(spec, 4, 64)
    state = emb.init_tables(spec, jax.random.PRNGKey(8))
    tables = [st["table"] for st in shards.shard_states(plan, state)]
    ids = rctr.gen_ids(s, 2**31 + 9, 0, 64)
    want = rdlrm.pooled_lookup(jnp.asarray(emb.to_logical(state, spec)["table"]),
                               rdlrm.global_rows(s, ids), s.bags)
    got = pooled_reader(plan, tables, s.bags)(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
