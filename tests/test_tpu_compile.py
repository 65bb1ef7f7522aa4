"""Main-path kernels compiled at full ``dlrm-ctr`` width for a described TPU
v5e chip — no chip attached (the TPU compiler ships with jaxlib).

Interpret mode has no tiling rules, so only the chip's compiler can refuse a
block narrower than a lane row, ids that overflow SMEM, or a program that does
not fit HBM. These compiles catch that here. The topology is described inside
a fixture, never at import, so every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import dlrm_ctr
from repro.core.runners import _dense_flatspace
from repro.embeddings import table as emb
from repro.kernels import layout
from repro.kernels.easgd_update.ops import easgd_round_op
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op

CFG = dlrm_ctr.CONFIG
# the launcher's default step: 4 trainers x 4 threads x batch 128
N_BAGS = 4 * 4 * 128 * CFG.n_sparse_features
HBM = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _table(one_chip):
    spec = emb.spec_from_config(CFG)
    shape = (layout.lane_rows(spec.total_rows, spec.dim), layout.lane_width(spec.dim))
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)


def test_tables_are_lane_dense_at_published_width(one_chip):
    t = _table(one_chip)
    assert t.shape == (CFG.n_embedding_rows // 2, 128)  # two d=64 rows per lane row


def test_embedding_bag_compiles_at_full_width(one_chip):
    ids = jax.ShapeDtypeStruct((N_BAGS, CFG.multi_hot), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda t, i: embedding_bag_op(
        t, i, dim=CFG.embedding_dim, interpret=False, strategy="stream"))
    compiled = fn.lower(_table(one_chip), ids).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6  # the table is read in place, never copied
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < HBM


def test_sparse_adagrad_compiles_at_full_width(one_chip):
    t = _table(one_chip)
    ids = jax.ShapeDtypeStruct((N_BAGS, CFG.multi_hot), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((N_BAGS, CFG.embedding_dim), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda t, a, i, g: sparse_adagrad_op(
        t, a, i, g, lr=0.05, dim=CFG.embedding_dim, interpret=False, strategy="rows"),
        donate_argnums=(0, 1))
    compiled = fn.lower(t, t, ids, g).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    table_bytes = 4 * t.shape[0] * t.shape[1]
    assert mem.alias_size_in_bytes >= 2 * table_bytes  # table and acc updated in place
    assert mem.temp_size_in_bytes < 1e9  # sorted occurrences only, no table copy
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM


def test_easgd_round_compiles_at_dense_width(one_chip):
    n = _dense_flatspace(CFG).n_rows
    R, fired = 4, 3
    sds = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    fn = jax.jit(lambda st, ps, snap, f: easgd_round_op(st, ps, snap, f, 0.5, interpret=False),
                 donate_argnums=(0, 1))
    compiled = fn.lower(sds(R, n, 128), sds(n, 128), sds(fired, n, 128),
                        sds(fired, dt=jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
