"""The compiled click stream (``repro.data.ctr.gen_batch``): bit for bit the
eager maths, one program per batch shape, the same batches from threads."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import dlrm_ctr
from repro.data import ctr

FIELDS = ("dense", "sparse", "labels")


def eager_batch(cfg, teacher, seed, batch_idx, batch_size):
    """The stream's definition, drawn op by op: keys, ids, clicks."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), batch_idx)
    kd, ks, kl = jax.random.split(key, 3)
    F, m = cfg.n_sparse_features, cfg.multi_hot
    dense = jax.random.normal(kd, (batch_size, cfg.n_dense_features))
    sizes = jnp.asarray(cfg.table_sizes)
    u = jax.random.uniform(ks, (batch_size, F, m))
    idx = jnp.minimum((u * u * sizes[None, :, None]).astype(jnp.int32), sizes[None, :, None] - 1)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(cfg.table_sizes)[:-1]]).astype(np.int32))
    rows = idx + offsets[None, :, None]
    latent = jnp.sum(jnp.take(teacher.true_rows, rows, axis=0), axis=(1, 2))
    latent = latent / (F * m) + dense @ teacher.w_dense
    score = jnp.tanh(latent) @ teacher.w_out + teacher.bias
    labels = jax.random.bernoulli(kl, jax.nn.sigmoid(score)).astype(jnp.float32)
    return {"dense": dense, "sparse": idx, "labels": labels}


@pytest.fixture(scope="module")
def cfg():
    return dlrm_ctr.tiny()


@pytest.fixture(scope="module")
def teacher(cfg):
    return ctr.make_teacher(cfg, seed=5)


@pytest.mark.parametrize("batch_size", [32, 128])
@pytest.mark.parametrize("batch_idx", [0, 5, 41])
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 7])
def test_compiled_batch_is_the_eager_maths_bit_for_bit(cfg, teacher, seed, batch_idx, batch_size):
    got = ctr.gen_batch(cfg, teacher, seed, batch_idx, batch_size)
    want = eager_batch(cfg, teacher, seed, batch_idx, batch_size)
    for k in FIELDS:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


def test_one_program_per_batch_shape(cfg, teacher):
    before = ctr.generator_programs()
    for seed in (11, 2**31 + 11):
        for i in range(10):
            ctr.gen_batch(cfg, teacher, seed, i, 24)
    assert ctr.generator_programs() - before == 1


def test_threads_drawing_at_once_get_the_serial_batches(cfg, teacher):
    n_threads, n_batches, seed = 4, 8, 2**31 + 11
    serial = {(t, i): ctr.gen_batch(cfg, teacher, seed + t, i, 32)
              for t in range(n_threads) for i in range(n_batches)}
    drawn = {}
    start = threading.Barrier(n_threads)

    def work(t):
        start.wait()
        for i in range(n_batches):
            drawn[(t, i)] = ctr.gen_batch(cfg, teacher, seed + t, i, 32)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive()
    assert drawn.keys() == serial.keys()
    for key, want in serial.items():
        for k in FIELDS:
            np.testing.assert_array_equal(np.asarray(drawn[key][k]), np.asarray(want[k]))
