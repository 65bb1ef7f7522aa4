"""The trace reduction, on hand-made intervals and on a small trace recorded on
a TPU v5e: the tiny DLRM under the threaded runner, 4 trainers x 2 iterations
over 4 embedding shards, inside the ``bench.window`` annotation."""
import math

import pytest

from bench import trace
from bench.tests.conftest import DATA

SMALL = DATA / "small.xplane.pb.gz"


def test_union_clip_and_gaps_by_hand():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert trace.gaps_between(trace.clip(busy, 1, 10), 1, 10) == [(3, 5), (8, 10)]


@pytest.mark.parametrize("text,name", [
    ("%sparse_adagrad_op.1 = (f32[8,128]) custom-call(s32[4096] %a)", "sparse_adagrad_op"),
    ("%copy.10 = f32[2001100,128]{1,0:T(8,128)} copy(f32[2001100,128] %t)", "copy"),
    ("%embedding_bag_op = f32[1024,64] custom-call()", "embedding_bag_op"),
    ("fusion", "fusion"),
])
def test_op_name(text, name):
    assert trace.op_name(text) == name


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(str(SMALL), "bench.window")


def test_recorded_window(small):
    assert 0 < small.busy_s < small.window_s < 5
    # every idle second of the window is named, by what a Python thread was doing
    assert math.isclose(sum(small.idle.values()), small.window_s - small.busy_s, rel_tol=1e-6)
    assert max(small.idle, key=small.idle.get).startswith("PjitFunction(")


def test_window_is_the_annotation():
    whole = trace.reduce_file(str(SMALL), None)  # no annotation: the device's own span
    small = trace.reduce_file(str(SMALL), "bench.window")
    assert small.window_s != whole.window_s
    assert small.busy_s <= whole.busy_s


def test_recorded_kernels(small):
    # one lookup+pool and one sparse-Adagrad launch per shard per trainer step
    assert small.op_count("embedding_bag_op") == 4 * 2 * 4
    assert small.op_count("sparse_adagrad_op") == 4 * 2 * 4
    assert small.op_seconds("sparse_adagrad_op") > 0
    assert small.op_count("easgd_pair_flat_op") > 0


def test_breakdown_shape(small):
    b = small.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for rows in b.values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(k, str) and v > 0 for k, v in rows)
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda kv: -kv[1])
