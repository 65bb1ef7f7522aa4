"""The threaded runner's profiler spans and lost-update counters.

The spans are ``jax.profiler.TraceAnnotation``s; here a recording stand-in
takes their place, so the tests see every span each thread opens and closes.
The counters are forced above zero by holding a round (or a trainer's step)
open until the other side has moved, and read zero under the fixed-rate
barrier, whose rounds run while the cohort is parked."""
import threading
import time
from collections import defaultdict

import pytest

from repro import optim
from repro.configs import dlrm_ctr
from repro.core import runners
from repro.core.runners import ThreadedShadowRunner
from repro.core.sync import SyncConfig

pytestmark = pytest.mark.timeout(300)

CFG = dlrm_ctr.tiny()
TRAINER_SPANS = ("trainer.batch", "trainer.step", "trainer.update", "trainer.loss")
ROUND_SPANS = ("shadow.capture", "shadow.round", "shadow.publish")
WAIT_S = 60.0  # bound on every wait a test adds to the runner's threads


class Recorder:
    """Stands in for ``TraceAnnotation``: logs (thread, enter/exit, name)."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        Recorder.log.append((threading.get_ident(), "enter", self.name))

    def __exit__(self, *exc):
        Recorder.log.append((threading.get_ident(), "exit", self.name))
        return False


@pytest.fixture
def spans(monkeypatch):
    """Each thread's spans, in order, as a list of names; fails on nesting."""
    Recorder.log = []
    monkeypatch.setattr(runners, "TraceAnnotation", Recorder)

    def by_thread():
        out = defaultdict(list)
        open_span = {}
        for tid, kind, name in Recorder.log:
            if kind == "enter":
                assert tid not in open_span, f"{name} opened inside {open_span[tid]}"
                open_span[tid] = name
                out[tid].append(name)
            else:
                assert open_span.pop(tid) == name
        assert not open_span, f"spans left open: {open_span}"
        return out

    return by_thread


def make(mode="shadow", trainers=2):
    return ThreadedShadowRunner(
        CFG, SyncConfig(algo="easgd", mode=mode, gap=3, alpha=0.5), n_trainers=trainers,
        batch_size=16, optimizer=optim.adagrad(0.02))


def wait_for(cond):
    deadline = time.monotonic() + WAIT_S
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.001)


def test_trainer_and_shadow_spans(spans):
    r = make()
    out = r.run(6)
    threads = spans()
    trainer = [s for s in threads.values() if s[0] == "trainer.batch"]
    shadow = [s for s in threads.values() if s[0] == "shadow.capture"]
    # one batch -> step -> update -> loss per iteration, in order, never nested
    assert sorted(len(s) // 4 for s in trainer) == sorted(out["iter_count"])
    for s in trainer:
        assert s == list(TRAINER_SPANS) * (len(s) // 4)
    assert len(shadow) == 1
    assert set(shadow[0]) == set(ROUND_SPANS) | {"shadow.upkeep"}
    assert shadow[0][:4] == [*ROUND_SPANS, "shadow.upkeep"]
    assert len(trainer) + len(shadow) == len(threads)


def test_publish_over_a_newer_step_counts_an_overwritten_step():
    r = make()
    orig = r._shadow_round

    def held_round(sub, state):
        # capture is done: let trainer 0 finish a step before the publish
        it0 = r.iter_count[0]
        res = orig(sub, state)
        wait_for(lambda: r.iter_count[0] > it0 or not r._alive[0])
        return res

    r._shadow_round = held_round
    out = r.run(30)
    assert out["overwritten_steps"][0] >= 1
    assert len(out["overwritten_steps"]) == len(out["overwritten_landings"]) == 2


def test_assignment_over_a_newer_publish_counts_an_overwritten_landing():
    r = make()
    orig = r._train_one
    held = []
    gate = threading.Lock()

    def held_step(w, *args):
        res = orig(w, *args)
        with gate:
            slot = next((j for j, x in enumerate(r.w) if x is w), None)
            hold = not held and slot is not None
            if hold:
                held.append(slot)
        if hold:
            # more than one round's landings: at least one whole round
            # published into this slot after the trainer read its plane
            s0 = r.sync_count
            wait_for(lambda: r.sync_count > s0 + r.R)
        return res

    r._train_one = held_step
    out = r.run(30)
    assert held, "no step read its slot's current plane"
    assert out["overwritten_landings"][held[0]] >= 1


def test_fixed_rate_rounds_lose_nothing_and_sit_between_trainer_spans(spans):
    r = make(mode="fixed_rate")
    out = r.run(9)
    threads = spans()
    assert out["sync_count"] > 0
    assert out["overwritten_steps"] == [0, 0]
    assert out["overwritten_landings"] == [0, 0]
    # no shadow thread: the barrier's leader runs the round on a trainer
    # thread, after the iteration's four leaf spans and before the next
    rounds = 0
    for s in threads.values():
        assert s[:4] == list(TRAINER_SPANS)
        k = 0
        while k < len(s):
            if s[k] == "trainer.batch":
                assert s[k:k + 4] == list(TRAINER_SPANS)
                k += 4
            else:
                assert s[k:k + 3] == list(ROUND_SPANS)
                rounds += 1
                k += 3
    assert rounds == out["sync_count"] // 2
