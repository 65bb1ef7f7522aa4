"""core/supervision + the runner's failure domains (DESIGN.md §10).

Unit layer: the ``Supervisor`` state machine driven deterministically — an
injected clock and public ``check_once`` replace the background watch loop,
so death/stall detection, exponential backoff, restart budgets, generation
fencing, and the give-up escalation are all asserted without sleeping.

Integration layer: real ``ThreadedShadowRunner`` chaos runs — the shadow
thread crashing and stalling mid-run (restarted against live membership,
sync_count strictly increasing afterwards), the restart budget exhausting
into the degradation ladder (final foreground sync at shutdown), an
embedding PS failing and rehydrating from its background snapshot (stale
reads + dropped writes counted, trainers never blocked), injected trainer
exceptions re-raised with slot provenance, and overlapping fault events
(crash + join + auto-demotion in one window) resolving without deadlock.
"""
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from repro import optim
from repro.configs import dlrm_ctr
from repro.core.membership import FaultSpec
from repro.core.runners import ThreadedShadowRunner
from repro.core.scheduler import PolicyConfig, StragglerPolicy
from repro.core.supervision import (
    SupervisionEvent,
    Supervisor,
    SupervisorConfig,
)
from repro.core.sync import SyncConfig

jax.config.update("jax_platform_name", "cpu")

# threaded chaos tests must never wedge CI: pytest-timeout enforces this
# ceiling when installed (requirements-ci.txt); locally it is a no-op marker
pytestmark = pytest.mark.timeout(120)

CFG = dlrm_ctr.tiny()


# ---------------------------------------------------------------------------
# Unit: the Supervisor state machine, deterministically
# ---------------------------------------------------------------------------

class _FakeThread:
    """Stands in for threading.Thread: only ``is_alive`` is consulted."""

    def __init__(self, alive=True):
        self.alive = alive

    def is_alive(self):
        return self.alive


class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _mk_sup(clock, **kw):
    cfg = dict(heartbeat_deadline_s=1.0, check_interval_s=0.01,
               max_restarts=2, backoff_s=0.5, backoff_factor=2.0)
    cfg.update(kw)
    return Supervisor(SupervisorConfig(**cfg), clock=clock)


class TestSupervisorUnit:
    def test_config_validation(self):
        with pytest.raises(ValueError, match="heartbeat_deadline_s"):
            SupervisorConfig(heartbeat_deadline_s=0).validate()
        with pytest.raises(ValueError, match="check_interval_s"):
            SupervisorConfig(check_interval_s=-1).validate()
        with pytest.raises(ValueError, match="max_restarts"):
            SupervisorConfig(max_restarts=-1).validate()
        with pytest.raises(ValueError, match="backoff"):
            SupervisorConfig(backoff_factor=0.5).validate()

    def test_healthy_thread_emits_nothing(self):
        clk = _Clock()
        sup = _mk_sup(clk)
        sup.register("w", _FakeThread())
        clk.t = 0.9
        sup.beat("w")
        clk.t = 1.8  # beat is only 0.9s old — inside the deadline
        assert sup.check_once() == []
        assert sup.events == []

    def test_death_detected_then_restarted_after_backoff(self):
        clk = _Clock()
        sup = _mk_sup(clk)
        spawned = []

        def restart():
            t = _FakeThread()
            spawned.append(t)
            return t

        dead = _FakeThread(alive=False)
        sup.register("w", dead, restart=restart)
        clk.t = 0.1
        evs = sup.check_once()
        assert [e.kind for e in evs] == ["death"]
        assert spawned == []  # backoff (0.5s) not yet elapsed
        clk.t = 0.3
        assert sup.check_once() == []  # still pending, still waiting
        clk.t = 0.65  # past failed_at + backoff_s
        evs = sup.check_once()
        assert [e.kind for e in evs] == ["restart"]
        assert len(spawned) == 1 and sup.thread("w") is spawned[0]
        assert sup.restarts("w") == 1
        assert not sup.is_degraded("w")

    def test_stall_detected_via_stale_heartbeat(self):
        clk = _Clock()
        sup = _mk_sup(clk)
        sup.register("w", _FakeThread(alive=True),
                     restart=lambda: _FakeThread())
        clk.t = 0.5
        sup.beat("w")
        clk.t = 1.4
        assert sup.check_once() == []  # 0.9s stale < 1.0s deadline
        clk.t = 1.6
        evs = sup.check_once()
        assert [e.kind for e in evs] == ["stall"]
        assert "stale" in evs[0].reason

    def test_beats_prevent_stall_forever(self):
        clk = _Clock()
        sup = _mk_sup(clk)
        sup.register("w", _FakeThread(alive=True))
        for i in range(50):
            clk.t += 0.9
            sup.beat("w")
            assert sup.check_once() == []

    def test_generation_bumps_on_restart_fencing_zombies(self):
        clk = _Clock()
        sup = _mk_sup(clk, backoff_s=0.0)
        sup.register("w", _FakeThread(alive=True),
                     restart=lambda: _FakeThread())
        gen0 = sup.generation("w")
        clk.t = 2.0  # heartbeat stale
        evs = sup.check_once()
        assert [e.kind for e in evs] == ["stall", "restart"]
        # the zombie (still alive!) sees itself superseded via the token
        assert sup.generation("w") == gen0 + 1

    def test_budget_exhausts_into_single_give_up(self):
        clk = _Clock()
        gave_up = []
        sup = _mk_sup(clk, max_restarts=2, backoff_s=0.0, backoff_factor=1.0)
        sup.register("w", _FakeThread(alive=False),
                     restart=lambda: _FakeThread(alive=False),
                     on_give_up=gave_up.append)
        kinds = []
        for _ in range(10):
            clk.t += 1.0
            kinds += [e.kind for e in sup.check_once()]
        # 2 restart attempts, then exactly one degraded escalation, then quiet
        assert kinds.count("restart") == 2
        assert kinds.count("degraded") == 1
        assert gave_up == ["w"]
        assert sup.is_degraded("w")
        assert sup.degraded_names() == ["w"]

    def test_watch_only_death_degrades_without_restart(self):
        clk = _Clock()
        gave_up = []
        sup = _mk_sup(clk)
        sup.register("w", _FakeThread(alive=False), on_give_up=gave_up.append)
        clk.t = 0.1
        evs = sup.check_once()
        assert [e.kind for e in evs] == ["death", "degraded"]
        assert "watch-only" in evs[1].reason
        assert gave_up == ["w"]

    def test_deregister_stops_watching(self):
        clk = _Clock()
        sup = _mk_sup(clk)
        sup.register("w", _FakeThread(alive=False))
        sup.deregister("w")
        clk.t = 5.0
        assert sup.check_once() == []
        assert sup.thread("w") is None

    def test_duplicate_registration_rejected(self):
        sup = _mk_sup(_Clock())
        sup.register("w", _FakeThread())
        with pytest.raises(ValueError, match="already supervised"):
            sup.register("w", _FakeThread())

    def test_exponential_backoff_schedule(self):
        clk = _Clock()
        spawned = []

        def restart():
            t = _FakeThread(alive=False)  # crash-loop: replacement dies too
            spawned.append(clk.t)
            return t

        sup = _mk_sup(clk, max_restarts=3, backoff_s=1.0, backoff_factor=2.0)
        sup.register("w", _FakeThread(alive=False), restart=restart)
        clk.t = 0.0
        sup.check_once()  # death at t=0 (failed_at anchors here)
        for t in np.arange(0.1, 12.0, 0.1):
            clk.t = float(t)
            sup.check_once()
        # attempt k waits backoff_s * factor**(restarts): 1s, then the
        # replacement's death re-anchors and waits 2s, then 4s
        assert len(spawned) == 3
        assert spawned[0] == pytest.approx(1.0, abs=0.11)
        gaps = np.diff([0.0] + spawned)
        assert gaps[1] >= 2.0 and gaps[2] >= 4.0

    def test_watch_loop_runs_tick_and_detects(self):
        """The real background loop (no injected clock): a registered thread
        that exits is detected and the tick callback keeps firing."""
        ticks = []
        sup = Supervisor(SupervisorConfig(heartbeat_deadline_s=5.0,
                                          check_interval_s=0.005),
                         tick=lambda: ticks.append(1))
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()
        sup.register("gone", t)
        sup.start()
        with pytest.raises(RuntimeError, match="already started"):
            sup.start()
        deadline = time.perf_counter() + 2.0
        while (not any(e.kind == "death" for e in sup.events)
               and time.perf_counter() < deadline):
            time.sleep(0.005)
        sup.stop()
        assert any(e.kind == "death" for e in sup.events)
        assert any(e.kind == "degraded" for e in sup.events)  # watch-only
        assert len(ticks) >= 1

    def test_watch_loop_records_what_it_survives(self):
        """A tick that raises does not kill the loop, and is never silent:
        the count and the latest exception are kept for the run report."""
        ticks = []

        def bad_tick():
            ticks.append(1)
            raise ValueError(f"tick {len(ticks)} failed")

        sup = Supervisor(SupervisorConfig(check_interval_s=0.005), tick=bad_tick)
        assert sup.swallowed_errors() == {"count": 0, "last": None}
        sup.start()
        deadline = time.perf_counter() + 2.0
        while len(ticks) < 3 and time.perf_counter() < deadline:
            time.sleep(0.005)
        sup.stop()
        rec = sup.swallowed_errors()
        assert rec["count"] == len(ticks) >= 3  # the loop outlived every failure
        assert rec["last"] == repr(ValueError(f"tick {len(ticks)} failed"))

    def test_event_record_shape(self):
        ev = SupervisionEvent("stall", "shadow", 1.5, "why")
        assert (ev.kind, ev.name, ev.t, ev.reason) == ("stall", "shadow",
                                                       1.5, "why")


# ---------------------------------------------------------------------------
# Integration: ThreadedShadowRunner chaos
# ---------------------------------------------------------------------------

SNAPPY = SupervisorConfig(heartbeat_deadline_s=0.5, check_interval_s=0.01,
                          backoff_s=0.05, max_restarts=3)


def _runner(mode="shadow", fault=None, sup_cfg=SNAPPY, **kw):
    r = ThreadedShadowRunner(
        CFG, SyncConfig(algo="easgd", alpha=0.5, mode=mode, gap=3),
        n_trainers=3, batch_size=32, optimizer=optim.adagrad(0.02),
        sync_sleep_s=0.01, fault_spec=fault, supervisor_config=sup_cfg, **kw)
    r.warmup()
    return r


class TestRunnerChaos:
    @pytest.fixture(scope="class", autouse=True)
    def warmup(self):
        _runner().run(2)

    def test_sync_crash_restart_resumes_syncing(self):
        """The tentpole acceptance: the sync thread dies, the supervisor
        restarts it against live membership, and sync_count STRICTLY
        increases post-restart."""
        out = _runner(fault=FaultSpec(sync_crash_at=2)).run(40)
        assert out["sync_restarts"] >= 1
        assert out["sync_count_at_restart"], "restart bookkeeping missing"
        assert out["sync_count"] > out["sync_count_at_restart"][0]
        kinds = [e.kind for e in out["supervision_events"]]
        assert "death" in kinds and "restart" in kinds
        assert not out["sync_degraded"]
        # the watch loop itself survived nothing: its record rides the report
        assert out["supervisor_errors"] == {"count": 0, "last": None}
        # provenance reached the membership log too
        assert any(e.kind == "sync_restart" for e in out["membership_events"])
        # and the cohort trained to completion regardless
        assert out["iter_count"] == [40, 40, 40]

    def test_sync_stall_fenced_and_restarted(self):
        """A stalled-but-alive shadow thread: detected via stale heartbeat,
        a replacement spawned, the zombie fenced out by its generation.
        Trainers carry a per-iteration sleep so the run comfortably outlives
        the 0.5s heartbeat deadline the detection needs to expire."""
        out = _runner(fault=FaultSpec(
            sync_stall_at=2, sync_stall_s=1.5,
            straggler_sleep_s={i: 0.03 for i in range(3)})).run(40)
        kinds = [e.kind for e in out["supervision_events"]]
        assert "stall" in kinds and "restart" in kinds
        assert out["sync_restarts"] >= 1
        assert out["sync_count"] > out["sync_count_at_restart"][0]
        assert out["iter_count"] == [40, 40, 40]

    def test_restart_budget_exhausted_degrades_with_final_sync(self):
        """Degradation ladder: budget 0 means the first death escalates —
        training continues locally, the membership log records ``degraded``
        with provenance, and shutdown forces one foreground sync."""
        cfg = SupervisorConfig(heartbeat_deadline_s=0.5,
                               check_interval_s=0.01, backoff_s=0.02,
                               max_restarts=0)
        out = _runner(fault=FaultSpec(sync_crash_at=1), sup_cfg=cfg).run(24)
        assert out["sync_degraded"]
        assert out["sync_restarts"] == 0
        assert out["final_foreground_sync"]
        deg = [e for e in out["membership_events"] if e.kind == "degraded"]
        assert deg and "restart budget exhausted" in deg[0].reason
        assert out["iter_count"] == [24, 24, 24]  # training never blocked
        assert out["sync_count"] >= 1  # the forced shutdown sync landed

    def test_ps_fail_serves_snapshot_and_rehydrates(self):
        """PS failure domain: lookups fall back to the background snapshot
        (counted), writes retry then drop (counted), recovery rehydrates
        within the provisioning delay, training never blocks."""
        out = _runner(fault=FaultSpec(ps_fail_at={0: 4},
                                      ps_recover_after_s=0.2)).run(40)
        kinds = [(e.kind, e.shard) for e in out["shard_events"]]
        assert ("ps_fail", 0) in kinds and ("ps_recover", 0) in kinds
        assert out["stale_lookups"][0] >= 1  # snapshot reads happened
        assert out["dropped_updates"][0] >= 1  # bounded-staleness cost paid
        # only the failed shard paid it
        assert sum(out["dropped_updates"][1:]) == 0
        assert out["iter_count"] == [40, 40, 40]
        notes = [e.kind for e in out["membership_events"]]
        assert "ps_fail" in notes and "ps_recover" in notes
        # the returned packed state reflects a healthy (rehydrated) substrate
        assert out["emb_state"]["table"].shape[0] > 0

    def test_ps_fail_in_fixed_rate_mode(self):
        """No shadow thread to take snapshots: the supervisor's watch loop
        takes them, and the same fail/recover cycle holds at the barrier."""
        out = _runner(mode="fixed_rate",
                      fault=FaultSpec(ps_fail_at={0: 4},
                                      ps_recover_after_s=0.2)).run(24)
        kinds = [(e.kind, e.shard) for e in out["shard_events"]]
        assert ("ps_fail", 0) in kinds and ("ps_recover", 0) in kinds
        assert out["iter_count"] == [24, 24, 24]
        assert out["sync_count"] >= 3  # the barrier kept firing throughout

    def test_trainer_exception_reraised_with_slot_provenance(self):
        """Satellite: a dying trainer thread is no longer silent — the run
        raises with the slot named, and membership recorded the failure."""
        r = _runner(fault=FaultSpec(raise_at={1: 3}))
        with pytest.raises(RuntimeError, match=r"slot 1.*injected trainer"):
            r.run(20)
        fails = [e for e in r.membership.events if e.kind == "fail"]
        assert fails and fails[0].slot == 1
        assert "RuntimeError" in fails[0].reason

    def test_survivors_unaffected_by_trainer_exception(self):
        r = _runner(fault=FaultSpec(raise_at={2: 2}))
        with pytest.raises(RuntimeError, match="slot 2"):
            r.run(16)
        # survivors trained to completion before the re-raise
        assert r.iter_count[0] == 16 and r.iter_count[1] == 16

    def test_chaos_without_supervision_rejected(self):
        with pytest.raises(ValueError, match="supervise"):
            ThreadedShadowRunner(
                CFG, SyncConfig(algo="easgd", mode="shadow", gap=3),
                n_trainers=2, batch_size=32, optimizer=optim.adagrad(0.02),
                fault_spec=FaultSpec(sync_crash_at=1), supervise=False)

    def test_sync_chaos_in_fixed_rate_rejected(self):
        with pytest.raises(ValueError, match="fixed_rate"):
            ThreadedShadowRunner(
                CFG, SyncConfig(algo="easgd", mode="fixed_rate", gap=3),
                n_trainers=2, batch_size=32, optimizer=optim.adagrad(0.02),
                fault_spec=FaultSpec(sync_crash_at=1))

    def test_bad_ps_shard_id_rejected(self):
        with pytest.raises(ValueError, match="shard"):
            ThreadedShadowRunner(
                CFG, SyncConfig(algo="easgd", mode="shadow", gap=3),
                n_trainers=2, batch_size=32, optimizer=optim.adagrad(0.02),
                n_emb_shards=2, fault_spec=FaultSpec(ps_fail_at={9: 1}))


class TestOverlappingFaults:
    """Satellite: concurrent fault events in the same round/window must
    resolve without deadlock or double-bookkeeping."""

    @pytest.fixture(scope="class", autouse=True)
    def warmup(self):
        _runner().run(2)

    @pytest.mark.parametrize("mode", ["shadow", "fixed_rate"])
    def test_crash_join_autodemote_same_window(self, mode):
        """Slot 0 crashes, slot 2 joins, and the policy demotes the slot-1
        straggler — all inside one short window. The run must complete with
        a consistent event log and exact per-slot accounting."""
        policy = StragglerPolicy(PolicyConfig(
            eps_floor_frac=0.5, readmit_frac=0.75, window_s=0.15,
            probation_s=0.2, min_active=1), n_slots=3)
        fault = FaultSpec(crash_at={0: 6}, join_at={2: 4},
                          straggler_sleep_s={1: 0.25},
                          straggler_until={1: 8})
        r = ThreadedShadowRunner(
            CFG, SyncConfig(algo="easgd", alpha=0.5, mode=mode, gap=3),
            n_trainers=3, batch_size=32, optimizer=optim.adagrad(0.02),
            sync_sleep_s=0.01, fault_spec=fault, straggler_policy=policy,
            eps_window_s=0.25, supervisor_config=SNAPPY)
        r.warmup()
        out = r.run(30)  # would hang forever on any barrier/join bug
        ev_kinds = [e.kind for e in out["membership_events"]]
        assert "fail" in ev_kinds     # the crash
        assert "activate" in ev_kinds  # the join completed its bootstrap
        assert out["iter_count"][0] == 6   # crashed exactly at its fault
        assert out["iter_count"][2] >= 1   # the joiner actually trained
        # no double-decrement / resurrection: each slot has at most one
        # terminal fail event, and the final mask is internally consistent
        fails = [e for e in out["membership_events"] if e.kind == "fail"]
        assert len([e for e in fails if e.slot == 0]) == 1
        assert out["sync_count"] >= 1
        if any(e.kind == "leave" for e in out["membership_events"]):
            # when the demotion landed, it carried straggler provenance
            leaves = [e for e in out["membership_events"]
                      if e.kind == "leave"]
            assert any("straggler" in e.reason for e in leaves)

    def test_shadow_join_timeout_warns_instead_of_hanging(self):
        """Satellite: a wedged sync engine at shutdown produces a VISIBLE
        warning after the bounded join, not a silent eternal hang. A 30s
        sync_sleep (which ignores ``done``) wedges the shadow loop;
        supervision is off so nothing restarts it."""
        r = ThreadedShadowRunner(
            CFG, SyncConfig(algo="easgd", alpha=0.5, mode="shadow", gap=3),
            n_trainers=2, batch_size=32, optimizer=optim.adagrad(0.02),
            sync_sleep_s=30.0, supervise=False)
        r.warmup()
        t0 = time.perf_counter()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = r.run(3)
        wall = time.perf_counter() - t0
        msgs = [str(x.message) for x in w]
        assert any("shadow thread failed to exit" in m for m in msgs), msgs
        assert wall < 25.0  # bounded: the 5s join timeout, not the 30s sleep
        assert out["iter_count"] == [3, 3]
