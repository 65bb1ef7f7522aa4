"""End-to-end training launcher (laptop-scale execution).

Two entry modes:
  dlrm  — the paper's system: n trainers x m Hogwild threads on synthetic CTR,
          ShadowSync or fixed-rate sync, EASGD/MA/BMUF. Deterministic HogwildSim
          by default; --threaded runs the real-thread Algorithm-1 runner.
  lm    — ShadowSync applied to a small LM (any --arch, reduced config) on a
          Markov token stream: replicas train independently, a host shadow loop
          dispatches the separate sync_step program in the background.

Examples:
  PYTHONPATH=src python -m repro.launch.train dlrm --algo easgd --mode shadow \
      --trainers 4 --threads 4 --iters 300
  PYTHONPATH=src python -m repro.launch.train dlrm --threaded --crash-at 2:50 \
      --straggler 1:0.02 --iters 200          # fault-injection harness
  PYTHONPATH=src python -m repro.launch.train dlrm --membership-schedule \
      "fail@60:2,join@100:2" --iters 200      # deterministic elasticity
  PYTHONPATH=src python -m repro.launch.train dlrm --threaded \
      --sync-crash-at 2 --ps-fail-at 0:50 --iters 200   # chaos drill: the
      # supervisor restarts the dead sync thread, PS 0 serves its snapshot
      # while down and rehydrates (DESIGN.md §10)
  PYTHONPATH=src python -m repro.launch.train lm --arch minicpm-2b --replicas 2 \
      --iters 100 --sync-gap 5
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import dlrm_ctr
from repro.configs.base import ARCH_IDS, get_config, reduced
from repro.core import algorithms, spmd
from repro.core.elp import elp
from repro.core.membership import FaultSpec
from repro.core.modeswitch import ModeConfig, ModeController
from repro.core.pipeline import PipelineConfig
from repro.core.runners import HogwildSim, ThreadedShadowRunner
from repro.core.scheduler import PolicyConfig, StragglerPolicy
from repro.core.sync import SyncConfig
from repro.embeddings.cache import CacheConfig
from repro.launch.compile_cache import use_compile_cache


def _parse_slot_map(spec, cast):
    """ "slot:value,slot:value" -> {int: cast}."""
    out = {}
    if spec:
        for part in spec.split(","):
            slot, val = part.split(":")
            out[int(slot)] = cast(val)
    return out


def _parse_schedule(spec):
    """ "kind@iter:slot,..." -> [(iter, kind, slot)] (e.g. "fail@60:2")."""
    events = []
    if spec:
        for part in spec.split(","):
            kind, rest = part.split("@")
            it, slot = rest.split(":")
            events.append((int(it), kind, int(slot)))
    return events or None


def run_dlrm(args) -> dict:
    cfg = dlrm_ctr.tiny(embedding_dim=args.embedding_dim) if args.tiny else dlrm_ctr.CONFIG
    # Contradictory flags fail loudly, naming BOTH sides (silently ignoring
    # one is how a benchmark measures the wrong thing):
    if args.auto_mode and args.mode is not None:
        raise SystemExit(
            "--auto-mode and --mode contradict: --auto-mode hands the "
            f"shadow/fixed_rate choice to the ModeController, but --mode "
            f"{args.mode} pins it. Drop --mode (auto runs start in "
            "fixed_rate and switch on measured dispersion) or drop "
            "--auto-mode."
        )
    if args.straggler_until and not args.straggler:
        raise SystemExit(
            "--straggler-until without --straggler does nothing: "
            "--straggler-until bounds the injected sleep that --straggler "
            "declares, and no slot has one. Add --straggler "
            '"slot:seconds" or drop --straggler-until.'
        )
    # Auto-mode runs start in fixed_rate (the homogeneous-cohort choice —
    # best quality) and let the controller earn shadow from dispersion.
    mode = "fixed_rate" if args.auto_mode else (args.mode or "shadow")
    sync_cfg = SyncConfig(
        algo=args.algo, mode=mode, gap=args.sync_gap, alpha=args.alpha, delay=args.sync_delay
    )
    opt = optim.make(args.optimizer, args.lr)
    # Tiered embedding cache (DESIGN.md §11): --cache-rows N keeps only N
    # rows of each store device-resident; --lookahead K peeks K queued
    # batches so the background prefetcher hides the cold misses.
    cache = None
    if args.cache_rows is not None:
        cache = CacheConfig(hot_rows=args.cache_rows, lookahead=args.lookahead)
    # Step pipelining (DESIGN.md §13): --pipeline-depth 2 double-buffers the
    # embedding lookups behind a read-after-write hazard check — bitwise the
    # same trajectory, overlapped wall clock.
    if args.pipeline_depth < 1:
        raise SystemExit(f"--pipeline-depth must be >= 1, got {args.pipeline_depth}")
    pipeline = PipelineConfig(depth=args.pipeline_depth) if args.pipeline_depth > 1 else None
    print(
        f"DLRM {'tiny' if args.tiny else 'full'}: {cfg.n_sparse_features} sparse features, "
        f"{cfg.n_embedding_rows:,} embedding rows; "
        f"ELP = {elp(args.batch_size, args.threads, args.trainers):,}"
        + (f"; cache hot_rows={args.cache_rows} lookahead={args.lookahead}" if cache else "")
        + (f"; pipeline depth={args.pipeline_depth}" if pipeline else "")
    )
    if args.auto_demote and not args.threaded:
        raise SystemExit(
            "--auto-demote requires --threaded: the deterministic sim has no "
            "real pace to measure — script one with "
            "core.scheduler.StragglerSchedule instead"
        )
    if args.auto_mode and not args.threaded:
        raise SystemExit(
            "--auto-mode requires --threaded: the deterministic sim has no "
            "real dispersion to measure — script one with "
            "core.modeswitch.ControllerModeSchedule instead"
        )
    chaos = (
        args.sync_crash_at is not None
        or args.sync_stall_at is not None
        or args.ps_fail_at
        or args.raise_at
    )
    if chaos and not args.threaded:
        raise SystemExit(
            "--sync-crash-at/--sync-stall-at/--ps-fail-at/--raise-at are "
            "chaos injections into the REAL threads — they require --threaded"
        )
    if args.threaded:
        fault = FaultSpec(
            straggler_sleep_s=_parse_slot_map(args.straggler, float),
            straggler_until=_parse_slot_map(args.straggler_until, int),
            crash_at=_parse_slot_map(args.crash_at, int),
            join_at=_parse_slot_map(args.join_at, int),
            raise_at=_parse_slot_map(args.raise_at, int),
            sync_crash_at=args.sync_crash_at,
            sync_stall_at=args.sync_stall_at,
            sync_stall_s=args.sync_stall_s,
            ps_fail_at=_parse_slot_map(args.ps_fail_at, int),
            ps_recover_after_s=args.ps_recover_after,
        )
        policy = None
        if args.auto_demote:
            # hysteresis: re-admission demands strictly more than marginal
            # health (readmit_frac > eps_floor_frac, or the policy rejects
            # the config as flap-prone) — readmit_frac may exceed 1.0,
            # meaning "beat the live median"
            policy = StragglerPolicy(
                PolicyConfig(
                    eps_floor_frac=args.eps_floor,
                    readmit_frac=max(args.eps_floor * 1.5, 0.75),
                    probation_s=args.probation,
                ),
                n_slots=args.trainers,
            )
        mode_ctl = None
        if args.auto_mode:
            # tuning-free sync<->async switching (DESIGN.md §14): hysteresis
            # bands + min-dwell keep the cohort from flapping between modes
            mode_ctl = ModeController(
                ModeConfig(
                    skew_high=args.skew_high,
                    skew_low=args.skew_low,
                    min_dwell_s=args.mode_dwell,
                    window_s=args.mode_window,
                    start_mode=mode,
                )
            )
        runner = ThreadedShadowRunner(
            cfg,
            sync_cfg,
            n_trainers=args.trainers,
            batch_size=args.batch_size,
            optimizer=opt,
            seed=args.seed,
            sync_sleep_s=args.sync_sleep,
            fault_spec=fault,
            straggler_policy=policy,
            cache=cache,
            pipeline=pipeline,
            mode_controller=mode_ctl,
        )
        # compile every program on throwaway state first: a first call that
        # compiles inside the run would read as a stalled thread to the
        # supervisor (heartbeat deadline) and as a straggler to the policy
        runner.warmup()
        out = runner.run(args.iters)
        if args.auto_mode:
            print(
                f"mode: final={out['mode']} switches="
                + str(
                    [
                        (round(t - out["t_start"], 3), f"{frm}->{to}")
                        for t, frm, to, _ in out["mode_transitions"]
                    ]
                )
            )
        if out["cache_stats"]:
            cs = out["cache_stats"]
            hits = cs["hit_rows"] / max(cs["hit_rows"] + cs["miss_rows"], 1)
            print(
                f"cache: hit_rate={hits:.3f} stalls={cs['stall_lookups']}"
                f"/{cs['lookups']} prefetched={cs['prefetch_rows']} "
                f"migrated={(cs['bytes_h2d'] + cs['bytes_d2h'])/1e6:.2f}MB"
            )
        if out.get("pipeline_stats"):
            ps = out["pipeline_stats"]
            print(
                f"pipeline: overlap_rate={ps['overlap_rate']:.3f} "
                f"hazard_serialized={ps['hazard_serialized']} "
                f"drains={ps['drains']}"
            )
        print(
            f"EPS={out['eps']:.0f} (window {out['eps_window']:.0f})  "
            f"avg_sync_gap={out['avg_sync_gap']:.2f} "
            f"iters/trainer={out['iter_count']} "
            f"final train loss per trainer={[round(l,4) for l in out['train_loss']]}"
        )
        if out["membership_events"]:
            print(
                "membership:",
                [
                    (e.kind, e.slot) + ((e.reason,) if e.reason else ())
                    for e in out["membership_events"]
                ],
            )
        if out["supervision_events"]:
            print("supervision:", [(e.kind, e.name, e.reason) for e in out["supervision_events"]])
            print(
                f"  sync_restarts={out['sync_restarts']} "
                f"degraded={out['sync_degraded']} "
                f"final_foreground_sync={out['final_foreground_sync']}"
            )
        if out["shard_events"]:
            print(
                "embedding PS:",
                [
                    (e.kind, e.shard) + ((e.reason,) if e.reason else ())
                    for e in out["shard_events"]
                ],
            )
            print(
                f"  dropped_updates={out['dropped_updates']} "
                f"stale_lookups={out['stale_lookups']}"
            )
        return {
            k: v
            for k, v in out.items()
            if k
            not in ("w", "emb_state", "membership_events", "supervision_events", "shard_events")
        }
    sim = HogwildSim(
        cfg,
        sync_cfg,
        n_trainers=args.trainers,
        n_threads=args.threads,
        batch_size=args.batch_size,
        optimizer=opt,
        seed=args.seed,
        schedule=_parse_schedule(args.membership_schedule),
        cache=cache,
        pipeline=pipeline,
    )
    st0 = None
    if args.restore:
        st0 = sim.load_state(args.restore)
        print(f"elastic restore <- {args.restore} (step {st0.step}, " f"now R={sim.R})")
    t0 = time.perf_counter()
    out = sim.run(args.iters, log_every=args.log_every, state=st0)
    wall = time.perf_counter() - t0
    ev = sim.evaluate(out["state"], n_batches=args.eval_batches)
    examples = out["examples"]
    print(
        f"train loss {np.mean(out['train_loss'][:10]):.5f} -> "
        f"{np.mean(out['train_loss'][-10:]):.5f}; eval {ev:.5f}; "
        f"avg_sync_gap {out['avg_sync_gap']:.2f}; EPS(sim wall) {examples/wall:.0f}"
    )
    if "cache_stats" in out:
        cs = out["cache_stats"]
        hits = cs["hit_rows"] / max(cs["hit_rows"] + cs["miss_rows"], 1)
        print(
            f"cache: hit_rate={hits:.3f} stalls={cs['stall_lookups']}"
            f"/{cs['lookups']} prefetched={cs['prefetch_rows']} "
            f"migrated={(cs['bytes_h2d'] + cs['bytes_d2h'])/1e6:.2f}MB"
        )
    if out.get("pipeline_stats"):
        ps = out["pipeline_stats"]
        print(
            f"pipeline: overlap_rate={ps['overlap_rate']:.3f} "
            f"hazard_serialized={ps['hazard_serialized']} "
            f"drains={ps['drains']}"
        )
    if args.save:
        # engine-independent elastic checkpoint: dense replicas as the named
        # pytree (not the flat engine's packed buffer) + opaque algo state
        sim.save_state(args.save, out["state"])
        print(f"checkpoint -> {args.save}")
    return {
        "final_train": float(np.mean(out["train_loss"][-10:])),
        "eval": ev,
        "avg_sync_gap": out["avg_sync_gap"],
    }


def run_lm(args) -> dict:
    from repro.data import tokens as tok

    cfg = reduced(get_config(args.arch))
    opt = optim.make(args.optimizer, args.lr)
    R = args.replicas
    sync_cfg = SyncConfig(algo=args.algo, alpha=args.alpha).validate()
    key = jax.random.PRNGKey(args.seed)
    params = spmd.init_params(cfg, key)
    stack = spmd.stack_replicas(params, R)
    stack = jax.tree.map(jnp.copy, stack)
    opt_stack = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (R,) + x.shape).copy(), opt.init(params)
    )
    train_step = jax.jit(spmd.make_train_step(cfg, opt, "shadow"))
    sync_step = jax.jit(spmd.make_sync_step(cfg, sync_cfg))
    # Opaque per-algorithm state (sync-PS copy, momentum, counter, or None).
    algo_state = algorithms.get(args.algo).init_state(params, sync_cfg)

    trans = tok.make_transition(cfg.vocab_size, seed=args.seed)
    losses = []
    t0 = time.perf_counter()
    for it in range(args.iters):
        b = tok.gen_batch(trans, args.seed, it, args.batch_size * R, args.seq_len)
        batch = jax.tree.map(lambda x: x.reshape(R, args.batch_size, *x.shape[1:]), b)
        stack, opt_stack, loss = train_step(stack, opt_stack, batch)
        losses.append(float(jnp.mean(loss)))
        # Background cadence (host loop quantization of the shadow thread).
        if (it + 1) % args.sync_gap == 0:
            stack, algo_state = sync_step(stack, algo_state)
    wall = time.perf_counter() - t0
    print(
        f"{args.arch} x{R} replicas [{args.algo}]: loss "
        f"{np.mean(losses[:5]):.4f} -> {np.mean(losses[-5:]):.4f} "
        f"({args.iters} iters, {wall:.1f}s, "
        f"EPS {args.iters*args.batch_size*R/wall:.1f})"
    )
    return {"loss_start": float(np.mean(losses[:5])), "loss_end": float(np.mean(losses[-5:]))}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    d = sub.add_parser("dlrm")
    d.add_argument("--algo", choices=list(algorithms.names()), default="easgd")
    d.add_argument(
        "--mode",
        choices=["shadow", "fixed_rate"],
        default=None,
        help="pin the sync mode (default shadow). Contradicts "
        "--auto-mode, which owns the choice at runtime",
    )
    d.add_argument("--trainers", type=int, default=4)
    d.add_argument("--threads", type=int, default=4)
    d.add_argument("--batch-size", type=int, default=128)
    d.add_argument("--iters", type=int, default=200)
    d.add_argument("--sync-gap", type=int, default=5)
    d.add_argument("--sync-delay", type=int, default=1)
    d.add_argument("--sync-sleep", type=float, default=0.0)
    d.add_argument("--alpha", type=float, default=0.5)
    d.add_argument("--lr", type=float, default=0.02)
    d.add_argument("--optimizer", default="adagrad")
    d.add_argument("--embedding-dim", type=int, default=16)
    d.add_argument("--tiny", action="store_true", default=True)
    d.add_argument("--full", dest="tiny", action="store_false")
    d.add_argument("--threaded", action="store_true")
    d.add_argument("--eval-batches", type=int, default=10)
    d.add_argument("--log-every", type=int, default=50)
    d.add_argument("--seed", type=int, default=0)
    d.add_argument("--save", default=None)
    d.add_argument(
        "--restore", default=None, help="elastic restore: checkpoint R may differ from --trainers"
    )
    d.add_argument(
        "--membership-schedule",
        default=None,
        help='deterministic elasticity (sim): "fail@60:2,join@100:2"',
    )
    d.add_argument("--crash-at", default=None, help='threaded fault injection: "slot:iter,..."')
    d.add_argument("--join-at", default=None, help='threaded mid-run join: "slot:iter,..."')
    d.add_argument(
        "--straggler", default=None, help='threaded straggler sleep seconds: "slot:0.02,..."'
    )
    d.add_argument(
        "--straggler-until",
        default=None,
        help="end of the straggler sleep, per slot local iteration:"
        ' "slot:40,..." (absent = degraded all run)',
    )
    # chaos injection into the supervised failure domains (--threaded only;
    # DESIGN.md §10): the supervisor detects/restarts/recovers, the run
    # report prints the supervision + PS event logs
    d.add_argument(
        "--raise-at",
        default=None,
        help='chaos: raise inside trainer threads, "slot:iter,..."'
        " — the run re-raises with slot provenance",
    )
    d.add_argument(
        "--sync-crash-at",
        type=int,
        default=None,
        help="chaos: kill the shadow/sync thread at this round "
        "(mode=shadow); the supervisor restarts it",
    )
    d.add_argument(
        "--sync-stall-at",
        type=int,
        default=None,
        help="chaos: wedge the shadow thread at this round; the "
        "supervisor detects the stale heartbeat and replaces "
        "it (the zombie is generation-fenced)",
    )
    d.add_argument(
        "--sync-stall-s", type=float, default=10.0, help="how long the wedged shadow thread sleeps"
    )
    d.add_argument(
        "--ps-fail-at",
        default=None,
        help='chaos: kill embedding PS shards, "shard:iter,..." — '
        "lookups serve the background snapshot, updates "
        "retry-then-drop, recovery rehydrates",
    )
    d.add_argument(
        "--ps-recover-after",
        type=float,
        default=0.25,
        help="provisioning delay before a failed PS rehydrates " "from its snapshot",
    )
    d.add_argument(
        "--auto-demote",
        action="store_true",
        help="closed-loop straggler controller (threaded only): "
        "demote a slot whose busy-clock EPS falls below "
        "--eps-floor x live median, re-admit after probation",
    )
    d.add_argument(
        "--eps-floor",
        type=float,
        default=0.5,
        help="demotion floor as a fraction of the live median EPS",
    )
    d.add_argument(
        "--probation",
        type=float,
        default=1.0,
        help="seconds a demoted slot must probe healthy before " "re-admission",
    )
    d.add_argument(
        "--auto-mode",
        action="store_true",
        help="tuning-free sync<->async switching (threaded only, "
        "DESIGN.md §14): start fixed_rate, switch the whole "
        "cohort to shadow when busy-EPS dispersion crosses "
        "--skew-high, and back once it falls to --skew-low",
    )
    d.add_argument(
        "--skew-high",
        type=float,
        default=2.0,
        help="dispersion above which fixed_rate hands off to "
        "shadow (max/median busy-EPS spread)",
    )
    d.add_argument(
        "--skew-low",
        type=float,
        default=1.3,
        help="dispersion at/below which shadow hands back to "
        "fixed_rate (must be < --skew-high: hysteresis)",
    )
    d.add_argument(
        "--mode-dwell",
        type=float,
        default=2.0,
        help="seconds a freshly entered mode is held regardless " "of the signal (anti-flap)",
    )
    d.add_argument(
        "--mode-window",
        type=float,
        default=0.5,
        help="seconds a dispersion breach must persist before " "the controller acts on it",
    )
    d.add_argument(
        "--cache-rows",
        type=int,
        default=None,
        help="tiered embedding cache: device-resident hot rows "
        "per store (absent = whole table on device)",
    )
    d.add_argument(
        "--lookahead",
        type=int,
        default=2,
        help="batches the background prefetcher peeks ahead "
        "(0 = no prefetch; cold rows stall synchronously)",
    )
    d.add_argument(
        "--pipeline-depth",
        type=int,
        default=1,
        help="step-pipeline depth (DESIGN.md §13): 2 double-"
        "buffers hazard-checked embedding lookups one step "
        "ahead; 1 = serial (bitwise-identical either way)",
    )

    l = sub.add_parser("lm")
    l.add_argument("--arch", choices=list(ARCH_IDS), default="minicpm-2b")
    l.add_argument("--algo", choices=list(algorithms.names()), default="easgd")
    l.add_argument("--replicas", type=int, default=2)
    l.add_argument("--batch-size", type=int, default=8)
    l.add_argument("--seq-len", type=int, default=128)
    l.add_argument("--iters", type=int, default=60)
    l.add_argument("--sync-gap", type=int, default=5)
    l.add_argument("--alpha", type=float, default=0.5)
    l.add_argument("--lr", type=float, default=1e-3)
    l.add_argument("--optimizer", default="adam")
    l.add_argument("--seed", type=int, default=0)
    return ap


def main():
    use_compile_cache()
    args = build_parser().parse_args()
    out = run_dlrm(args) if args.cmd == "dlrm" else run_lm(args)
    print(json.dumps(out, default=float))


if __name__ == "__main__":
    main()
