#!/usr/bin/env python3
"""On-chip smoke test of the ShadowSync trainer at ``dlrm-ctr`` widths.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --four-chip  # four TPU chips: the SPMD shadow path only

One chip, in order (any failure ends the run with a non-zero exit):

  (i)   kernels at full width. On one full training batch of the default
        ``dlrm --full`` sim (4 trainers x 4 threads x batch 128) the fused
        embedding lookup and sparse-Adagrad update are compared with their
        jnp oracles, and each sync algorithm's fused kernel with its ``ref``
        at the full dense width. The lowered training step must hold a
        Mosaic kernel (``tpu_custom_call``). Then the step's compile time and
        steady step time (each step ended by ``block_until_ready``).
  (ii)  the paper's runner: ``dlrm --full --threaded --algo easgd --mode
        shadow --trainers 4 --batch-size 128`` through ``launch/train.py``'s
        ``run_dlrm``. Losses finite, background syncs landed, and no restart,
        degradation, dropped update, stager error or swallowed supervisor
        exception.
  (iii) the default sim path ``dlrm --full`` through ``run_dlrm``: finite train
        and eval loss.

``--four-chip`` runs only the ``core/spmd.py`` shadow ``train_step`` plus the
MA ``sync_step`` for the ``lm`` launcher's default reduced ``minicpm-2b``, with
4 replicas on the replica axis, one per chip, and the same steps with all 4
replicas on one chip. Losses must agree; the compiled ``train_step`` must hold
no collective (every collective on a replica-only mesh crosses replicas) and
``sync_step`` at least one.

Tolerance for every kernel-vs-oracle comparison (fp32): the largest absolute
error must be at most ``RTOL`` times the largest absolute reference value.
The last line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every phase passed. Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
RTOL = 1e-5  # fp32: normwise (max-abs) relative error, kernel vs oracle
LOSS_RTOL = 1e-4  # four-chip vs one-chip losses (partitioned reductions reorder sums)
GB = 1e9


class SmokeFailure(Exception):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def close(name: str, got, want) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape, f"{name}: shape {got.shape} != oracle {want.shape}")
    err = np.abs(got - want)
    scale = float(np.abs(want).max()) or 1.0
    rel = float(err.max()) / scale
    elem = float((err / np.maximum(np.abs(want), 1e-30)).max())
    print(f"  {name}: max_abs_err={float(err.max()):.3e} max_rel_err={rel:.3e} "
          f"(max elementwise rel {elem:.3e}) tol={RTOL:g}", flush=True)
    require(np.isfinite(got).all(), f"{name}: non-finite kernel output")
    require(rel <= RTOL, f"{name}: relative error {rel:.3e} above {RTOL:g}")


def memory(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use', 0) / GB:.3f} GB "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use', 0) / GB:.3f} GB")


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# (i) kernels at full width
# ---------------------------------------------------------------------------

def check_embedding_kernels(sim, st, batch) -> None:
    import jax
    import jax.numpy as jnp

    from repro.embeddings import table as emb
    from repro.kernels.layout import rows_per_lane_row, take_rows
    from repro.kernels.sparse_adagrad.ops import sparse_adagrad_op
    from repro.kernels.sparse_adagrad.ref import sparse_adagrad_ref

    cfg, spec, d = sim.cfg, sim.spec, sim.spec.dim
    state = st.emb_state
    idx = batch["sparse"].reshape(-1, cfg.n_sparse_features, cfg.multi_hot)
    print(f"  batch: {idx.shape[0]} examples x {cfg.n_sparse_features} features x "
          f"{cfg.multi_hot} ids; table {tuple(state['table'].shape)} lane-dense "
          f"for {spec.total_rows:,} rows of d={d}", flush=True)
    close("embedding_bag_op vs lookup_ref",
          emb.lookup(state, spec, idx), emb.lookup_ref(state, spec, idx))

    # Update: the kernel on the full table; the oracle on the touched rows
    # only (a full-table oracle would hold three more table copies).
    rows = np.asarray(emb.global_row_ids(spec, idx)).reshape(-1, cfg.multi_hot)
    uniq, remap = np.unique(rows, return_inverse=True)
    remap = remap.reshape(rows.shape)
    g = jax.random.normal(jax.random.PRNGKey(1), (rows.shape[0], d)) * 1e-2
    lr = sim.emb_lr
    new_t, new_a = sparse_adagrad_op(state["table"], state["acc"], jnp.asarray(rows), g,
                                     lr=lr, dim=d)
    u = jnp.asarray(uniq)
    want_t, want_a = sparse_adagrad_ref(
        take_rows(state["table"], u, d), take_rows(state["acc"], u, d),
        jnp.asarray(remap), g, lr)
    dup = rows.size - uniq.size
    print(f"  update: {rows.size} occurrences, {uniq.size} distinct rows ({dup} duplicates)")
    close("sparse_adagrad_op table vs sparse_adagrad_update", take_rows(new_t, u, d), want_t)
    close("sparse_adagrad_op acc vs sparse_adagrad_update", take_rows(new_a, u, d), want_a)
    # rows no occurrence touched must come back bit-identical: whole lane
    # rows, and the partner rows sharing a lane row with a touched one
    p = rows_per_lane_row(state["table"].shape[-1], d)
    touched = np.zeros(state["table"].shape[0], bool)
    touched[uniq // p] = True
    same = jax.jit(lambda a, b, t: jnp.all((a == b) | t[:, None]))
    for k, new in (("table", new_t), ("acc", new_a)):
        require(bool(same(new, state[k], jnp.asarray(touched))),
                f"sparse_adagrad_op changed an untouched lane row of {k}")
    partners = np.setdiff1d((uniq // p)[:, None] * p + np.arange(p)[None, :], uniq)
    partners = jnp.asarray(partners[partners < spec.total_rows])
    for k, new in (("table", new_t), ("acc", new_a)):
        require(bool(jnp.array_equal(take_rows(new, partners, d),
                                     take_rows(state[k], partners, d))),
                f"sparse_adagrad_op changed a row sharing a lane row with a touched one ({k})")
    print(f"  untouched rows bit-identical ({int(partners.shape[0])} lane-row partners checked)")


def check_sync_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.runners import _dense_flatspace
    from repro.kernels.bmuf_update.ops import bmuf_sync_op
    from repro.kernels.easgd_update.ops import easgd_round_op
    from repro.kernels.gossip_update.ops import gossip_round_op
    from repro.kernels.ma_update.ops import ma_sync_op, replica_mean_op

    R, n = 4, _dense_flatspace(cfg).n_rows
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    stack = jax.random.normal(keys[0], (R, n, 128))
    plane = jax.random.normal(keys[1], (n, 128))
    vel = jax.random.normal(keys[2], (n, 128)) * 0.1
    print(f"  dense flat space: R={R} x ({n}, 128) fp32", flush=True)
    cp = lambda *xs: [jnp.copy(x) for x in xs]  # the ops donate their inputs

    fired = jnp.asarray([2, 0, 3], jnp.int32)
    snap = stack[fired] + 0.5
    for name, out in (
        ("easgd_round_op", lambda pallas: easgd_round_op(
            *cp(stack, plane), snap, fired, 0.5, use_pallas=pallas)),
        ("replica_mean_op", lambda pallas: replica_mean_op(stack, use_pallas=pallas)),
        ("ma_sync_op", lambda pallas: ma_sync_op(*cp(stack), plane, 0.5, use_pallas=pallas)),
        ("bmuf_sync_op", lambda pallas: bmuf_sync_op(
            *cp(stack), plane, *cp(plane * 0.9, vel), 0.5, block_momentum=0.9,
            use_pallas=pallas)),
        ("gossip_round_op", lambda pallas: gossip_round_op(
            *cp(stack), snap, jnp.asarray([2, 0], jnp.int32), jnp.asarray([0, 1], jnp.int32),
            jnp.asarray([1, 0], jnp.int32), 0.5, use_pallas=pallas)),
    ):
        got, want = out(True), out(False)
        for i, (a, b) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
            close(f"{name}[{i}] vs ref", a, b)


def phase_kernels(dev) -> None:
    import jax

    from repro import optim
    from repro.configs import dlrm_ctr
    from repro.core.runners import HogwildSim
    from repro.core.sync import SyncConfig
    from repro.kernels.backend import resolve_interpret

    require(resolve_interpret(None) is False, "kernels would run in interpret mode on this chip")
    defaults = train_args(["dlrm", "--full"])
    sim = HogwildSim(
        dlrm_ctr.CONFIG, SyncConfig(algo=defaults.algo, mode="shadow"),
        n_trainers=defaults.trainers, n_threads=defaults.threads,
        batch_size=defaults.batch_size, optimizer=optim.make(defaults.optimizer, defaults.lr),
        seed=defaults.seed)
    st = sim.init_state()
    batches = [sim.make_batch(i) for i in range(6)]
    check_embedding_kernels(sim, st, batches[0])
    gc.collect()
    check_sync_kernels(sim.cfg)

    args = (st.w_stack, st.opt_stack, st.emb_state)
    lowered = sim._train_iter.lower(*args, batches[0])
    require("tpu_custom_call" in lowered.as_text(),
            "the lowered training step holds no Mosaic kernel (tpu_custom_call)")
    print("  lowered HogwildSim step holds tpu_custom_call; resolve_interpret(None) is False")
    t0 = time.perf_counter()
    step = lowered.compile()
    print(f"  compile_s={time.perf_counter() - t0:.3f} (HogwildSim step, full width)", flush=True)
    times = []
    for b in batches:
        t0 = time.perf_counter()
        *args, loss = step(*args, b)
        jax.block_until_ready((args, loss))
        times.append(time.perf_counter() - t0)
        require(math.isfinite(float(loss)), f"non-finite training loss {float(loss)}")
    print(f"  step_s first={times[0]:.4f} steady_median={float(np.median(times[1:])):.4f} "
          f"(R=4 x 4 threads x batch 128 = {4 * 4 * 128} examples/step)")
    print(f"  {memory(dev)}", flush=True)


# ---------------------------------------------------------------------------
# (ii) threaded runner, (iii) sim — through launch/train.py
# ---------------------------------------------------------------------------

def train_args(argv):
    from repro.launch import train
    return train.build_parser().parse_args(argv)


def phase_threaded(dev) -> None:
    from repro.launch import train

    t0 = time.perf_counter()
    out = train.run_dlrm(train_args([
        "dlrm", "--full", "--threaded", "--algo", "easgd", "--mode", "shadow",
        "--trainers", "4", "--batch-size", "128", "--iters", "30"]))
    wall = time.perf_counter() - t0
    errors = out["pipeline_stats"].get("worker_errors", 0)
    print(f"  wall_s={wall:.1f} (warmup compiles included) run_wall_s={out['wall_s']:.3f} "
          f"eps={out['eps']:.1f} sync_count={out['sync_count']} sync_rounds={out['sync_rounds']} "
          f"sync_restarts={out['sync_restarts']} sync_degraded={out['sync_degraded']} "
          f"dropped_updates={out['dropped_updates']} pipeline_worker_errors={errors} "
          f"supervisor_errors={out['supervisor_errors']}")
    require(all(math.isfinite(x) for x in out["train_loss"]), f"non-finite losses {out['train_loss']}")
    require(out["sync_count"] > 0, "no background sync landed")
    require(out["sync_restarts"] == 0, f"shadow thread restarted {out['sync_restarts']} times")
    require(not out["sync_degraded"], "sync engine degraded")
    require(not any(out["dropped_updates"]), f"dropped embedding updates {out['dropped_updates']}")
    require(errors == 0, f"{errors} pipeline stager errors")
    require(out["supervisor_errors"]["count"] == 0,
            f"supervisor swallowed exceptions: {out['supervisor_errors']}")
    print(f"  {memory(dev)}", flush=True)


def phase_sim(dev) -> None:
    from repro.launch import train

    out = train.run_dlrm(train_args(["dlrm", "--full", "--iters", "5", "--eval-batches", "2",
                                     "--log-every", "0"]))
    require(math.isfinite(out["final_train"]) and math.isfinite(out["eval"]),
            f"non-finite sim losses {out}")
    print(f"  final_train={out['final_train']:.5f} eval={out['eval']:.5f}")
    print(f"  {memory(dev)}", flush=True)


# ---------------------------------------------------------------------------
# --four-chip: the SPMD shadow path
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")


def collectives(hlo: str) -> int:
    return sum(line.count(f" {c}(") + line.count(f" {c}-start(")
               for line in hlo.splitlines() for c in COLLECTIVES)


def run_spmd(devices, n_steps: int):
    """Shadow train_step x n_steps, one MA sync_step, train_step x n_steps, with
    4 replicas placed on ``devices`` (4 = one per chip, 1 = all on one chip).
    Returns (per-step replica losses, train_step HLO, sync_step HLO)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro import optim
    from repro.configs.base import get_config, reduced
    from repro.core import spmd
    from repro.core.sync import SyncConfig
    from repro.data import tokens as tok

    lm = train_args(["lm"])
    cfg = reduced(get_config(lm.arch))
    R = 4
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("pod",),
                axis_types=(AxisType.Explicit,))
    on_replicas = NamedSharding(mesh, P("pod"))
    place = lambda tree: jax.tree.map(lambda x: jax.device_put(x, on_replicas), tree)
    opt = optim.make(lm.optimizer, lm.lr)
    params = spmd.init_params(cfg, jax.random.PRNGKey(lm.seed))
    stack = place(jax.tree.map(lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), params))
    opt_stack = place(jax.tree.map(lambda x: jnp.broadcast_to(x[None], (R,) + x.shape),
                                   opt.init(params)))
    trans = tok.make_transition(cfg.vocab_size, seed=lm.seed)
    batches = [place(jax.tree.map(lambda x: x.reshape(R, lm.batch_size, *x.shape[1:]),
                                  tok.gen_batch(trans, lm.seed, i, lm.batch_size * R, lm.seq_len)))
               for i in range(2 * n_steps)]
    train = jax.jit(spmd.make_train_step(cfg, opt, "shadow"))
    sync = jax.jit(spmd.make_sync_step(cfg, SyncConfig(algo="ma", alpha=lm.alpha)))
    train_c = train.lower(stack, opt_stack, batches[0]).compile()
    sync_c = sync.lower(stack).compile()
    losses = []
    for i, b in enumerate(batches):
        if i == n_steps:
            stack, _ = sync_c(stack)
        stack, opt_stack, loss = train_c(stack, opt_stack, b)
        losses.append(np.asarray(loss))
    return np.stack(losses), train_c.as_text(), sync_c.as_text()


def phase_four_chip(devices) -> None:
    require(len(devices) >= 4, f"--four-chip needs 4 chips, JAX sees {len(devices)}")
    t0 = time.perf_counter()
    four, train_hlo, sync_hlo = run_spmd(devices[:4], n_steps=3)
    print(f"  4 replicas on 4 chips: {time.perf_counter() - t0:.1f}s; losses per step "
          f"(rows) x replica (cols):\n{np.array2string(four, precision=6)}", flush=True)
    t0 = time.perf_counter()
    one, _, _ = run_spmd(devices[:1], n_steps=3)
    print(f"  4 replicas on 1 chip: {time.perf_counter() - t0:.1f}s; losses:\n"
          f"{np.array2string(one, precision=6)}", flush=True)
    n_train, n_sync = collectives(train_hlo), collectives(sync_hlo)
    diff = float(np.abs(four - one).max() / np.abs(one).max())
    print(f"  loss agreement: max relative difference {diff:.3e} (tol {LOSS_RTOL:g})")
    print(f"  collectives across replicas: train_step={n_train} sync_step={n_sync}")
    require(np.isfinite(four).all() and np.isfinite(one).all(), "non-finite LM losses")
    require(diff <= LOSS_RTOL, f"four-chip losses differ from one-chip by {diff:.3e}")
    require(n_train == 0, f"train_step holds {n_train} cross-replica collectives")
    require(n_sync >= 1, "sync_step holds no cross-replica collective")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the SPMD shadow path across four chips")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's platform is {dev.platform!r} "
              f"({dev.device_kind}); this smoke test runs only on the chip",
              file=sys.stderr)
        return 2
    entries = cache_entries(cache_dir)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir} ({entries} entries at start{', warm' if entries else ', cold'})",
          flush=True)
    phases = ([("four-chip SPMD shadow path", lambda: phase_four_chip(devices))]
              if args.four_chip else
              [("(i) kernels at full width", lambda: phase_kernels(dev)),
               ("(ii) threaded ShadowSync runner", lambda: phase_threaded(dev)),
               ("(iii) HogwildSim", lambda: phase_sim(dev))])
    t_all = time.perf_counter()
    for name, run in phases:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except SmokeFailure as e:
            print(f"chip_smoke: FAIL in {name}: {e}", file=sys.stderr)
            return 1
        gc.collect()
        print(f"   {name}: passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(f"total_s={time.perf_counter() - t_all:.1f}; compile cache {cache_dir}: "
          f"{entries} entries at start, {cache_entries(cache_dir)} at end; {memory(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
