#!/usr/bin/env python3
"""One run of one benchmark cell: the threaded ShadowSync DLRM trainer on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's runner, compiles it (``runner.warmup()``), drives
it through the check run (``bench/program.py``), and times two short
calibration runs that set how many iterations fill ``--seconds``. The
window is one ``runner.run(iters)``: from the runner's start stamp until
the device has finished the run's work. After it, the held-out normalized
entropy of the model the run hands back is measured, the program's state is
freed, and the reference replays the check run (``bench/check.py`` decides
``correct``). The last line of standard output is one JSON object; with
``--trace 1`` the window runs under the profiler and the per-layer metrics
(``bench/metrics/<name>.py``, each a ``read(ctx)`` of a ``Context`` that
returns the number or None) replace the end-to-end ones. The configuration's
``interaction`` names ``bench/reference/interactions/<name>.py``, which the
reference's forward, its initial weights, the FLOP count and the program's
config read (``out_dim``, ``n_keys``, ``init``, ``apply``,
``forward_flops``, ``PROGRAM_KEYS``: ``dot.py`` there documents each); its
``multi_hot``, one bag size or one per table, sets the ids' layout. A
run fails when JAX finds no TPU, or fewer chips than the cell asks for,
and in set-up when a named file is missing."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# run as a script, this directory heads sys.path, where its modules would
# shadow standard ones (``trace``); the package is imported from the root
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

GB = 1e9
WINDOW = "bench.window"  # host annotation around the measured window


def require_chips(n: int):
    """The cell's chips, or exit: a run never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} TPU chips, JAX found {len(devs)}")
    return devs[:n]


def configure_process(matmul_precision: str) -> None:
    """Process-wide JAX settings, before anything is traced: the persistent
    compilation cache where the program keeps it (the checkout's
    ``.jax_cache/`` unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), for
    every program however fast it compiled; and matmuls in the precision the
    configuration file states, in every thread. The program's launcher sets
    none, and on a TPU JAX's default rounds float32 matmul inputs to
    bfloat16."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision", matmul_precision)


STALLED = 3  # exit code of a run whose runner did not finish in time


@contextlib.contextmanager
def deadline(what: str, seconds: float, runner):
    """End the process, naming the stall, if ``what`` (a ``runner.run``) has
    not returned after ``seconds``: the runner's progress, the device's
    memory and every thread's stack go to standard error, and no result is
    printed."""
    import jax

    def expire():
        mem = jax.devices()[0].memory_stats() or {}
        log(f"STALLED: {what} has not ended after {seconds:.0f}s; iterations per trainer "
            f"{getattr(runner, 'iter_count', None)}, sync count "
            f"{getattr(runner, 'sync_count', None)}, device memory in use "
            f"{mem.get('bytes_in_use', 0) / GB:.3f} GB, peak {mem.get('peak_bytes_in_use', 0) / GB:.3f} GB")
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        sys.stderr.flush()
        os._exit(STALLED)

    timer = threading.Timer(seconds, expire)
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()


@contextlib.contextmanager
def window_end(runner):
    """Stamp the moment ``runner.run`` has finished its device work.

    ``run`` ends by converting the final tables to a host copy for
    checkpoints (``EmbeddingShards.to_packed``); here that conversion is
    replaced by a wait for the run's arrays and a time stamp."""
    import jax
    from repro.embeddings import shards

    stamp = {}
    orig = shards.EmbeddingShards.to_packed

    def done(emb):
        jax.block_until_ready((runner.w, emb.states, runner.algo_state))
        stamp["t"] = time.perf_counter()

    shards.EmbeddingShards.to_packed = done
    try:
        yield stamp
    finally:
        shards.EmbeddingShards.to_packed = orig
    if "t" not in stamp:
        raise RuntimeError("runner.run ended without converting its tables: no end stamp")


class Context:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._ids = None

    def window_ids(self):
        """The ids of every batch the window trained on, in the layout of
        ``shapes.bags``: (B, F, m), or (B, sum of m_f) for bag sizes per table."""
        if self._ids is None:
            import numpy as np
            from bench.reference import ctr

            self._ids = [
                np.asarray(ctr.gen_ids(self.shapes, self.seed + i, it, self.batch_size))
                for i, n in enumerate(self.iter_count) for it in range(n)
            ]
        return self._ids


def read_metric(name: str, ctx: Context):
    from bench.cell import load_module

    return load_module(BENCH / "metrics", name).read(ctx)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CheckInputs:
    """The check run's schedule, and the reference stream's batches for it."""

    def __init__(self, shapes, traffic, teacher, seed: int):
        from bench.reference import ctr
        from bench.reference.train import schedule, touched_ids

        self.sched = schedule(traffic["trainers"], traffic["check_sweeps"])
        self.batches = {
            (ev[1], ev[2]): ctr.gen_batch(shapes, teacher, seed + ev[1], ev[2], traffic["batch_size"])
            for ev in self.sched if ev[0] == "train"
        }
        self.touched = touched_ids(shapes, list(self.batches.values()))
        self.bags = shapes.bags


def build(cell, seed: int, plant=None):
    """Set-up up to the check run: the runner, compiled, with the task's teacher."""
    from bench import program
    from bench.reference import ctr
    from bench.reference.shapes import Shapes

    tr = cell.traffic
    shapes = Shapes.from_config(cell.config)
    teacher = ctr.make_teacher(shapes, tr["task_seed"])
    runner = program.build_runner(cell.config, tr, seed)
    program.use_teacher(runner, teacher)
    if runner.emb_lr != tr["emb_lr"]:
        raise RuntimeError(f"the runner steps its tables at {runner.emb_lr}, the reference "
                           f"at the traffic file's {tr['emb_lr']}")
    if plant is not None:
        plant(runner)
    runner.warmup()
    return runner, shapes, teacher


def check_program(runner, inputs: CheckInputs):
    """The program's readings from the check run, or the reason there are none."""
    from bench import program

    try:
        with deadline("the check run", 180, runner), window_end(runner):
            return program.CheckRun(runner, inputs.batches, inputs.sched, inputs.touched,
                                    inputs.bags).run(), None
    except (program.CheckAbort, RuntimeError) as e:
        # the trainers' exceptions hold their frames, and with them tables
        runner._trainer_excs = []
        return None, f"check run failed: {type(e).__name__}: {e}"
    finally:
        runner.emb = None  # the next run builds its own tables


def check_reference(shapes, traffic, seed: int, inputs: CheckInputs, dtype=None):
    import jax.numpy as jnp

    from bench.reference.train import Hyper, replay

    hyper = Hyper(traffic["trainers"], traffic["batch_size"], traffic["lr"], traffic["emb_lr"],
                  traffic["alpha"])
    return replay(shapes, hyper, seed, inputs.batches, inputs.sched, inputs.touched,
                  dtype or jnp.float32)


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float, plant=None) -> dict:
    """One run; ``plant(runner)``, for tests, breaks the program before set-up."""
    import jax

    from bench import check, program
    from bench.peaks import peaks
    from bench.reference.train import eval_ne

    dev = jax.devices()[0]
    tr = cell.traffic
    R, B = tr["trainers"], tr["batch_size"]
    runner, shapes, teacher = build(cell, seed, plant)
    log(f"warm after {time.perf_counter() - t0:.2f}s")
    inputs = CheckInputs(shapes, tr, teacher, seed)
    prog, check_error = check_program(runner, inputs)
    log(f"check run done after {time.perf_counter() - t0:.2f}s"
        + (f": {check_error}" if check_error else ""))

    # two calibration runs: a run pays a fixed start (fresh tables, threads)
    # besides its iterations, which a single short run would count as rate
    timed = []
    for n in tr["calibration_iters"]:
        with deadline("a calibration run", 120, runner), window_end(runner) as stamp:
            out = runner.run(n)
        timed.append((sum(out["iter_count"]), stamp["t"] - out["t_start"]))
        del out
        runner.emb = None
    (n1, t1), (n2, t2) = timed
    rate = (n2 - n1) / (t2 - t1) if t2 > t1 else n2 / t2
    start = min(max(t1 - n1 / rate, 0.0), t1)
    iters = max(1, math.ceil(rate * (seconds - start) / R))
    log(f"calibrated after {time.perf_counter() - t0:.2f}s: {rate:.1f} iterations/s after a "
        f"{start:.3f}s start, {iters} per trainer in the window")

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with (deadline("the window", 4 * seconds + 30, runner), jax.profiler.TraceAnnotation(WINDOW),
              window_end(runner) as stamp):
            out = runner.run(iters)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = stamp["t"] - out["t_start"]
    setup_s = out["t_start"] - t0
    iter_count = list(out["iter_count"])
    samples = B * sum(iter_count)
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell.chips,
              "memory_peak_bytes": peak_bytes}
    log(f"window {window_s:.3f}s, {iter_count} iterations, peak {peak_bytes / GB:.3f} GB")
    t_trace = time.perf_counter()
    ne = eval_ne(shapes, program.leaf_dict(out["w"][0]),
                 program.pooled_reader(runner.plan, [s["table"] for s in runner.emb.states],
                                       shapes.bags),
                 teacher, tr["eval_seed"], tr["eval_batches"], tr["eval_batch_size"])
    counters = {k: out[k] for k in ("sync_count", "sync_rounds", "dropped_updates",
                                    "sync_restarts", "supervisor_errors")}
    log(f"counters {counters}")
    del out, runner
    gc.collect()

    e2e = {"samples_per_s": samples / window_s, "eval_ne": ne, "setup_s": setup_s}
    metrics, breakdown = {}, None
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace as xtrace

        summary = xtrace.reduce(tdir, WINDOW)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_trace:.2f}s")
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = Context(shapes=shapes, seed=seed, batch_size=B, iter_count=iter_count,
                      samples_per_s=samples / window_s, trace=summary, counters=counters,
                      memory_peak_bytes=peak_bytes, peaks=peaks(dev.device_kind))
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    gaps = check.gaps(prog, check_reference(shapes, tr, seed, inputs))
    log(f"reference done after {time.perf_counter() - t0:.2f}s")
    result = {
        "correct": check_error is None and check.verdict(gaps, cell.limits),
        "attempted": sum(iter_count),
        "failed": int(sum(counters["dropped_updates"])),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if check_error is not None:
        result["check_error"] = check_error
    result["compared"] = {k: {"value": v, "limit": cell.limits.get(k)} for k, v in gaps.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"the program under test is missing: no {ROOT / 'src' / 'repro'}")
    from bench.cell import load

    cell = load(args.workload)
    configure_process(cell.config["matmul_precision"])
    require_chips(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for k, v in result["compared"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
