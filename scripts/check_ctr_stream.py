#!/usr/bin/env python
"""The compiled click stream against the benchmark's eager one, bit for bit.

Draws ``repro.data.ctr.gen_batch`` (one compiled program per batch shape) and
``bench/reference/ctr.py``'s ``gen_batch`` (the same maths, op by op) for
every ``(seed, batch_idx)`` pair of a grid, at a benchmark configuration's
widths and matmul precision, and counts the pairs whose ``dense``, ``sparse``
or ``labels`` differ. Then times both on the host: one thread, and four
threads drawing at once as the trainers do.

    PYTHONPATH=src python scripts/check_ctr_stream.py                # the Kaggle cell
    PYTHONPATH=src python scripts/check_ctr_stream.py \\
        --config bench/tests/data/dlrm-tiny.json --seeds 2 --batches 3  # a quick look

The last line of standard output is the JSON result; the exit code is 1 if
any pair differs.
"""
import argparse
import json
import os
import sys
import threading
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.reference import ctr as rctr  # noqa: E402
from bench.reference.shapes import Shapes  # noqa: E402
from repro.data import ctr  # noqa: E402

FIELDS = ("dense", "sparse", "labels")


def per_batch_ms(draw, n_threads: int, n: int) -> float:
    """Mean wall time of one batch when ``n_threads`` threads each draw ``n``."""

    def work(t):
        for i in range(n):
            draw(t, i)["labels"].block_until_ready()

    threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return (time.perf_counter() - t0) * 1e3 / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default=os.path.join(ROOT, "bench/configs/dlrm-criteo-kaggle.json"))
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--seeds", type=int, default=10, help="seeds of the grid, half above 2**31")
    ap.add_argument("--batches", type=int, default=25, help="batch indices per seed")
    ap.add_argument("--teacher-seed", type=int, default=777)
    ap.add_argument("--timed", type=int, default=50, help="batches per thread in the timing")
    args = ap.parse_args(argv)

    config = json.load(open(args.config))
    if "matmul_precision" in config:
        jax.config.update("jax_default_matmul_precision", config["matmul_precision"])
    shapes = Shapes.from_config(config)
    cfg = SimpleNamespace(
        table_sizes=shapes.table_sizes, n_dense_features=shapes.n_dense, multi_hot=shapes.multi_hot
    )
    rt = rctr.make_teacher(shapes, args.teacher_seed)
    teacher = ctr.CTRTeacher(rt["true_rows"], rt["w_dense"], rt["w_out"], rt["bias"])

    low = [3 + 7 * j for j in range(args.seeds - args.seeds // 2)]
    high = [2**31 + 11 + 13 * j for j in range(args.seeds // 2)]
    n_idx = args.batches
    idxs = ([0, 1, 5, 41] + [97 * j + 2 for j in range(1, max(n_idx - 4, 0) + 1)])[:n_idx]
    differ = []
    for seed in low + high:
        for i in idxs:
            a = ctr.gen_batch(cfg, teacher, seed, i, args.batch)
            b = rctr.gen_batch(shapes, rt, seed, i, args.batch)
            bad = [k for k in FIELDS if not np.array_equal(np.asarray(a[k]), np.asarray(b[k]))]
            if bad:
                differ.append({"seed": seed, "batch_idx": i, "fields": bad})

    seed = 2**31 + 11
    compiled = lambda t, i: ctr.gen_batch(cfg, teacher, seed + t, i, args.batch)
    eager = lambda t, i: rctr.gen_batch(shapes, rt, seed + t, i, args.batch)
    timing = {
        f"{name}_{n}_threads_ms": per_batch_ms(draw, n, args.timed)
        for name, draw in (("compiled", compiled), ("eager", eager))
        for n in (1, 4)
    }
    out = {
        "device": jax.devices()[0].device_kind,
        "batch": args.batch,
        "pairs": len(low + high) * len(idxs),
        "seeds_above_2_31": len(high),
        "differ": differ,
        "generator_programs": ctr.generator_programs(),
        **timing,
    }
    print(json.dumps(out))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
