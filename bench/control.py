#!/usr/bin/env python3
"""The readings a cell's comparison limits are set from (run on the chip).

    python3 bench/control.py --workload <cell> --seeds 12 --faults 3 --first-seed <n>

For each seed it prints the three gaps of ``bench/check.py`` twice: the
program's (its check run against the float32 reference) and the control's
(the reference itself computed in bfloat16, put in the program's place). Then
it plants each fault of ``bench/faults.py`` in the program and prints the
program's gaps on the first ``--faults`` seeds. One JSON object per line; the
benchmark's own runs never run this."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def readings(cell, seeds, fault_seeds):
    """Yield one dict per reading: program and control per seed, then faults."""
    import jax.numpy as jnp

    from bench import check
    from bench.faults import FAULTS
    from bench.run import CheckInputs, build, check_program, check_reference

    tr = cell.traffic
    runner, shapes, teacher = build(cell, seeds[0])
    refs = {}
    for s in seeds:
        runner.seed = s  # the same compiled runner, driven from another seed
        inputs = CheckInputs(shapes, tr, teacher, s)
        prog, err = check_program(runner, inputs)
        ref = refs[s] = check_reference(shapes, tr, s, inputs)
        ctl = check_reference(shapes, tr, s, inputs, jnp.bfloat16)
        yield {"seed": s, "who": "program", "gaps": check.gaps(prog, ref), "error": err}
        yield {"seed": s, "who": "control", "gaps": check.gaps(ctl, ref), "error": None}
    del runner
    for name, plant in FAULTS.items() if fault_seeds else ():
        runner, _, _ = build(cell, fault_seeds[0], plant)
        for s in fault_seeds:
            runner.seed = s
            inputs = CheckInputs(shapes, tr, teacher, s)
            prog, err = check_program(runner, inputs)
            yield {"seed": s, "who": name, "gaps": check.gaps(prog, refs[s]), "error": err}
        del runner


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--step", type=int, default=7919, help="between two seeds")
    args = ap.parse_args(argv)
    from bench.cell import load
    from bench.run import configure_process, require_chips

    cell = load(args.workload)
    configure_process(cell.config["matmul_precision"])
    require_chips(cell.chips)
    seeds = [args.first_seed + args.step * k for k in range(args.seeds)]
    for r in readings(cell, seeds, seeds[: args.faults]):
        print(json.dumps(r), flush=True)
    print(json.dumps({"done_s": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
