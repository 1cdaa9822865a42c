#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process.

    python3 perfbench/readings.py --workload sim.multitask \\
        --seeds 11,12,13 --control-seeds 3

For each seed: the cell's own calls at its own size, as many as a run
checks, then the numbers the check compares, for the program and, on the
first ``--control-seeds`` seeds, for the control (the reference at the
next lower precision, put in the program's place).  One JSON line per
seed.  Like ``run.py`` it needs a TPU, or ``--rehearse`` on the CPU.
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def readings(workload, seeds, control_seeds, rehearse, out=sys.stdout):
    cell, config, traffic, params, _, _ = run.resolve(workload, rehearse)
    surface = run.load_module(BENCH / "surfaces" / f"{traffic['surface']}.py",
                              f"perfbench_surface_{traffic['surface']}")
    if not rehearse:
        run.enable_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if (platform == "cpu") != rehearse or platform not in ("cpu", "tpu"):
        raise SystemExit(f"readings: refusing to run on {platform} "
                         f"(rehearse={rehearse})")
    sys.path.insert(0, str(run.ROOT / "src"))
    prev = None
    rows = []
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ctx = SimpleNamespace(seed=seed, config=config, traffic=traffic,
                              params=params, span=run.Spans(False), extra={},
                              counters={}, rehearse=rehearse)
        st = surface.State(ctx, prev)
        for i in range(int(params["check_calls"])):
            surface.call(st, i)
        row = {"workload": workload, "seed": seed,
               "program": surface.numbers(st)}
        if n < control_seeds:
            row["control"] = surface.numbers(st, control=True)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), file=out, flush=True)
        rows.append(row)
        prev = st
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.control_seeds, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
