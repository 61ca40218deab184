"""Readings that the limits of a cell's compared numbers are set from.

    python bench/readings.py --workload s2.batch --seeds 11,12,13 --seconds 10

For each seed, in one process: build the cell, warm up, run a window of
``--seconds`` at the cell's own load, and compare what it produced with the
reference, as a benchmark run does; on the same queries, compare the
control, the reference computed in bfloat16 in the program's place.  Prints
one JSON line per seed, then the summary: the largest program reading of
each number (the lower reading) and the smallest control reading (the
upper one).  The benchmark's own runs never run the control.

Exits non-zero, before any work, when JAX's first device is not a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seeds, seconds: float) -> dict:
    from bench import harness, reference, cells
    drv = cells.traffic(cell.traffic)
    program, control = [], []
    for seed in seeds:
        ctx = harness.build(cell, seed)
        state = drv.prepare(ctx, seconds)
        drv.warmup(ctx, state)
        record = drv.window(ctx, state, seconds)
        ctx.control = []
        got, extra = drv.check(ctx, state, record)
        ctl = reference.worst(ctx.control)
        program.append(got)
        control.append(ctl)
        print(json.dumps({"seed": seed, "program": got, "extra": extra,
                          "control": ctl, "attempted": record["attempted"],
                          "failed": record["failed"]}), flush=True)
    return {"lower": reference.worst(program),
            "upper": {k: min(c[k] for c in control)
                      for k in reference.NUMBERS}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import cells, harness
    cell = cells.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    print(json.dumps({"summary": readings(cell, seeds, args.seconds)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
