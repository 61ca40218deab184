"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload s2.batch --seed 7 --seconds 30 --trace 0

Run from the root of a checkout.  The cell is ``bench/workloads/<name>.json``
(see ``bench/README.md``).  The run builds the cell's database from data
drawn from ``--seed``, warms up every shape its traffic uses, measures for
``--seconds`` seconds, checks what the window produced against the plain
reference, and prints as its last line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, with ``--trace 1``,
``breakdown``), and last ``checks``, each compared number beside its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, from a profiler trace of the window.

It exits non-zero and prints no result when JAX's first device is not a
TPU, when the machine has fewer chips than the cell asks for, or when the
checkout holds no program (``src/repro``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: no program in this checkout (src/repro not found)",
              file=sys.stderr)
        return 2
    sys.path[:0] = [src, ROOT]
    from bench import cells
    try:
        cell = cells.load_cell(args.workload)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is "
              f"{devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from bench import harness
    harness.enable_compile_cache()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
