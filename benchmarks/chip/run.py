"""Chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload paper.dpp --seed 7 --seconds 40 --trace 0

from the root of a checkout, on a machine whose JAX finds the chips the
cell asks for.  It exits non-zero, printing no result, when JAX finds no
TPU.  Progress and the check go to standard error, the check's numbers
last; the last line of standard output is the result as one JSON object.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the program's spans and counters and from a
device trace of a few seconds of the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip, run_cell
    from chipbench.layout import load_benchmark, resolve

    cell = resolve(load_benchmark(), args.workload)
    try:
        run = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    for line in run.lines:
        print(line, file=sys.stderr)
    print(json.dumps(run.result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
