"""Readings that set a cell's correctness limits, many seeds in one process.

    python3 bench/prove.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed: one run of the cell as ``bench/run.py`` makes it (trace
off), then the control -- the plain reference with fp8 matrix-product
operands in the program's place -- on the same inputs.  Prints one JSON line per seed
with the program's readings and the control's.  A limit lies above every
program reading and below every control reading (PERF.md gives both).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402
from bench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.prepare_process()
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"bench/prove.py: {e}", file=sys.stderr)
        return 3
    t_start = T_START
    for seed in args.seeds:
        result, loop = run_cell(cell, seed, args.seconds, False, devices,
                                t_start)
        control = loop.compare(quant="fp8")
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "correct": result["correct"],
            "program": {k: c["value"] for k, c in result["checks"].items()},
            "control": control, "metrics": result["metrics"],
            "attempted": result["attempted"],
            "device": result["device"]}), flush=True)
        del loop
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
