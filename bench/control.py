#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference put in the
program's place at a lower precision (``reference/<system>.py``), judged by
the same comparison a run makes.  Every seed's numbers should break a limit.

    python3 bench/control.py --workload <cell> --requests <n> \
        --seeds <s1,s2,...>

``--requests`` is how many requests of the window's stream are answered
(about what one run serves).  The benchmark's own runs do not run this; it
is how the limits' upper readings were taken, and ``bench/tests`` runs it at
a small size.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness.cell import Cell, load_cell  # noqa: E402


def readings(cell: Cell, seed: int, n_requests: int) -> dict:
    """The numbers the control's answers read for one seed."""
    data = cell.system.generate(cell.config, cell.traffic, seed)
    reqs = list(itertools.islice(
        cell.generator.requests(cell.traffic, data, seed), n_requests))
    got = cell.reference.control(data, reqs)
    return cell.reference.check(data, reqs, got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    limits = cell.config["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = readings(cell, seed, args.requests)
        broken = [k for k in limits if nums[k] > limits[k]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "numbers": nums, "limits": limits,
                          "breaks": broken}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
