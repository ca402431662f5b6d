#!/usr/bin/env python3
"""A benchmark run with a fault or the control planted in the program.

    python3 bench/control.py --plant control --workload <name> \
        --seed <n> --seconds <s>

Takes ``run.py``'s arguments after ``--plant`` (default ``control``:
the reference at bfloat16 in the program's place; see ``plant.py``)
and prints the same result line, which has to read ``correct: false``.
It is never part of the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import sys
import time

T_START = time.perf_counter()

import run  # noqa: E402  (bench/ is on sys.path when run as a script)
from bench import cells, plant  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plant", default="control", choices=plant.PLANTS)
    args, rest = ap.parse_known_args(argv)
    workload = run.parse_args(rest).workload
    cell = cells.resolve(cells.load_benchmark(), workload)
    with plant.plant(cell.traffic["driver"], args.plant):
        return run.main(rest, cell=cell, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
