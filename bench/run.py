#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload rel16k-rw --seed 7 --seconds 30 --trace 0

Needs a TPU with the cell's chips: on any other platform, or with fewer
chips, it exits nonzero and prints no result.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; its last key, ``checks``, gives each number compared with
its limit, which are also the last lines of standard error.

``--control <fault>`` plants a fault of ``bench/faults.py`` under the
timed path (the control runs: ``correct`` must come out false).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench.faults import FAULTS
    from bench.harness import SetupError, run_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=FAULTS)
    args = ap.parse_args(argv)
    try:
        line = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, fault=args.control,
        )
    except (SetupError, FileNotFoundError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
