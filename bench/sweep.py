#!/usr/bin/env python3
"""Find a cell's knee: run its window at several offered rates, one after
another in this process, and print one summary line per rate.

    python3 bench/sweep.py --workload rel16k-rw --rates 2 4 6 8 --seconds 30

The knee is the highest rate at which no read is shed and the backlog
does not grow over the window: the last answer comes soon after the
window's close (``drain_s``), and reads due in its last third wait no
longer than those in its first.  The cell's traffic file is then set at
4/5 of it.  Each line: rate, answered and shed reads, read p50 / p95 and
write p50 (ms), ``drain_s``, and the median read latency of the last
third over that of the first.  Needs a TPU, as ``run.py`` does.
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


def trend(dump: dict, seconds: float) -> float:
    """Median latency of reads due in the window's last third over that
    of reads due in its first third (missing reads count as latest)."""
    from bench.harness import quantile

    def lat(lo, hi):
        return [(r["done"] if r["done"] is not None else float("inf"))
                - r["due"] for r in dump["reads"] if lo <= r["due"] < hi]

    first = quantile(lat(0, seconds / 3), 0.5)
    last = quantile(lat(2 * seconds / 3, seconds), 0.5)
    return last / first if first > 0 else float("inf")


def write_p50_ms(dump: dict) -> float:
    """Median write latency (ms) from when each write was due to its
    acknowledgement (a lost write counts as latest)."""
    from bench.harness import quantile

    return 1e3 * quantile([(w["done"] if w["done"] is not None
                            else float("inf")) - w["due"]
                           for w in dump["writes"]], 0.5)


def main(argv=None) -> int:
    from bench.harness import OUT, run_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for k, rate in enumerate(args.rates):
        dump = OUT / f"sweep-{args.workload}-{rate}.json"
        line = run_cell(args.workload, args.seed + k, args.seconds, False,
                        t_start=T_START if k == 0 else None, rate=rate,
                        dump=dump)
        d = json.loads(dump.read_text())
        m = {k: v["value"] for k, v in line["metrics"].items()}
        w = line["window"]
        print(json.dumps({
            "rate": rate, "correct": line["correct"],
            "reads": w["reads"], "shed": w["shed"],
            "read_p50_ms": m.get("read_p50_ms"),
            "read_p95_ms": m.get("read_p95_ms"),
            "write_p50_ms": write_p50_ms(d),
            "drain_s": w["drain_s"],
            "trend": trend(d, args.seconds),
            "setup_s": m.get("setup_s"),  # the first counts the imports
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
