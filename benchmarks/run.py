"""Benchmark entrypoint: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Prints CSV blocks:
  [table1-2]  Q1/Q2 over the ontology suite (paper Tables 1 & 2)
  [scaling]   graph-size scaling + fixpoint iteration counts (g1-g3 obs.)
  [kernels]   Boolean-matmul kernel micro-bench
  [engine]    single-source query engine vs all-pairs (quick sizes; the
              full n ∈ {256, 1024, 4096} sweep is `-m benchmarks.bench_engine`)
  [count]     counting closure vs relational + all-path extraction (quick
              sizes; the full sweep is `-m benchmarks.bench_count`)

Aggregation mode (CI bench-smoke lane; OBSERVABILITY.md):

    PYTHONPATH=src python -m benchmarks.run \
        --aggregate BENCH_serving.json --inputs serving.json metrics.json

folds per-bench JSON payloads into one history file keyed by git SHA, so
successive CI runs accrete comparable entries instead of overwriting:

    {"schema": 1,
     "entries": {"<sha>": {"date": "...", "benches": {"serving": {...}}}}}
"""
from __future__ import annotations

import argparse
import json
import subprocess
import time
from pathlib import Path


def git_sha() -> str:
    """HEAD commit of the repo containing this file ("unknown" outside
    git — aggregation still works, keyed on the placeholder)."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=Path(__file__).resolve().parent,
            capture_output=True,
            text=True,
            check=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def aggregate(out_path: str, inputs: list[str]) -> dict:
    """Merge per-bench JSON files into ``out_path`` under the current git
    SHA (each input keyed by its file stem; re-running a SHA replaces its
    entry, distinct SHAs accrete a history)."""
    out = Path(out_path)
    if out.exists():
        data = json.loads(out.read_text())
    else:
        data = {"schema": 1, "entries": {}}
    benches = {
        Path(p).stem: json.loads(Path(p).read_text()) for p in inputs
    }
    data["entries"][git_sha()] = {
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "benches": benches,
    }
    out.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    return data


def run_all() -> None:
    from . import (
        bench_cfpq,
        bench_count,
        bench_engine,
        bench_kernels,
        bench_scaling,
    )

    print("[table1-2] CFPQ ontology suite (paper Tables 1-2 analog)")
    print("\n".join(bench_cfpq.main()))
    print()
    print("[scaling] graph-size scaling")
    print("\n".join(bench_scaling.main()))
    print()
    print("[kernels] boolean matmul micro-bench")
    print("\n".join(bench_kernels.main()))
    print()
    print("[engine] single-source vs all-pairs (quick)")
    bench_engine.main(["--sizes", "256", "1024"])
    print()
    print("[count] counting vs relational + all-path extraction (quick)")
    print("\n".join(bench_count.main()))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--aggregate",
        default=None,
        metavar="OUT",
        help="merge --inputs JSON files into OUT keyed by git SHA "
        "(skips running benchmarks)",
    )
    ap.add_argument(
        "--inputs", nargs="*", default=[], help="per-bench JSON files"
    )
    args = ap.parse_args(argv)
    if args.aggregate is not None:
        data = aggregate(args.aggregate, args.inputs)
        print(
            f"aggregated {len(args.inputs)} file(s) into {args.aggregate} "
            f"({len(data['entries'])} entry/ies)"
        )
        return
    run_all()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
