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
"""
from __future__ import annotations


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


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run_all()
