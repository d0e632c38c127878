"""Counting-closure serving cost and all-path extraction latency.

    PYTHONPATH=src python -m benchmarks.bench_count
    PYTHONPATH=src python -m benchmarks.bench_count --smoke
    PYTHONPATH=src python -m benchmarks.bench_count --json count.json

Two sections:

[count]    count-vs-relational overhead on layered DAGs of growing width
           (every adjacent-layer pair connected, so path counts grow as
           width^depth and the uint32 planes do real carries).  Each row
           times the engine's relational closure (compile-warm cold, then
           row-cache hit) against the counting closure on the same graph
           and grammar.  ``count_cold_ms / rel_cold_ms`` is the price of
           the three-phase counting pipeline (support closure, divergence
           gfp, saturating Jacobi); the decision label shows the planner
           routing the query to the one dense counting executable
           (``...+count``).

[paths]    bounded all-path enumeration: ``QueryEngine.extract_paths``
           on the widest DAG, pulling k derivation-distinct witnesses
           through the packed DerivationIndex.  ``per_path_ms`` is the
           marginal enumeration cost once the Boolean closure is cached;
           ``index_ms`` is the one-time packing cost after a cold query.

Emits ONE JSON object with --json.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.core.semantics import SAT_COUNT, evaluate_count
from repro.engine import CompiledClosureCache, EngineConfig, Query, QueryEngine

#: unambiguous a^+ grammar: derivation counts == path counts, so the
#: closure's uint32 arithmetic is checkable against combinatorics
LINEAR = Grammar.from_text("S -> a S | a").to_cnf()

CSV_COUNT = (
    "width,depth,nodes,pairs,max_count,rel_cold_ms,rel_hit_ms,"
    "count_cold_ms,count_hit_ms,decision"
)
CSV_PATHS = "width,depth,k,index_ms,extract_ms,per_path_ms"


def layered_dag(width: int, depth: int) -> Graph:
    """depth+1 layers of ``width`` nodes, complete bipartite between
    adjacent layers: width^d distinct a-paths from layer 0 to layer d."""
    edges = []
    for d in range(depth):
        for i in range(width):
            for j in range(width):
                edges.append((d * width + i, "a", (d + 1) * width + j))
    return Graph((depth + 1) * width, edges)


def _timed(fn, warmups: int = 1) -> tuple[float, object]:
    for _ in range(warmups):
        out = fn()
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def bench_count(grid: list[tuple[int, int]], engine: str) -> list[dict]:
    plans = CompiledClosureCache()
    rows = []
    for width, depth in grid:
        graph = layered_dag(width, depth)
        q_rel = Query(LINEAR, "S")
        q_cnt = Query(LINEAR, "S", semantics="count")

        QueryEngine(  # warm the compile cache (shared `plans`)
            graph, plans=plans, config=EngineConfig(engine=engine)
        ).query_batch([q_rel, q_cnt])

        eng = QueryEngine(
            graph, plans=plans, config=EngineConfig(engine=engine)
        )
        t0 = time.perf_counter()
        rel = eng.query(q_rel)
        rel_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.query(q_rel)
        rel_hit_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        cnt = eng.query(q_cnt)
        count_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        hit = eng.query(q_cnt)
        count_hit_s = time.perf_counter() - t0

        # corner to corner: free choice at each interior layer only
        expected = width ** (depth - 1)
        top = cnt.counts[(0, (depth * width))]
        if top != min(expected, int(SAT_COUNT)):
            raise AssertionError(
                f"count mismatch at {width}x{depth}: {top} != {expected}"
            )
        if cnt.pairs != rel.pairs or hit.stats.cache != "hit":
            raise AssertionError(f"support/cache skew at {width}x{depth}")
        rows.append(
            {
                "width": width,
                "depth": depth,
                "nodes": graph.n_nodes,
                "pairs": len(cnt.pairs),
                "max_count": max(cnt.counts.values()),
                "rel_cold_s": round(rel_cold_s, 4),
                "rel_hit_s": round(rel_hit_s, 5),
                "count_cold_s": round(count_cold_s, 4),
                "count_hit_s": round(count_hit_s, 5),
                "decision": cnt.stats.planner["label"],
            }
        )
    return rows


def bench_paths(width: int, depth: int, k: int, engine: str) -> list[dict]:
    graph = layered_dag(width, depth)
    eng = QueryEngine(
        graph,
        plans=CompiledClosureCache(),
        config=EngineConfig(engine=engine),
    )
    eng.query(Query(LINEAR, "S"))  # closure cached; packing is what's left
    t0 = time.perf_counter()
    eng.extract_paths(LINEAR, "S", 0, depth * width, k=1, max_len=depth)
    index_s = time.perf_counter() - t0  # pack + first witness
    t0 = time.perf_counter()
    paths = eng.extract_paths(
        LINEAR, "S", 0, depth * width, k=k, max_len=depth
    )
    extract_s = time.perf_counter() - t0
    if len(paths) != min(k, width ** (depth - 1)):
        raise AssertionError(f"expected {k} witnesses, got {len(paths)}")
    return [
        {
            "width": width,
            "depth": depth,
            "k": len(paths),
            "index_s": round(index_s, 4),
            "extract_s": round(extract_s, 4),
            "per_path_s": round(extract_s / max(len(paths), 1), 6),
        }
    ]


def _csv(count: list[dict], paths: list[dict], rows: list[str]) -> list[str]:
    rows.append(CSV_COUNT)
    for r in count:
        rows.append(
            f"{r['width']},{r['depth']},{r['nodes']},{r['pairs']},"
            f"{r['max_count']},{r['rel_cold_s'] * 1e3:.1f},"
            f"{r['rel_hit_s'] * 1e3:.2f},{r['count_cold_s'] * 1e3:.1f},"
            f"{r['count_hit_s'] * 1e3:.2f},{r['decision']}"
        )
    rows.append(CSV_PATHS)
    for r in paths:
        rows.append(
            f"{r['width']},{r['depth']},{r['k']},{r['index_s'] * 1e3:.1f},"
            f"{r['extract_s'] * 1e3:.1f},{r['per_path_s'] * 1e3:.3f}"
        )
    return rows


def main(rows: list[str] | None = None) -> list[str]:
    """run.py-style quick section: small sizes, CSV lines returned."""
    rows = rows if rows is not None else []
    return _csv(
        bench_count([(3, 3)], "auto"),
        bench_paths(3, 3, 8, "auto"),
        rows,
    )


def cli(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--grid", type=int, nargs="+", default=[3, 3, 4, 4, 6, 4],
        help="flat (width, depth) pairs for the layered-DAG sweep",
    )
    ap.add_argument(
        "--paths-k", type=int, default=64,
        help="witnesses to enumerate in the extraction section",
    )
    ap.add_argument(
        "--engine", default="auto",
        help="engine config (auto routes through the planner)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CI config: 3x3 + 4x4 DAGs, k=16",
    )
    ap.add_argument(
        "--json", default=None, metavar="OUT", help="write JSON payload"
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.grid = [3, 3, 4, 4]
        args.paths_k = 16
    if len(args.grid) % 2:
        ap.error("--grid takes (width, depth) pairs")
    grid = list(zip(args.grid[::2], args.grid[1::2]))
    count = bench_count(grid, args.engine)
    wide, deep = grid[-1]
    paths = bench_paths(wide, deep, args.paths_k, args.engine)
    out = {"engine": args.engine, "count": count, "paths": paths}
    print("[count] counting vs relational closure on layered DAGs")
    print("[paths] bounded all-path extraction")
    print("\n".join(_csv(count, paths, [])))
    if args.json:
        Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cli()
