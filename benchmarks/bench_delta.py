"""Delta-repair benchmark: row-level repair vs drop-and-recompute.

    PYTHONPATH=src python -m benchmarks.bench_delta
    PYTHONPATH=src python -m benchmarks.bench_delta --sizes 1024 --rates 0.01
    PYTHONPATH=src python -m benchmarks.bench_delta --smoke

Workload model: the bench_engine community graph (disjoint ~128-node
ontology trees, same-generation grammar) with a warm materialized closure
over one source per community.  A write batch then inserts ``rate *
n_edges`` up/down edge pairs into the warmed communities, and we compare

  repair_s     ``QueryEngine.apply_delta`` — reverse-reachability planning
               plus the warm-started masked re-closure of affected rows
               (what PR 2 ships);
  recompute_s  a fresh engine on the mutated graph re-materializing the
               same source set from scratch (what the pre-delta engine did
               on every edit, minus its compile costs — plans are shared).

Both paths are measured after a warmup pass, so no trace/compile time is
included in either number.  A delete phase measures the eviction path the
same way.  Emits ONE JSON object on stdout, shaped like bench_engine.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES

from .bench_engine import COMMUNITY, GRAMMAR, community_graph


def _time(fn) -> tuple[object, float]:
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _edit_batch(
    base: Graph, n_sources: int, rate: float, seed: int, spread: int
) -> list[tuple[int, str, int]]:
    """~rate * n_edges up/down pairs between random nodes of ``spread``
    warmed communities (new derivations land in materialized rows).

    ``spread`` models write locality: a transaction's edits cluster in a
    few entities' neighborhoods.  Repair cost tracks the number of touched
    communities (the edit's blast radius), not the edit count — scattering
    the same batch over every community is the adversarial case where
    row-level repair degrades toward drop-and-recompute.
    """
    rng = np.random.default_rng(seed)
    want = max(2, int(rate * base.n_edges))
    have = set(base.edges)
    spread = max(1, min(spread, n_sources))
    communities = rng.choice(n_sources, size=spread, replace=False)
    out: list[tuple[int, str, int]] = []
    while len(out) < want:
        off = int(communities[int(rng.integers(0, spread))]) * COMMUNITY
        c, p = rng.integers(0, COMMUNITY, size=2)
        up = (off + int(c), "up", off + int(p))
        if int(c) == int(p) or up in have:
            continue
        down = (off + int(p), "down", off + int(c))
        have.add(up), have.add(down)
        out.extend((up, down))
    return out


def bench_size(
    n: int, engine: str, rate: float, n_sources: int, spread: int, plans
) -> dict:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    base = community_graph(n)
    n_sources = min(n_sources, n // COMMUNITY)
    sources = tuple(t * COMMUNITY + 1 for t in range(n_sources))
    queries = [Query(g, "S", sources=(m,)) for m in sources]
    inserts = _edit_batch(base, n_sources, rate, seed=n, spread=spread)
    deletes = [base.edges[i] for i in range(0, 2 * len(inserts), 2)]

    def scenario(record: dict | None) -> None:
        # --- incremental path: one long-lived engine, repaired in place ---
        graph_r = Graph(base.n_nodes, list(base.edges))
        eng = QueryEngine(graph_r, plans=plans, config=EngineConfig(engine=engine))
        eng.query_batch(queries)  # warm the materialized closure
        st, repair_s = _time(lambda: eng.apply_delta(insert=list(inserts)))
        rs = eng.query_batch(queries)
        _, evict_s = _time(lambda: eng.apply_delta(delete=list(deletes)))
        rs_del, requery_s = _time(lambda: eng.query_batch(queries))

        # --- drop path: fresh engine on the same mutated graph ---
        graph_d = Graph(base.n_nodes, list(base.edges))
        graph_d.insert_edges(list(inserts))
        cold = QueryEngine(graph_d, plans=plans, config=EngineConfig(engine=engine))
        rs_cold, recompute_s = _time(lambda: cold.query_batch(queries))

        for a, b in zip(rs, rs_cold):  # differential: identical answers
            assert a.pairs == b.pairs, f"repair mismatch at n={n}"
        graph_d.delete_edges(list(deletes))
        cold2 = QueryEngine(graph_d, plans=plans, config=EngineConfig(engine=engine))
        for a, b in zip(rs_del, cold2.query_batch(queries)):
            assert a.pairs == b.pairs, f"evict mismatch at n={n}"
        if record is not None:
            record.update(
                n=n,
                n_edges=base.n_edges,
                edit_rate=rate,
                edits=len(inserts),
                repair_s=round(repair_s, 4),
                recompute_s=round(recompute_s, 4),
                speedup=round(recompute_s / max(repair_s, 1e-9), 1),
                rows_repaired=st.rows_repaired,
                repair_iters=st.repair_iters,
                delete_evict_s=round(evict_s, 4),
                delete_requery_s=round(requery_s, 4),
                hit_after_repair=all(
                    r.stats["cache"] == "hit" for r in rs
                ),
                pairs=sum(len(r.pairs) for r in rs_del),
            )

    scenario(None)  # warmup: populate every compiled-plan bucket
    out: dict = {}
    scenario(out)
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=int, nargs="+", default=[1024, 4096])
    ap.add_argument(
        "--rates", type=float, nargs="+", default=[0.001, 0.01, 0.05]
    )
    ap.add_argument("--engine", default="dense", choices=sorted(MASKED_ENGINES))
    ap.add_argument("--sources", type=int, default=8)
    ap.add_argument(
        "--spread",
        type=int,
        default=2,
        help="communities a write batch touches (edit locality)",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny CI config: n=256, one rate, 2 sources",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.sizes, args.rates, args.sources = [256], [0.01], 2
        args.spread = 1
    plans = CompiledClosureCache()
    out = {
        "engine": args.engine,
        "sources": args.sources,
        "spread": args.spread,
        "grammar": GRAMMAR,
        "results": [
            bench_size(n, args.engine, rate, args.sources, args.spread, plans)
            for n in args.sizes
            for rate in args.rates
        ],
    }
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
