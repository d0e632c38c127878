"""Single-path semantics benchmark: masked vs all-pairs (T, L) closure,
witness-extraction throughput, and length-state repair vs drop-and-recompute.

    PYTHONPATH=src python -m benchmarks.bench_single_path
    PYTHONPATH=src python -m benchmarks.bench_single_path --sizes 256
    PYTHONPATH=src python -m benchmarks.bench_single_path --smoke
    PYTHONPATH=src python -m benchmarks.bench_single_path --mesh 2x1

Workload model: the bench_engine community graph (disjoint ~128-node
ontology trees, same-generation grammar), queried with
``semantics="single_path"``.  Three sections per (n, rate):

  closure     the all-pairs ``single_path_closure`` (the paper's Section 5
              algorithm, |P|·n³ min-plus per iteration) vs the engine's
              masked batch over one source per community (|P|·R²·n) — the
              tentpole's row-compaction win on the min-plus path;
  extract     batched witness reconstruction (PathExtractor) over every
              result pair, reported as per-witness latency;
  repair      ``QueryEngine.apply_delta`` repairing the cached length
              state after an insert batch of ``rate * n_edges`` edges vs a
              fresh engine recomputing the same single-path rows from
              scratch (shared compiled plans, warmup pass first — no
              trace/compile time in either number).

``--mesh DxM`` adds a distributed section: the masked-opt single-path
closure sharded over a (data=D, model=M) host mesh vs the single-device
masked engine on the same batch (re-execs itself with forced host
devices when needed, like bench_engine).

Emits ONE JSON object on stdout, shaped like bench_delta.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.core.matrices import ProductionTables, init_matrix
from repro.core.semantics import PathExtractor, single_path_closure
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES

from .bench_delta import _edit_batch
from .bench_engine import (
    COMMUNITY,
    GRAMMAR,
    bench_mesh_size,
    community_graph,
    mesh_setup,
)


def _time(fn) -> tuple[object, float]:
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def bench_size(
    n: int,
    engine: str,
    rate: float,
    n_sources: int,
    spread: int,
    plans: CompiledClosureCache,
    allpairs_cap: int,
    allpairs_memo: dict,
) -> dict:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    base = community_graph(n)
    tables = ProductionTables.from_grammar(g)
    n_sources = min(n_sources, n // COMMUNITY)
    sources = tuple(t * COMMUNITY + 1 for t in range(n_sources))
    queries = [
        Query(g, "S", sources=(m,), semantics="single_path") for m in sources
    ]
    out: dict = {"n": n, "n_edges": base.n_edges, "edit_rate": rate}

    # --- all-pairs Section 5 closure (AOT so compile time is excluded;
    #     memoized per n — the reference is rate-independent) ---
    if n <= allpairs_cap:
        if n not in allpairs_memo:
            T0 = init_matrix(base, g)
            exe = single_path_closure.lower(T0, tables).compile()
            exe(T0)[0].block_until_ready()  # warm
            _, allpairs_memo[n] = _time(
                lambda: exe(T0)[1].block_until_ready()
            )
        out["allpairs_s"] = round(allpairs_memo[n], 4)

    # --- masked batch through the service (warm plans, fresh state) ---
    QueryEngine(base, plans=plans, config=EngineConfig(engine=engine)).query_batch(queries)
    eng = QueryEngine(base, plans=plans, config=EngineConfig(engine=engine))
    rs, batch_miss_s = _time(lambda: eng.query_batch(queries))
    _, batch_hit_s = _time(lambda: eng.query_batch(queries))
    n_paths = sum(len(r.paths) for r in rs)
    out.update(
        batch_miss_s=round(batch_miss_s, 4),
        batch_hit_s=round(batch_hit_s, 6),
        active_rows=rs[0].stats["active_rows"],
        witnesses=n_paths,
    )
    if "allpairs_s" in out:
        out["speedup_vs_allpairs"] = round(
            out["allpairs_s"] / max(batch_miss_s, 1e-9), 1
        )

    # --- witness extraction alone (the host-side slice cost) ---
    (state,) = eng._states.values()
    L = state.sp_L_host
    extractor = PathExtractor(base, g)
    a0 = g.index_of("S")

    def extract_all() -> int:
        count = 0
        for m in sources:
            for j in np.nonzero(np.isfinite(L[a0, m, : base.n_nodes]))[0]:
                extractor.extract(L, "S", m, int(j))
                count += 1
        return count

    count, extract_s = _time(extract_all)
    out.update(
        extract_s=round(extract_s, 4),
        per_witness_us=round(1e6 * extract_s / max(count, 1), 1),
    )

    # --- repair vs drop-and-recompute on the cached length state ---
    inserts = _edit_batch(base, n_sources, rate, seed=n, spread=spread)

    def scenario(record: dict | None) -> None:
        graph_r = Graph(base.n_nodes, list(base.edges))
        eng_r = QueryEngine(graph_r, plans=plans, config=EngineConfig(engine=engine))
        eng_r.query_batch(queries)  # warm the materialized length state
        st, repair_s = _time(lambda: eng_r.apply_delta(insert=list(inserts)))
        rs_r = eng_r.query_batch(queries)

        graph_d = Graph(base.n_nodes, list(base.edges))
        graph_d.insert_edges(list(inserts))
        cold = QueryEngine(graph_d, plans=plans, config=EngineConfig(engine=engine))
        rs_c, recompute_s = _time(lambda: cold.query_batch(queries))
        for a, b in zip(rs_r, rs_c):  # differential: identical pair sets
            assert a.pairs == b.pairs, f"single-path repair mismatch n={n}"
        if record is not None:
            record.update(
                edits=len(inserts),
                repair_s=round(repair_s, 4),
                recompute_s=round(recompute_s, 4),
                speedup=round(recompute_s / max(repair_s, 1e-9), 1),
                rows_repaired=st.rows_repaired,
                repair_iters=st.repair_iters,
                hit_after_repair=all(
                    r.stats["cache"] == "hit" for r in rs_r
                ),
            )

    scenario(None)  # warmup: populate every compiled-plan bucket
    scenario(out)
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=int, nargs="+", default=[256, 1024])
    ap.add_argument("--rates", type=float, nargs="+", default=[0.001, 0.01])
    ap.add_argument(
        "--engine", default="dense", choices=sorted(MASKED_ENGINES)
    )
    ap.add_argument("--sources", type=int, default=4)
    ap.add_argument(
        "--spread",
        type=int,
        default=1,
        help="communities a write batch touches (edit locality)",
    )
    ap.add_argument(
        "--allpairs-cap",
        type=int,
        default=1024,
        help="skip the all-pairs min-plus reference above this n",
    )
    ap.add_argument(
        "--mesh",
        default=None,
        metavar="DxM",
        help="add a masked-opt vs single-device-masked single-path "
        "section on a (data=D, model=M) host mesh",
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
    shape = mesh_setup(args, "benchmarks.bench_single_path", argv)
    plans = CompiledClosureCache()
    allpairs_memo: dict = {}
    out = {
        "engine": args.engine,
        "sources": args.sources,
        "spread": args.spread,
        "grammar": GRAMMAR,
        "results": [
            bench_size(
                n, args.engine, rate, args.sources, args.spread, plans,
                args.allpairs_cap, allpairs_memo,
            )
            for n in args.sizes
            for rate in args.rates
        ],
    }
    if shape:
        out["mesh"] = {
            "shape": args.mesh,
            "results": [
                bench_mesh_size(n, shape, args.sources, "single_path")
                for n in args.sizes
            ],
        }
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
