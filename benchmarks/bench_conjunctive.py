"""Conjunctive-closure serving cost: engine vs standalone evaluate.

    PYTHONPATH=src python -m benchmarks.bench_conjunctive
    PYTHONPATH=src python -m benchmarks.bench_conjunctive --smoke
    PYTHONPATH=src python -m benchmarks.bench_conjunctive --json conj.json

Two sections:

[anbncn]   the {a^n b^n c^n} grammar on word chains of growing n, timing
           standalone ``core.conjunctive.evaluate`` (jit-warm) against the
           engine path (compile-warm cold closure, then row-cache hit).
           The gap between ``standalone_ms`` and ``engine_cold_ms`` is the
           masked-row machinery's overhead; ``engine_hit_ms`` is what
           repeat queries actually pay.

[conjuncts] work-multiplier sweep: k independent even-length-path
           conjuncts ANDed under one start symbol, k in {1, 2, 4}, on an
           all-"a" chain.  Each row reports the planner's decision label,
           so the conjunct-count multiplier feeding ``PlanFeatures``
           is visible end to end (``...+conjunctive`` routes).

Emits ONE JSON object with --json.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.conjunctive import ConjunctiveGrammar, evaluate
from repro.core.graph import Graph
from repro.engine import CompiledClosureCache, EngineConfig, Query, QueryEngine

# {a^n b^n c^n}: S -> (AB . c^+) & (a^+ . BC) — same grammar as the test
# battery (tests/test_conjunctive.py), kept in sync by the differential.
ABC = ConjunctiveGrammar.from_rules(
    terminal_rules={"a": ["A"], "b": ["B"], "c": ["C"]},
    conjunctive_rules=[
        ("S", [("AB", "C"), ("A", "BC")]),
        ("S", [("AB", "Cp"), ("Ap", "BC")]),
        ("AB", [("A", "B")]),
        ("AB", [("A", "ABb")]),
        ("ABb", [("AB", "B")]),
        ("BC", [("B", "C")]),
        ("BC", [("B", "BCc")]),
        ("BCc", [("BC", "C")]),
        ("Cp", [("C", "C")]),
        ("Cp", [("C", "Cp")]),
        ("Ap", [("A", "A")]),
        ("Ap", [("A", "Ap")]),
    ],
)

CSV_ANBNCN = (
    "n,nodes,conjuncts,pairs,standalone_ms,engine_cold_ms,engine_hit_ms,"
    "decision"
)
CSV_SWEEP = "k,nodes,conjuncts,pairs,engine_cold_ms,decision"


def _chain(word: str) -> Graph:
    return Graph(len(word) + 1, [(i, ch, i + 1) for i, ch in enumerate(word)])


def conjunct_sweep_grammar(k: int) -> ConjunctiveGrammar:
    """k independent even-length-a-path recognizers ANDed under S.

    Per copy i:  E_i -> (A_i A_i) | (A_i O_i),  O_i -> (A_i E_i)
    (E_i = a^{2m}, m >= 1 — the fixpoint iterates ~n/2 deep), then
    S -> E_0 E_0 & ... & E_{k-1} E_{k-1}.  Copies are structurally
    identical but name-distinct, so dedupe keeps all k conjuncts and the
    closure pays the k-fold AND the planner must price.
    """
    rules = [("S", [(f"E{i}", f"E{i}") for i in range(k)])]
    for i in range(k):
        rules += [
            (f"E{i}", [(f"A{i}", f"A{i}")]),
            (f"E{i}", [(f"A{i}", f"O{i}")]),
            (f"O{i}", [(f"A{i}", f"E{i}")]),
        ]
    return ConjunctiveGrammar.from_rules(
        terminal_rules={"a": [f"A{i}" for i in range(k)]},
        conjunctive_rules=rules,
    )


def _timed(fn, warmups: int = 1) -> tuple[float, object]:
    for _ in range(warmups):
        out = fn()
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def bench_anbncn(sizes: list[int], engine: str) -> list[dict]:
    plans = CompiledClosureCache()
    rows = []
    for n in sizes:
        graph = _chain("a" * n + "b" * n + "c" * n)
        q = Query(ABC, "S", semantics="conjunctive")

        standalone_s, ref = _timed(lambda: evaluate(graph, ABC, "S"))

        QueryEngine(  # warm the compile cache (shared `plans`)
            graph, plans=plans, config=EngineConfig(engine=engine)
        ).query(q)
        eng = QueryEngine(graph, plans=plans, config=EngineConfig(engine=engine))
        t0 = time.perf_counter()
        cold = eng.query(q)
        engine_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        hit = eng.query(q)
        engine_hit_s = time.perf_counter() - t0

        if cold.pairs != ref or hit.stats.cache != "hit":
            raise AssertionError(f"engine/standalone mismatch at n={n}")
        rows.append(
            {
                "n": n,
                "nodes": graph.n_nodes,
                "conjuncts": sum(
                    len(ps) for _, ps in ABC.conj_prods
                ),
                "pairs": len(ref),
                "standalone_s": round(standalone_s, 4),
                "engine_cold_s": round(engine_cold_s, 4),
                "engine_hit_s": round(engine_hit_s, 5),
                "decision": cold.stats.planner["label"],
            }
        )
    return rows


def bench_conjunct_sweep(ks: list[int], n: int, engine: str) -> list[dict]:
    graph = _chain("a" * n)
    rows = []
    for k in ks:
        g = conjunct_sweep_grammar(k)
        q = Query(g, "S", semantics="conjunctive")
        plans = CompiledClosureCache()
        QueryEngine(
            graph, plans=plans, config=EngineConfig(engine=engine)
        ).query(q)  # compile warmup
        eng = QueryEngine(graph, plans=plans, config=EngineConfig(engine=engine))
        t0 = time.perf_counter()
        res = eng.query(q)
        engine_cold_s = time.perf_counter() - t0
        if res.pairs != evaluate(graph, g, "S"):
            raise AssertionError(f"engine/standalone mismatch at k={k}")
        rows.append(
            {
                "k": k,
                "nodes": graph.n_nodes,
                "conjuncts": sum(len(ps) for _, ps in g.conj_prods),
                "pairs": len(res.pairs),
                "engine_cold_s": round(engine_cold_s, 4),
                "decision": res.stats.planner["label"],
            }
        )
    return rows


def _csv(anbncn: list[dict], sweep: list[dict], rows: list[str]) -> list[str]:
    rows.append(CSV_ANBNCN)
    for r in anbncn:
        rows.append(
            f"{r['n']},{r['nodes']},{r['conjuncts']},{r['pairs']},"
            f"{r['standalone_s'] * 1e3:.1f},{r['engine_cold_s'] * 1e3:.1f},"
            f"{r['engine_hit_s'] * 1e3:.2f},{r['decision']}"
        )
    rows.append(CSV_SWEEP)
    for r in sweep:
        rows.append(
            f"{r['k']},{r['nodes']},{r['conjuncts']},{r['pairs']},"
            f"{r['engine_cold_s'] * 1e3:.1f},{r['decision']}"
        )
    return rows


def main(rows: list[str] | None = None) -> list[str]:
    """run.py-style quick section: small sizes, CSV lines returned."""
    rows = rows if rows is not None else []
    return _csv(
        bench_anbncn([30], "auto"),
        bench_conjunct_sweep([1, 2], 32, "auto"),
        rows,
    )


def cli(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=int, nargs="+", default=[30, 60, 120])
    ap.add_argument("--conjuncts", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument(
        "--sweep-n", type=int, default=64,
        help="all-'a' chain length of the conjunct-count sweep",
    )
    ap.add_argument(
        "--engine", default="auto",
        help="engine config (auto routes through the planner)",
    )
    ap.add_argument(
        "--smoke", action="store_true", help="tiny CI config: n=30, k<=2"
    )
    ap.add_argument(
        "--json", default=None, metavar="OUT", help="write JSON payload"
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.sizes = [30]
        args.conjuncts = [1, 2]
        args.sweep_n = 32
    anbncn = bench_anbncn(args.sizes, args.engine)
    sweep = bench_conjunct_sweep(args.conjuncts, args.sweep_n, args.engine)
    out = {"engine": args.engine, "anbncn": anbncn, "conjunct_sweep": sweep}
    print("[anbncn] engine vs standalone evaluate")
    print("[conjuncts] work-multiplier sweep")
    print("\n".join(_csv(anbncn, sweep, [])))
    if args.json:
        Path(args.json).write_text(json.dumps(out, indent=2) + "\n")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cli()
