"""Paper Tables 1-2 analog: Query 1 / Query 2 over the ontology graph suite.

Columns mirror the paper: #triples (edge pairs), #results, and per
implementation the wall time — here the Hellings worklist baseline (the
GLL-class algorithm the paper compares against) vs our matrix engines
(dense MXU-saturation, frontier incremental) on CPU.  The GPU speedups of
the paper translate to the TPU dry-run/roofline path (EXPERIMENTS.md);
this benchmark demonstrates algorithmic-level parity + the engine choices.
"""
from __future__ import annotations

import time

import numpy as np

from repro.baselines import hellings_cfpq
from repro.core import closure
from repro.core.grammar import query1_grammar, query2_grammar
from repro.core.graph import PAPER_TABLE_GRAPHS, paper_table_graph
from repro.core.matrices import (
    ProductionTables,
    init_matrix,
    relations_from_matrix,
)

GRAPHS = list(PAPER_TABLE_GRAPHS) + ["g1", "g2", "g3"]


def _time(fn, reps=1):
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


#: matrix engines run where the padded n^3 is CPU-tractable; larger graphs
#: get the worklist only ("-" like the paper's dGPU column on g1-g3) — the
#: dense path's home is the MXU (see EXPERIMENTS.md §Roofline for those).
MATRIX_ENGINE_MAX_N = 768


def run_query(name: str, qgram, rows: list[str]) -> None:
    for gname in GRAPHS:
        graph = paper_table_graph(gname)
        g = qgram().to_cnf()
        tables = ProductionTables.from_grammar(g)

        rel_base, t_base = _time(lambda: hellings_cfpq(graph, g))
        n_results = len(rel_base["S"])

        T0 = init_matrix(graph, g)
        if T0.shape[-1] <= MATRIX_ENGINE_MAX_N:
            closure.dense_closure(T0, tables).block_until_ready()  # compile
            Td, t_dense = _time(
                lambda: closure.dense_closure(T0, tables).block_until_ready()
            )
            closure.frontier_closure(T0, tables).block_until_ready()
            Tf, t_front = _time(
                lambda: closure.frontier_closure(T0, tables).block_until_ready()
            )
            rel_d = relations_from_matrix(np.asarray(Td), g, graph.n_nodes)["S"]
            rel_f = relations_from_matrix(np.asarray(Tf), g, graph.n_nodes)["S"]
            assert rel_d == rel_base["S"] == rel_f, gname  # "#results equal"
            dense_ms = f"{t_dense*1e3:.1f}"
            front_ms = f"{t_front*1e3:.1f}"
        else:
            dense_ms = front_ms = "-"
        rows.append(
            f"{name},{gname},{graph.n_edges},{n_results},"
            f"{t_base*1e3:.1f},{dense_ms},{front_ms}"
        )


def main(rows: list[str] | None = None) -> list[str]:
    rows = rows if rows is not None else []
    rows.append(
        "query,graph,n_edges,n_results,hellings_ms,dense_ms,frontier_ms"
    )
    run_query("Q1", query1_grammar, rows)
    run_query("Q2", query2_grammar, rows)
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("\n".join(main()))
