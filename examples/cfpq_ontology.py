"""CFPQ over the paper's ontology benchmark suite (Tables 1-2 analog).

    PYTHONPATH=src python examples/cfpq_ontology.py [graph_name]

Evaluates Query 1 (same generation) and Query 2 (adjacent layers) over one
of the regenerated ontology graphs, comparing the matrix engine against the
Hellings worklist baseline, and prints the relation sizes (the paper's
#results column).
"""
import sys
import time

import numpy as np

from repro.baselines import hellings_cfpq
from repro.compile_cache import enable_compile_cache
from repro.core import closure
from repro.core.grammar import query1_grammar, query2_grammar
from repro.core.graph import paper_table_graph
from repro.core.matrices import (
    ProductionTables,
    init_matrix,
    relations_from_matrix,
)

enable_compile_cache()
name = sys.argv[1] if len(sys.argv) > 1 else "wine"
graph = paper_table_graph(name)
print(f"graph {name}: {graph.n_nodes} nodes, {graph.n_edges} edges")

for qname, qgram in (("Q1", query1_grammar), ("Q2", query2_grammar)):
    g = qgram().to_cnf()
    tables = ProductionTables.from_grammar(g)

    t0 = time.perf_counter()
    base = hellings_cfpq(graph, g)["S"]
    t_base = time.perf_counter() - t0

    T0 = init_matrix(graph, g)
    closure.dense_closure(T0, tables).block_until_ready()  # compile
    t0 = time.perf_counter()
    T = closure.dense_closure(T0, tables)
    T.block_until_ready()
    t_mat = time.perf_counter() - t0

    rel = relations_from_matrix(np.asarray(T), g, graph.n_nodes)["S"]
    assert rel == base
    print(
        f"{qname}: #results={len(rel):6d}  worklist={t_base*1e3:7.1f}ms  "
        f"matrix={t_mat*1e3:7.1f}ms"
    )
