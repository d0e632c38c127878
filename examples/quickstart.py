"""Quickstart: run a context-free path query end to end.

    PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's worked example (Section 4.3): the same-generation
query over a 3-node ontology fragment, then the same query with single-path
semantics (Section 5) to extract witness paths.
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.core.semantics import evaluate_relational, evaluate_single_path

enable_compile_cache()

# The same-generation query (paper Fig. 3) in the natural (non-CNF) form —
# the CNF transform is part of the frontend.
GRAMMAR = """
S -> subClassOf_r S subClassOf | type_r S type
S -> subClassOf_r subClassOf | type_r type
"""

# The input graph (paper Fig. 5).
graph = Graph(
    3,
    [
        (0, "subClassOf_r", 0),
        (0, "type_r", 1),
        (1, "type_r", 2),
        (2, "subClassOf", 0),
        (2, "type", 2),
    ],
)

g = Grammar.from_text(GRAMMAR).to_cnf()

# Relational semantics: which (m, n) pairs are connected by an S-path?
rel = evaluate_relational(graph, g, "S")
print("R_S =", sorted(rel))
assert rel == {(0, 0), (0, 2), (1, 2)}  # paper Fig. 9

# Single-path semantics: one witness path per pair.
paths = evaluate_single_path(graph, g, "S")
for (i, j), path in sorted(paths.items()):
    labels = " ".join(x for _, x, _ in path)
    print(f"witness {i} -> {j}: {labels}")

# Engines agree (dense MXU path vs bitpacked vs incremental frontier):
for engine in ("dense", "frontier", "bitpacked"):
    assert evaluate_relational(graph, g, "S", engine=engine) == rel
print("all engines agree — OK")
