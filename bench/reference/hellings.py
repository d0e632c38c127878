"""Hellings' worklist CFPQ over a plain edge list (relational semantics).

A copy of the classic cubic worklist algorithm, kept with the benchmark so
that no change to the program can move the oracle.  ``cnf`` is the
reference's own encoding of the grammar: ``{"terminal": [[A, label], ...],
"binary": [[A, B, C], ...]}``, every nonterminal a string.
"""
from __future__ import annotations

from collections import defaultdict, deque


def hellings(edges, cnf: dict) -> dict[str, set[tuple[int, int]]]:
    """``R_A`` for every nonterminal ``A`` of ``cnf`` over ``edges``
    (``(i, label, j)`` triples)."""
    by_label: dict[str, list[str]] = defaultdict(list)
    for a, x in cnf["terminal"]:
        by_label[x].append(a)
    by_b: dict[str, list[tuple[str, str]]] = defaultdict(list)
    by_c: dict[str, list[tuple[str, str]]] = defaultdict(list)
    for a, b, c in cnf["binary"]:
        by_b[b].append((a, c))
        by_c[c].append((a, b))

    facts: set[tuple[str, int, int]] = set()
    out: dict[str, dict[int, set[int]]] = defaultdict(lambda: defaultdict(set))
    inc: dict[str, dict[int, set[int]]] = defaultdict(lambda: defaultdict(set))
    work: deque[tuple[str, int, int]] = deque()

    def add(a: str, i: int, j: int) -> None:
        if (a, i, j) not in facts:
            facts.add((a, i, j))
            out[a][i].add(j)
            inc[a][j].add(i)
            work.append((a, i, j))

    for i, x, j in edges:
        for a in by_label.get(x, ()):
            add(a, i, j)
    while work:
        x, i, j = work.popleft()
        for a, c in by_b.get(x, ()):  # new fact as the left operand
            for m in tuple(out[c][j]):
                add(a, i, m)
        for a, b in by_c.get(x, ()):  # new fact as the right operand
            for m in tuple(inc[b][i]):
                add(a, m, j)

    rel: dict[str, set[tuple[int, int]]] = defaultdict(set)
    for a, i, j in facts:
        rel[a].add((i, j))
    return rel
