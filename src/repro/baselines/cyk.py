"""CYK membership: does a CNF grammar derive a label string?"""
from __future__ import annotations

import numpy as np

from repro.core.grammar import CNFGrammar


def cyk_recognize(g: CNFGrammar, start: str, word: list[str]) -> bool:
    """Classic CYK over a CNF grammar — verifies that extracted witness
    paths really derive from the queried nonterminal (the tests and
    ``chip_smoke.py``).  The split-point scan is a NumPy reduction, so
    long witness strings stay cheap."""
    n = len(word)
    if n == 0:
        return start in g.nullable
    N = g.n_nonterms
    tab = np.zeros((n, n + 1, N), dtype=bool)  # [i, j) span
    for i, x in enumerate(word):
        for a in g.term_prods.get(x, ()):
            tab[i, i + 1, a] = True
    for span in range(2, n + 1):
        for i in range(0, n - span + 1):
            j = i + span
            for a, b, c in g.binary_prods:
                if not tab[i, j, a]:
                    # any split k in (i, j): B spans [i, k), C spans [k, j)
                    tab[i, j, a] = bool(
                        np.any(tab[i, i + 1 : j, b] & tab[i + 1 : j, j, c])
                    )
    return bool(tab[0, n, g.index_of(start)])
