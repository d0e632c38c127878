"""CYK membership over the reference CNF: does ``start`` derive ``word``?

Kept with the benchmark to check single-path witnesses: a witness is
right when it is a path of the graph and its label string is derived."""
from __future__ import annotations


def cyk(cnf: dict, start: str, word: list[str]) -> bool:
    n = len(word)
    if n == 0:
        return False  # the benchmark's grammars derive no empty string
    by_label: dict[str, set[str]] = {}
    for a, x in cnf["terminal"]:
        by_label.setdefault(x, set()).add(a)
    # tab[(i, j)]: nonterminals deriving word[i:j]
    tab: dict[tuple[int, int], set[str]] = {
        (i, i + 1): set(by_label.get(x, ())) for i, x in enumerate(word)
    }
    for span in range(2, n + 1):
        for i in range(n - span + 1):
            j = i + span
            got: set[str] = set()
            for k in range(i + 1, j):
                left, right = tab[(i, k)], tab[(k, j)]
                if left and right:
                    got.update(
                        a for a, b, c in cnf["binary"]
                        if b in left and c in right
                    )
            tab[(i, j)] = got
    return start in tab[(0, n)]
