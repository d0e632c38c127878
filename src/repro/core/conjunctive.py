"""Conjunctive-grammar CFPQ (the paper's §7 future work, implemented).

The paper: "our algorithm can be trivially generalized to [conjunctive and
Boolean] grammars because parsing with conjunctive grammars can be expressed
by matrix multiplication [Okhotin 19]. ... Our hypothesis is that it would
produce the upper approximation of a solution."

A conjunctive production  A -> B1 C1 & B2 C2 & ...  derives w iff EVERY
conjunct derives w.  The matrix closure generalizes exactly as the paper
predicts: per iteration

    new[A] = AND_conjuncts ( T[B_i] x T[C_i] )   (Boolean AND of products)

Because the path-existence abstraction loses which *string* realizes each
(i, j) pair (two conjuncts may hold via different strings between the same
nodes), the fixpoint is an UPPER approximation of the conjunctive relation
— sound (never misses a real pair), possibly over-approximate; for
linear-conjunctive reachability this is the standard semantics used in
static analysis [Zhang & Su '17].  tests/test_conjunctive.py checks both
soundness (against string-level brute force on small graphs) and exactness
on DAG cases, plus the classic non-context-free language {a^n b^n c^n}.
"""
from __future__ import annotations

import functools
import operator
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .graph import Graph


@dataclass(frozen=True)
class ConjunctiveGrammar:
    """CNF-like conjunctive grammar: terminal rules A -> x and binary
    conjunctive rules A -> &_k (B_k C_k) given as index tuples."""

    nonterms: tuple[str, ...]
    term_prods: tuple[tuple[str, int], ...]  # (terminal, lhs_idx)
    conj_prods: tuple[tuple[int, tuple[tuple[int, int], ...]], ...]
    # each: (lhs_idx, ((b1, c1), (b2, c2), ...)) — one or more conjuncts

    @classmethod
    def from_rules(
        cls,
        terminal_rules: dict[str, list[str]],
        conjunctive_rules: list[tuple[str, list[tuple[str, str]]]],
    ) -> "ConjunctiveGrammar":
        names: list[str] = []

        def idx(n: str) -> int:
            if n not in names:
                names.append(n)
            return names.index(n)

        for a, pairs in conjunctive_rules:
            if not pairs:
                raise ValueError(
                    f"conjunctive rule for {a!r} has no conjuncts; a "
                    "production A -> &_k (B_k C_k) needs at least one "
                    "(B, C) pair (an empty AND would derive everything)"
                )
            idx(a)
        for x, lhss in terminal_rules.items():
            for a in lhss:
                idx(a)
        term = tuple(
            (x, idx(a)) for x, lhss in terminal_rules.items() for a in lhss
        )

        def dedupe(pairs):
            # duplicate conjuncts are idempotent under AND — drop them so
            # the closure doesn't pay for redundant products (and so the
            # planner's conjunct-count pricing reflects real work)
            seen: set[tuple[int, int]] = set()
            out = []
            for b, c in pairs:
                bc = (idx(b), idx(c))
                if bc not in seen:
                    seen.add(bc)
                    out.append(bc)
            return tuple(out)

        conj = tuple(
            (idx(a), dedupe(pairs)) for a, pairs in conjunctive_rules
        )
        return cls(tuple(names), term, conj)

    def index_of(self, name: str) -> int:
        return self.nonterms.index(name)

    @property
    def nullable(self) -> frozenset:
        """CNF-like conjunctive grammars have no epsilon rules; the empty
        set keeps the engine's result slicing uniform across grammars."""
        return frozenset()


@dataclass(frozen=True)
class ConjunctiveTables:
    """Device-ready index form of a conjunctive grammar — the analog of
    :class:`repro.core.matrices.ProductionTables` for PlanKey identity.

    Stored as tuples so the whole object is hashable and usable as a
    static argument of the jitted masked conjunctive closures
    (core/semantics.py).  Conjuncts are flattened: conjunct position ``k``
    contracts ``T[conj_b[k]] x T[conj_c[k]]`` and belongs to production
    ``prod_of[k]``, whose LHS is ``a_idx[prod_of[k]]``.
    """

    a_idx: tuple[int, ...]  # LHS nonterminal per production
    conj_b: tuple[int, ...]  # flattened conjunct operands
    conj_c: tuple[int, ...]
    prod_of: tuple[int, ...]  # production position per flattened conjunct
    n_nonterms: int

    @classmethod
    def from_grammar(cls, g: ConjunctiveGrammar) -> "ConjunctiveTables":
        prods = sorted(g.conj_prods)
        a_idx, conj_b, conj_c, prod_of = [], [], [], []
        for p, (a, pairs) in enumerate(prods):
            a_idx.append(a)
            for b, c in pairs:
                conj_b.append(b)
                conj_c.append(c)
                prod_of.append(p)
        return cls(
            tuple(a_idx),
            tuple(conj_b),
            tuple(conj_c),
            tuple(prod_of),
            len(g.nonterms),
        )

    @property
    def n_prods(self) -> int:
        return len(self.a_idx)

    @property
    def n_conjuncts(self) -> int:
        return len(self.conj_b)

    def conj_groups(self) -> dict[int, list[int]]:
        """Production position -> flattened conjunct positions (for the
        trace-time AND trees of the masked closures)."""
        out: dict[int, list[int]] = {}
        for k, p in enumerate(self.prod_of):
            out.setdefault(p, []).append(k)
        return out

    def lhs_groups(self) -> dict[int, list[int]]:
        """LHS nonterminal -> production positions (for the OR trees)."""
        out: dict[int, list[int]] = {}
        for p, a in enumerate(self.a_idx):
            out.setdefault(a, []).append(p)
        return out


def init_matrix(graph: Graph, g: ConjunctiveGrammar, pad_to: int | None = None):
    import numpy as np

    from .matrices import padded_size

    n = pad_to or padded_size(graph.n_nodes)
    T = np.zeros((len(g.nonterms), n, n), bool)
    by_label: dict[str, list[int]] = {}
    for x, a in g.term_prods:
        by_label.setdefault(x, []).append(a)
    for i, x, j in graph.edges:
        for a in by_label.get(x, ()):
            T[a, i, j] = True
    return jnp.asarray(T)


def init_matrix_rows(
    graph: Graph, g: ConjunctiveGrammar, rows, pad_to: int | None = None
):
    """Base-matrix rows for a subset of source nodes, shape
    ``(|N|, len(rows), n)``, used by the engine's insert-only delta repair
    of conjunctive states."""
    import numpy as np

    from .matrices import padded_size

    n = pad_to if pad_to is not None else padded_size(graph.n_nodes)
    by_label: dict[str, list[int]] = {}
    for x, a in g.term_prods:
        by_label.setdefault(x, []).append(a)
    pos = {int(r): k for k, r in enumerate(rows)}
    out = np.zeros((len(g.nonterms), len(pos), n), dtype=bool)
    for i, x, j in graph.edges:
        k = pos.get(i)
        if k is not None:
            for a in by_label.get(x, ()):
                out[a, k, j] = True
    return out


def _bool_matmul(lhs, rhs):
    return (
        jax.lax.dot_general(
            lhs.astype(jnp.float32),
            rhs.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
        )
        > 0
    )


@functools.partial(jax.jit, static_argnames=("g", "max_iters"))
def conjunctive_closure(
    T: jnp.ndarray, g: ConjunctiveGrammar, max_iters: int | None = None
):
    """Fixpoint of  new[A] = AND_k (T[b_k] x T[c_k])  — upper approximation
    of the conjunctive relations (exact for ordinary CFG productions)."""
    # |V|^2 |N| divergence guard (closure._iter_limit) — n*N truncates on
    # deep derivations where each iteration adds a single entry.
    limit = (
        max_iters
        if max_iters is not None
        else T.shape[-1] * T.shape[-1] * T.shape[0]
    )

    def body(state):
        T, _, it = state
        rows = list(jnp.unstack(T, axis=0))
        for a, pairs in g.conj_prods:
            prod = functools.reduce(
                operator.and_,
                [_bool_matmul(T[b], T[c]) for b, c in pairs],
            )
            rows[a] = rows[a] | prod
        T_next = jnp.stack(rows)
        grew = jnp.any(T_next & ~T)
        return T_next, grew, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < limit)

    T, _, _ = jax.lax.while_loop(cond, body, (T, jnp.bool_(True), 0))
    return T


def evaluate(
    graph: Graph, g: ConjunctiveGrammar, start: str
) -> set[tuple[int, int]]:
    import numpy as np

    T = conjunctive_closure(init_matrix(graph, g), g)
    a = g.index_of(start)
    sub = np.asarray(T)[a, : graph.n_nodes, : graph.n_nodes]
    return {(int(i), int(j)) for i, j in zip(*sub.nonzero())}
