"""The subsets-of-nonterminals matrix of the paper, as JAX tensors.

A matrix ``a`` whose entries are subsets of N is stored as a stacked Boolean
tensor ``T`` of shape ``(|N|, n, n)`` — ``T[A, i, j]`` iff ``A in a[i, j]``.
This is exactly Valiant's decomposition of the subset algebra into |N|^2
Boolean matrix multiplications, laid out so that ALL productions ``A -> B C``
are evaluated as one batched matmul (see closure.py).

Physical layouts:
  * dense Boolean ``(N, n, n)`` — lifted to bf16 0/1 for the MXU matmul path;
  * bitpacked ``(N, n, ceil(n/32))`` uint32 — 32x smaller HBM footprint, used
    by the Pallas VPU kernel (kernels/bitmm.py).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from .grammar import CNFGrammar
from .graph import Graph

LANE = 128  # TPU lane width; pad n to a multiple for MXU-aligned tiles.


@dataclass(frozen=True)
class ProductionTables:
    """Device-ready index form of the CNF grammar.

    Stored as tuples so the whole object is hashable and can be passed as a
    static argument to jitted closure engines (the grammar is compile-time
    constant; the graph is the data).
    """

    a_idx: tuple[int, ...]  # LHS nonterminal per production, sorted ascending
    b_idx: tuple[int, ...]
    c_idx: tuple[int, ...]
    n_nonterms: int

    @classmethod
    def from_grammar(cls, g: CNFGrammar) -> "ProductionTables":
        trip = sorted(g.binary_prods)
        return cls(
            tuple(t[0] for t in trip),
            tuple(t[1] for t in trip),
            tuple(t[2] for t in trip),
            g.n_nonterms,
        )

    @property
    def n_prods(self) -> int:
        return len(self.a_idx)

    def groups(self) -> dict[int, list[int]]:
        """LHS nonterminal -> production positions (for trace-time OR trees)."""
        out: dict[int, list[int]] = {}
        for p, a in enumerate(self.a_idx):
            out.setdefault(a, []).append(p)
        return out

    def arrays(self):
        return (
            np.asarray(self.a_idx, np.int32),
            np.asarray(self.b_idx, np.int32),
            np.asarray(self.c_idx, np.int32),
        )


def padded_size(n: int, lane: int = LANE) -> int:
    return max(lane, -(-n // lane) * lane)


def init_matrix(
    graph: Graph, g: CNFGrammar, pad_to: int | None = None
) -> jnp.ndarray:
    """Lines 6-7 of Algorithm 1: T[A,i,j] = 1 iff (i,x,j) in E and A->x in P.

    Padding nodes have no edges and therefore never participate in any path,
    so padding is exact (not an approximation).
    """
    n = pad_to if pad_to is not None else padded_size(graph.n_nodes)
    if n < graph.n_nodes:
        raise ValueError("pad_to smaller than the graph")
    T = np.zeros((g.n_nonterms, n, n), dtype=bool)
    for i, x, j in graph.edges:
        for a in g.term_prods.get(x, ()):
            T[a, i, j] = True
    return jnp.asarray(T)


def init_matrix_entries(graph: Graph, g: CNFGrammar, rows) -> np.ndarray:
    """Base-matrix entries of a subset of source nodes: the set entries of
    :func:`init_matrix`'s ``rows`` slices, as a ``(3, k)`` int32 list of
    ``(a, i, j)`` — O(edges) memory instead of O(|rows|·n), for delta
    repair's row surgery (``delta/repair.py:row_surgery``)."""
    rows = {int(r) for r in rows}
    out = [
        (a, i, j)
        for i, x, j in graph.edges
        if i in rows
        for a in g.term_prods.get(x, ())
    ]
    return np.asarray(out, dtype=np.int32).reshape(-1, 3).T


# ---------------------------------------------------------------------- #
# Bitpacked layout: pack the trailing (column) axis, 32 columns per word.
# ---------------------------------------------------------------------- #


def pack_bits(T: jnp.ndarray) -> jnp.ndarray:
    """(…, n) bool -> (…, ceil(n/32)) uint32, bit b of word w = column 32w+b."""
    n = T.shape[-1]
    w = -(-n // 32)
    pad = w * 32 - n
    if pad:
        T = jnp.concatenate(
            [T, jnp.zeros((*T.shape[:-1], pad), T.dtype)], axis=-1
        )
    bits = T.reshape(*T.shape[:-1], w, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(Tp: jnp.ndarray, n: int) -> jnp.ndarray:
    """(…, w) uint32 -> (…, n) bool."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (Tp[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(*Tp.shape[:-1], Tp.shape[-1] * 32)
    return out[..., :n].astype(bool)


def relations_from_matrix(
    T: np.ndarray | jnp.ndarray, g: CNFGrammar, n_nodes: int
) -> dict[str, set[tuple[int, int]]]:
    """Extract the context-free relations R_A (Theorem 2)."""
    T = np.asarray(T)[:, :n_nodes, :n_nodes]
    out: dict[str, set[tuple[int, int]]] = {}
    for a, name in enumerate(g.nonterms):
        i, j = np.nonzero(T[a])
        out[name] = set(zip(i.tolist(), j.tolist()))
    return out
