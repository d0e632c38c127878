"""Shared test utilities."""
from __future__ import annotations

import numpy as np

from repro.baselines import cyk_recognize
from repro.core.grammar import CNFGrammar, Production
from repro.core.graph import Graph


def assert_path_witness(
    graph: Graph,
    g: CNFGrammar,
    start: str,
    i: int,
    j: int,
    path: list[tuple[int, str, int]],
    length: int | None = None,
) -> None:
    """Path-witness oracle: the reusable check every single-path test
    asserts against.  ``path`` must be a real edge-by-edge walk i ->* j
    through ``graph`` whose label string CYK-derives from ``start``;
    with ``length`` given, the edge count must equal it.  An empty path
    witnesses only (m, m) pairs of a nullable start symbol."""
    if not path:
        assert i == j, f"empty path cannot witness ({i}, {j})"
        assert start in g.nullable, (
            f"empty path for non-nullable start {start!r}"
        )
        assert length in (None, 0)
        return
    assert path[0][0] == i, f"path starts at {path[0][0]}, not {i}"
    assert path[-1][2] == j, f"path ends at {path[-1][2]}, not {j}"
    edges = graph.edge_set()
    prev = i
    for e in path:
        s, _, d = e
        assert s == prev, f"path breaks at {e} (expected source {prev})"
        assert e in edges, f"{e} is not a graph edge"
        prev = d
    word = [x for _, x, _ in path]
    assert cyk_recognize(g, start, word), (
        f"label string {word} does not derive from {start!r}"
    )
    if length is not None:
        assert len(path) == length, (
            f"witness has {len(path)} edges, annotation says {length}"
        )


def random_cnf(rng: np.random.Generator, n_nt=3, n_t=2, n_bin=4, n_term=3):
    """A random CNF grammar over terminals t0..; nonterminal A0 is start."""
    prods = []
    for _ in range(n_bin):
        a, b, c = rng.integers(0, n_nt, size=3)
        prods.append(Production(f"A{a}", (f"A{b}", f"A{c}")))
    for _ in range(n_term):
        a = rng.integers(0, n_nt)
        t = rng.integers(0, n_t)
        prods.append(Production(f"A{a}", (f"t{t}",)))
    # every nonterminal referenced on a RHS must have a production; dropping
    # a production can orphan others, so filter to a fixpoint
    while True:
        lhs = {p.lhs for p in prods}
        kept = [
            p
            for p in prods
            if all(s in lhs or s.startswith("t") for s in p.rhs)
        ]
        if len(kept) == len(prods):
            break
        prods = kept
    if not prods:
        prods = [Production("A0", ("t0",))]
    return CNFGrammar.from_productions(prods)


def random_graph(rng: np.random.Generator, n_nodes=6, n_edges=12, n_t=2):
    edges = []
    for _ in range(n_edges):
        i, j = rng.integers(0, n_nodes, size=2)
        t = rng.integers(0, n_t)
        edges.append((int(i), f"t{t}", int(j)))
    return Graph(n_nodes, edges)


# ---------------------------------------------------------------------- #
# Sparse-graph generators — shared by the block-sparse differential tests
# (tests/test_blocksparse.py) and the scaling benchmarks
# (benchmarks/bench_scaling.py), so both exercise identical topology
# families at controlled densities.
# ---------------------------------------------------------------------- #


def chain_graph(n_nodes: int, labels=("t0", "t1"), stride: int = 1) -> Graph:
    """A labeled chain 0 -> stride -> 2·stride -> …, labels alternating —
    the minimal-density family (density == 1 edge/node), whose closure
    stays banded: the worst case for dense padding, the best for tiles."""
    edges = []
    for k, i in enumerate(range(0, n_nodes - stride, stride)):
        edges.append((i, labels[k % len(labels)], i + stride))
    return Graph(n_nodes, edges)


def community_graph(
    rng: np.random.Generator,
    n_nodes: int,
    n_communities: int = 8,
    intra_density: float = 2.0,
    inter_edges: int = 4,
    labels=("t0", "t1"),
) -> Graph:
    """Dense little communities, sparse bridges: edges cluster into
    ``n_communities`` node ranges (``intra_density`` edges per node inside
    each) plus ``inter_edges`` random cross-community bridges.  Occupied
    blocks concentrate on the diagonal — the regime block-sparse states
    are built for."""
    size = max(n_nodes // n_communities, 1)
    edges = []
    for c in range(n_communities):
        lo = c * size
        hi = min(lo + size, n_nodes)
        if hi - lo < 2:
            continue
        for _ in range(int(intra_density * (hi - lo))):
            i, j = rng.integers(lo, hi, size=2)
            edges.append((int(i), labels[rng.integers(len(labels))], int(j)))
    for _ in range(inter_edges):
        i, j = rng.integers(0, n_nodes, size=2)
        edges.append((int(i), labels[rng.integers(len(labels))], int(j)))
    return Graph(n_nodes, edges)


def power_law_graph(
    rng: np.random.Generator,
    n_nodes: int,
    n_edges: int,
    exponent: float = 1.5,
    labels=("t0", "t1"),
) -> Graph:
    """Preferential-attachment-flavored sparse graph: endpoint popularity
    follows ``rank^-exponent``, giving a few hub rows and a long tail of
    near-empty ones (web/social-graph shape; hubs make some row-blocks hot
    while most tiles stay empty)."""
    ranks = np.arange(1, n_nodes + 1, dtype=np.float64)
    p = ranks**-exponent
    p /= p.sum()
    # draw in rounds until n_edges DISTINCT edges accumulate — Graph
    # collapses duplicates, and hub-heavy sampling collides often, so a
    # single draw of size n_edges would under-deliver (feature skew)
    target = min(n_edges, n_nodes * n_nodes * len(labels))
    seen: set = set()
    edges = []
    while len(edges) < target:
        need = target - len(edges)
        src = rng.choice(n_nodes, size=need, p=p)
        dst = rng.choice(n_nodes, size=need, p=p)
        lab = rng.integers(0, len(labels), size=need)
        for i, j, k in zip(src, dst, lab):
            e = (int(i), labels[int(k)], int(j))
            if e not in seen:
                seen.add(e)
                edges.append(e)
    return Graph(n_nodes, edges)


SPARSE_FAMILIES = ("chain", "community", "power_law")


def sparse_graph(
    family: str, rng: np.random.Generator, n_nodes: int, density: float = 1.0
) -> Graph:
    """One generator entry point keyed by family name, scaled to roughly
    ``density`` edges per node (chain ignores density — it is 1 by
    construction)."""
    if family == "chain":
        return chain_graph(n_nodes)
    if family == "community":
        return community_graph(
            rng,
            n_nodes,
            n_communities=max(n_nodes // 64, 2),
            intra_density=density,
            inter_edges=max(int(0.05 * density * n_nodes), 2),
        )
    if family == "power_law":
        return power_law_graph(rng, n_nodes, int(density * n_nodes))
    raise ValueError(f"unknown sparse family {family!r}")


def masked_oracle_run(
    T0,
    tables,
    src_mask,
    mesh_shape: tuple[int, int] | None = None,
    row_capacity: int = 128,
    single_path: bool = False,
    max_restarts: int = 20,
):
    """Mesh-parametrized oracle runner for the distributed (`opt`) masked
    closures: runs ``masked_opt_closure`` (or, with ``single_path=True``,
    ``masked_opt_single_path_closure`` on the f32 state ``T0``) under a
    host-device mesh of shape ``(data, model)`` — ``None`` runs the same
    math without a mesh plan — re-entering on overflow with a doubled row
    capacity exactly like the engine's bucket ladder does.

    Returns ``(state, mask, snapshots)`` as NumPy arrays, where
    ``snapshots`` is the list of per-call ``(state, mask)`` pairs (one per
    warm restart, final included) so callers can assert restart
    invariants: the fixpoint is monotone, and already-converged entries —
    Boolean rows / finite single-path lengths — come back bit-identical
    from every re-entry regardless of the mesh shape.
    """
    import contextlib

    import jax.numpy as jnp

    from repro.core.closure import masked_opt_closure
    from repro.core.semantics import masked_opt_single_path_closure
    from repro.shard import MeshPlan, make_mesh

    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape)
        plan = MeshPlan.from_mesh(mesh)
        ctx = mesh
    else:
        plan, ctx = None, contextlib.nullcontext()
    fn = masked_opt_single_path_closure if single_path else masked_opt_closure
    n = T0.shape[-1]
    state, mask = T0, jnp.asarray(src_mask)
    cap = min(row_capacity, n)
    snapshots = []
    for _ in range(max_restarts):
        with ctx:
            state, mask, overflow, _ = fn(
                state, tables, mask, row_capacity=cap, plan=plan
            )
        snapshots.append((np.asarray(state), np.asarray(mask)))
        if not bool(overflow):
            return np.asarray(state), np.asarray(mask), snapshots
        # grow to the power-of-two bucket covering the overflowing active
        # set (like the engine's ladder — and it bounds the number of
        # distinct row_capacity values that get traced/compiled)
        needed = max(int(snapshots[-1][1].sum()), 2 * cap, 2)
        cap = min(n, 1 << int(np.ceil(np.log2(needed))))
    raise AssertionError(f"no fixpoint within {max_restarts} restarts")
