"""Single-path semantics as an engine workload (paper Section 5).

The load-bearing test is the property one: on random graphs/grammars, for
every masked backend, (a) the single-path pair set equals the relational
closure, (b) every extracted witness passes the path-witness oracle
(helpers.assert_path_witness), and (c) the witness length equals the
frozen annotation ``L[A, m, n]``.  Lengths may differ across backends
(discovery order differs) — validity is asserted, not cross-engine
equality.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import closure
from repro.core.grammar import Grammar, query1_grammar
from repro.core.graph import ontology_graph, paper_example_graph
from repro.core.matrices import ProductionTables, init_matrix
from repro.core.semantics import (
    base_lengths,
    evaluate_relational,
    evaluate_single_path,
    masked_frontier_single_path_closure,
    masked_single_path_closure,
)
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES
from helpers import assert_path_witness, random_cnf, random_graph

ENGINES = sorted(MASKED_ENGINES)

#: shared across the module so dense/bitpacked single-path plans (which
#: alias to the same executable) compile once per grammar
PLANS = CompiledClosureCache()


# ---------------------------------------------------------------------- #
# Core masked single-path closures
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "fn", [masked_single_path_closure, masked_frontier_single_path_closure]
)
def test_masked_single_path_support_equals_boolean_closure(fn):
    """isfinite(L) rows under the returned mask are bit-identical to the
    all-pairs Boolean closure rows, per single source."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(20, 40, seed=3)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    ref = np.asarray(closure.dense_closure(T0, tables))
    for m in (0, 5, 11):
        src = np.zeros(n, bool)
        src[m] = True
        L, M, ovf, _ = fn(base_lengths(T0), tables, jnp.asarray(src),
                          row_capacity=n)
        assert not bool(ovf)
        M = np.asarray(M)
        assert M[m]
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(L))[:, M, :], ref[:, M, :]
        )


def test_masked_single_path_warm_restart_freezes_lengths():
    """Re-entering with more sources never rewrites already-finite entries
    (the freeze contract warm restarts and delta repair rely on)."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(20, 40, seed=3)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    src = np.zeros(n, bool)
    src[0] = True
    L1, M1, _, _ = masked_single_path_closure(
        base_lengths(T0), tables, jnp.asarray(src), row_capacity=n
    )
    more = np.asarray(M1).copy()
    more[:graph.n_nodes] = True
    L2, M2, _, _ = masked_single_path_closure(
        L1, tables, jnp.asarray(more), row_capacity=n
    )
    L1, L2 = np.asarray(L1), np.asarray(L2)
    was = np.isfinite(L1)
    np.testing.assert_array_equal(L2[was], L1[was])
    assert np.asarray(M2).sum() >= np.asarray(M1).sum()


# ---------------------------------------------------------------------- #
# Property test through the service (ISSUE 3 satellite)
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed", range(4))
def test_single_path_property_random(engine, seed):
    rng = np.random.default_rng(seed)
    g = random_cnf(rng)
    graph = random_graph(rng, n_nodes=6, n_edges=12)
    start = g.nonterms[0]
    rel = evaluate_relational(graph, g, start)
    eng = QueryEngine(graph, plans=PLANS, config=EngineConfig(engine=engine))
    sources = (0, 2, 4)
    r = eng.query(Query(g, start, sources=sources, semantics="single_path"))
    # (a) isfinite(L) == relational closure, per requested source rows
    assert r.pairs == {(i, j) for (i, j) in rel if i in sources}
    (state,) = eng._states.values()
    L = state.sp_L_host
    a0 = g.index_of(start)
    for (i, j), path in r.paths.items():
        # (b) oracle-valid witness; (c) length equals the frozen L[A, m, n]
        ann = None if not path else int(L[a0, i, j])
        assert_path_witness(graph, g, start, i, j, path, length=ann)


@pytest.mark.parametrize("engine", ENGINES)
def test_single_path_through_service_matches_library(engine):
    graph = paper_example_graph()
    g = query1_grammar().to_cnf()
    sp_full = evaluate_single_path(graph, g, "S")
    eng = QueryEngine(graph, plans=PLANS, config=EngineConfig(engine=engine))
    r = eng.query(Query(g, "S", sources=(0,), semantics="single_path"))
    assert set(r.paths) == {p for p in sp_full if p[0] == 0}
    r2 = eng.query(Query(g, "S", semantics="single_path"))
    assert r2.stats["cache"] in ("warm", "hit")
    assert set(r2.paths) == set(sp_full)
    (state,) = eng._states.values()
    L = state.sp_L_host
    a0 = g.index_of("S")
    for (i, j), path in r2.paths.items():
        assert_path_witness(graph, g, "S", i, j, path, length=int(L[a0, i, j]))


def test_single_path_caches_next_to_relational_state():
    """The two semantics materialize independently: a single-path query
    does not warm the Boolean cache and vice versa, but both serve hits
    once materialized, and the plan cache keys them apart."""
    graph = ontology_graph(30, 60, seed=2)
    g = query1_grammar().to_cnf()
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    r = eng.query(Query(g, "S", sources=(0,), semantics="single_path"))
    assert r.stats["cache"] == "miss" and r.stats["semantics"] == "single_path"
    rr = eng.query(Query(g, "S", sources=(0,)))
    assert rr.stats["cache"] == "miss"  # Boolean state starts cold
    assert rr.stats["semantics"] == "relational"
    assert eng.query(
        Query(g, "S", sources=(0,), semantics="single_path")
    ).stats["cache"] == "hit"
    assert eng.query(Query(g, "S", sources=(0,))).stats["cache"] == "hit"
    assert r.pairs == rr.pairs


def test_single_path_batch_coalesces_and_overflow_buckets_up():
    """A batch of single-path queries shares one masked min-plus closure,
    and an active set outgrowing the first bucket warm-restarts."""
    graph = ontology_graph(40, 99, seed=2)
    g = query1_grammar().to_cnf()
    full = evaluate_relational(graph, g, "S")
    eng = QueryEngine(graph, config=EngineConfig(engine="frontier", row_capacity=128))
    rs = eng.query_batch(
        [
            Query(g, "S", sources=(0,), semantics="single_path"),
            Query(g, "S", sources=(5, 17), semantics="single_path"),
        ]
    )
    assert [r.stats["cache"] for r in rs] == ["miss", "miss"]
    assert rs[0].stats["active_rows"] > 128  # reachable set overflows 128
    for r in rs:
        assert r.pairs == {
            (i, j) for (i, j) in full if i in r.query.sources
        }
        for (i, j), path in r.paths.items():
            assert_path_witness(graph, g, "S", i, j, path)


def test_nullable_start_yields_empty_path_witnesses():
    g = Grammar.from_text("S -> a S | a | eps").to_cnf()
    graph_edges = [(0, "a", 1)]
    from repro.core.graph import Graph

    graph = Graph(3, graph_edges)
    eng = QueryEngine(graph)
    r = eng.query(Query(g, "S", sources=(0, 2), semantics="single_path"))
    assert r.pairs == {(0, 0), (0, 1), (2, 2)}
    assert r.paths[(2, 2)] == [] and r.paths[(0, 0)] == []
    assert r.paths[(0, 1)] == [(0, "a", 1)]
    for (i, j), path in r.paths.items():
        assert_path_witness(graph, g, "S", i, j, path)
    # pairs agree with the relational semantics, nullable diagonal included
    assert r.pairs == eng.query(Query(g, "S", sources=(0, 2))).pairs
