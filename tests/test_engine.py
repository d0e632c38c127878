"""Query-engine subsystem: single-/multi-source results equal the row
slice of the all-pairs closure, and repeated queries hit the caches."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import closure
from repro.core.grammar import Grammar, PAPER_EXAMPLE_CNF, query1_grammar
from repro.core.graph import Graph, ontology_graph, paper_example_graph
from repro.core.matrices import ProductionTables, init_matrix
from repro.core.semantics import evaluate_relational, evaluate_single_path
from repro.engine import (
    EngineConfig,
    Query,
    QueryEngine,
    bucket_for,
    row_buckets,
)
from repro.engine.plan import MASKED_ENGINES

ENGINES = sorted(MASKED_ENGINES)


@pytest.mark.parametrize("engine", ENGINES)
def test_masked_closure_rows_equal_dense_closure(engine):
    """Per-backend: masked rows == the same rows of the all-pairs closure
    on the paper's worked example, for every single source."""
    g = PAPER_EXAMPLE_CNF
    graph = paper_example_graph()
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    dense = np.asarray(closure.dense_closure(T0, tables))
    for m in range(graph.n_nodes):
        mask = np.zeros(n, bool)
        mask[m] = True
        T, M, ovf, _ = MASKED_ENGINES[engine](T0, tables, jnp.asarray(mask))
        assert not bool(ovf)
        M = np.asarray(M)
        assert M[m]
        assert (np.asarray(T)[:, M, :] == dense[:, M, :]).all()


@pytest.mark.parametrize("engine", ENGINES)
def test_single_source_query_matches_allpairs(engine):
    """Through the service: single-source results == filtered relational
    evaluation, on the paper example and an ontology graph."""
    for graph, g in (
        (paper_example_graph(), query1_grammar().to_cnf()),
        (ontology_graph(40, 99, seed=2), query1_grammar().to_cnf()),
    ):
        full = evaluate_relational(graph, g, "S")
        eng = QueryEngine(graph, config=EngineConfig(engine=engine))
        for sources in [(0,), (1, 2), tuple(range(min(8, graph.n_nodes)))]:
            r = eng.query(Query(g, "S", sources=sources))
            assert r.pairs == {(i, j) for (i, j) in full if i in sources}


def test_allpairs_query_through_service():
    graph = ontology_graph(30, 60, seed=1)
    g = query1_grammar().to_cnf()
    eng = QueryEngine(graph)
    r = eng.query(Query(g, "S"))
    assert r.pairs == evaluate_relational(graph, g, "S")


def test_repeated_query_hits_materialized_cache_without_retrace():
    graph = ontology_graph(40, 99, seed=2)
    g = query1_grammar().to_cnf()
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    r1 = eng.query(Query(g, "S", sources=(0, 5)))
    assert r1.stats["cache"] == "miss"
    compiles = eng.plans.stats.compile_misses
    assert compiles >= 1
    # identical query: served from materialized rows — no closure run, no
    # new executable compiled (no retrace)
    r2 = eng.query(Query(g, "S", sources=(0, 5)))
    assert r2.stats["cache"] == "hit"
    assert eng.plans.stats.compile_misses == compiles
    assert r2.pairs == r1.pairs
    # a subset of already-materialized rows is also a pure hit
    r3 = eng.query(Query(g, "S", sources=(5,)))
    assert r3.stats["cache"] == "hit"
    assert eng.plans.stats.compile_misses == compiles


def test_new_sources_warm_start_reuses_compiled_plan():
    graph = ontology_graph(40, 99, seed=2)
    g = query1_grammar().to_cnf()
    full = evaluate_relational(graph, g, "S")
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    eng.query(Query(g, "S", sources=(0,)))
    compiles = eng.plans.stats.compile_misses
    r = eng.query(Query(g, "S", sources=(1,)))
    assert r.stats["cache"] in ("warm", "hit")
    assert r.pairs == {(i, j) for (i, j) in full if i == 1}
    # warm start may bucket up at most once beyond the plans already built
    assert eng.plans.stats.compile_misses <= compiles + 1


def test_batch_coalesces_one_closure_per_grammar():
    graph = ontology_graph(40, 99, seed=2)
    g = query1_grammar().to_cnf()
    full = evaluate_relational(graph, g, "S")
    eng = QueryEngine(graph, config=EngineConfig(engine="bitpacked"))
    rs = eng.query_batch(
        [
            Query(g, "S", sources=(2,)),
            Query(g, "S", sources=(7, 9)),
            Query(g, "S", sources=(2, 9)),
        ]
    )
    statuses = [r.stats["cache"] for r in rs]
    assert statuses == ["miss", "miss", "miss"]  # ONE shared closure call
    for r in rs:
        assert r.stats["batched_with"] == 3
        assert r.pairs == {
            (i, j) for (i, j) in full if i in r.query.sources
        }


def test_single_path_semantics_through_service():
    graph = paper_example_graph()
    g = query1_grammar().to_cnf()
    eng = QueryEngine(graph)
    sp_full = evaluate_single_path(graph, g, "S")
    r = eng.query(Query(g, "S", sources=(0,), semantics="single_path"))
    assert set(r.paths) == {p for p in sp_full if p[0] == 0}
    r2 = eng.query(Query(g, "S", semantics="single_path"))
    assert r2.stats["cache"] == "hit"
    assert r2.paths == sp_full


def test_nullable_start_contributes_empty_paths():
    g = Grammar.from_text("S -> a S | a | eps").to_cnf()
    graph = Graph(3, [(0, "a", 1)])
    eng = QueryEngine(graph)
    assert eng.query(Query(g, "S", sources=(2,))).pairs == {(2, 2)}
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 0), (0, 1)}


def test_graph_edit_invalidates_materialized_closure():
    graph = Graph(3, [(0, "a", 1)])
    g = Grammar.from_text("S -> a").to_cnf()
    eng = QueryEngine(graph)
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1)}
    graph.edges.append((0, "a", 2))
    r = eng.query(Query(g, "S", sources=(0,)))
    assert r.stats["cache"] == "miss"  # fingerprint change dropped the state
    assert r.pairs == {(0, 1), (0, 2)}


def test_overflow_grows_capacity_and_stays_correct():
    graph = ontology_graph(40, 99, seed=2)
    g = query1_grammar().to_cnf()
    full = evaluate_relational(graph, g, "S")
    eng = QueryEngine(graph, config=EngineConfig(engine="dense", row_capacity=128))
    # the reachable set (139 rows) overflows the first bucket; the service
    # must bucket up and still return exact rows
    r = eng.query(Query(g, "S", sources=(0, 5, 17)))
    assert r.stats["active_rows"] > 128
    assert r.pairs == {(i, j) for (i, j) in full if i in (0, 5, 17)}


def test_row_buckets():
    assert row_buckets(128) == [128]
    assert row_buckets(512) == [128, 256, 512]
    assert row_buckets(384) == [128, 256, 384]
    assert bucket_for(3, 512) == 128
    assert bucket_for(200, 512) == 256
    assert bucket_for(400, 512) == 512


def test_opt_and_masked_engines_registered_in_dispatch():
    """Regression: evaluate_relational knows every closure engine."""
    graph = paper_example_graph()
    g = query1_grammar().to_cnf()
    ref = evaluate_relational(graph, g, "S", engine="dense")
    for engine in ("frontier", "bitpacked", "opt", "masked"):
        assert evaluate_relational(graph, g, "S", engine=engine) == ref
