"""Counting semantics, bounded all-path enumeration, and the PR's
silent-truncation/feature-skew correctness sweep.

Layered like the subsystem:

* standalone semantics — the saturating counting closure vs a
  string-level brute-force path-count oracle on path-unique graphs
  (unambiguous grammar, so derivation counts ARE path counts), the
  saturation golden case (dense cycle -> SAT_COUNT sentinel, sticky
  through downstream pairs), and the support/relational agreement;
* the differential battery — engine-served counts bit-equal to the
  standalone ``evaluate_count`` across every registered backend (each
  aliases onto the one dense counting executable), cold / cache-warm /
  source-sliced;
* bounded all-path enumeration — ``extract_paths`` returns k distinct
  witness-valid paths within the length bound, consistent with the
  count matrix on DAGs, including the nullable empty path;
* the delta contract — insert-only recount vs a per-epoch
  ``evaluate_count`` oracle, any delete a full state drop, stats
  recording which path ran;
* the serving loop — count queries coalesced through CFPQServer with
  the ``+count`` planner-route label visible;
* regression sweep — the three satellite bugfixes: the n*N iteration
  cap that truncated deep derivations before the fixpoint, duplicate
  edges surviving ``random_labeled_graph`` into ``Graph.edges``, and
  torn metric-child increments under thread contention.
"""
import asyncio
import threading

import numpy as np
import pytest

from repro.core.closure import dense_closure
from repro.core.grammar import Grammar
from repro.core.graph import Graph, random_labeled_graph, worst_case_graph
from repro.core.matrices import ProductionTables, init_matrix, padded_size
from repro.core.semantics import (
    SAT_COUNT,
    count_base,
    count_closure,
    evaluate_count,
    evaluate_relational,
    extract_paths,
    masked_count_closure,
)
from repro.engine import CompiledClosureCache, EngineConfig, Query, QueryEngine
from repro.engine.plan import MASKED_ENGINES, count_engine_name

from helpers import assert_path_witness, cyk_recognize

#: unambiguous right-linear grammar over one label: S derives a^+ — on any
#: graph its derivation count per pair equals the number of distinct
#: a-labeled paths, which is what the brute-force oracle counts
LINEAR = Grammar.from_text("S -> a S | a").to_cnf()

#: the paper's worst-case balanced grammar (a^n b^n), for the deep
#: derivation regression
BALANCED = Grammar.from_text("S -> a S b | a b").to_cnf()

#: one compile cache for the whole module — every backend's count
#: PlanKeys alias onto the one dense counting executable
PLANS = CompiledClosureCache()

ENGINES = sorted(MASKED_ENGINES) + ["auto"]

def _graph(edges, n: int | None = None) -> Graph:
    """Literal construction: exactly these edges under these node ids
    (``from_triples`` renumbers by first occurrence and adds inverse
    ``x_r`` edges, which the count oracle must not have to model)."""
    if n is None:
        n = 1 + max(max(i, j) for i, _, j in edges)
    return Graph(n, list(edges))


DIAMOND = _graph([(0, "a", 1), (0, "a", 2), (1, "a", 3), (2, "a", 3)])


def _engine(graph: Graph, engine: str = "auto") -> QueryEngine:
    return QueryEngine(graph, plans=PLANS, config=EngineConfig(engine=engine))


def brute_count(
    graph: Graph, g, start: str, max_len: int | None = None
) -> dict:
    """String-level oracle: count every distinct edge path i ->* j whose
    label word CYK-derives from ``start``.  Exact when the grammar is
    unambiguous and path counts are finite (DAGs); ``max_len`` defaults
    to n (long enough for any simple-path-rich DAG used here)."""
    bound = max_len if max_len is not None else graph.n_nodes
    adj: dict[int, list] = {}
    for i, x, j in graph.edges:
        adj.setdefault(i, []).append((x, j))
    counts: dict[tuple[int, int], int] = {}
    for start_node in range(graph.n_nodes):
        stack = [(start_node, [])]
        while stack:
            node, word = stack.pop()
            if word and cyk_recognize(g, start, word):
                key = (start_node, node)
                counts[key] = counts.get(key, 0) + 1
            if len(word) >= bound:
                continue
            for x, j in adj.get(node, ()):
                stack.append((j, word + [x]))
    if start in g.nullable:
        for m in range(graph.n_nodes):
            counts[(m, m)] = counts.get((m, m), 0) + 1
    return counts


# --------------------------------------------------------------------- #
# Standalone semantics
# --------------------------------------------------------------------- #
def test_count_base_counts_parallel_edges():
    """Two parallel edges with different labels deriving the same
    nonterminal are two distinct length-1 paths — the Boolean base
    collapses them to one bit, the count base must not."""
    g = Grammar.from_text("S -> a | b").to_cnf()
    graph = _graph([(0, "a", 1), (0, "b", 1)])
    C0 = np.asarray(count_base(graph, g))
    assert C0[g.index_of("S"), 0, 1] == 2
    assert evaluate_count(graph, g, "S") == {(0, 1): 2}


def test_diamond_golden():
    assert evaluate_count(DIAMOND, LINEAR, "S") == {
        (0, 1): 1, (0, 2): 1, (0, 3): 2, (1, 3): 1, (2, 3): 1,
    }


@pytest.mark.parametrize("n_par", [3, 5])
def test_parallel_stages_multiply(n_par):
    """k parallel 2-hop stages compose multiplicatively: counts are
    products along the chain of stages."""
    edges = []
    for s in range(2):  # two stages: s*2 -> s*2+2 via n_par midpoints
        for p in range(n_par):
            mid = 10 + s * n_par + p
            edges += [(s * 2, "a", mid), (mid, "a", (s + 1) * 2)]
    graph = _graph(edges)
    counts = evaluate_count(graph, LINEAR, "S")
    assert counts[(0, 2)] == n_par
    assert counts[(0, 4)] == n_par * n_par


def test_count_support_matches_relational():
    for seed in range(3):
        graph = random_labeled_graph(6, 14, ["a"], seed=seed)
        counts = evaluate_count(graph, LINEAR, "S")
        assert set(counts) == evaluate_relational(graph, LINEAR, "S")


def test_saturation_golden_dense_cycle():
    """A cycle admits unboundedly many a-paths between every pair: every
    connected pair must carry exactly the SAT_COUNT sentinel, stamped by
    the divergence phase rather than reached by 2^32 additions."""
    loop = _graph([(0, "a", 0)])
    assert evaluate_count(loop, LINEAR, "S") == {(0, 0): int(SAT_COUNT)}
    cycle = _graph([(0, "a", 1), (1, "a", 0)])
    assert evaluate_count(cycle, LINEAR, "S") == {
        (i, j): int(SAT_COUNT) for i in (0, 1) for j in (0, 1)
    }


def test_saturation_is_sticky_downstream():
    """Entries that ride on a divergent prefix are divergent themselves:
    the sentinel absorbs through the semiring product."""
    graph = _graph([(0, "a", 0), (0, "a", 1), (1, "a", 2)])
    counts = evaluate_count(graph, LINEAR, "S")
    assert counts[(0, 0)] == int(SAT_COUNT)
    assert counts[(0, 1)] == int(SAT_COUNT)  # loop^k then the hop
    assert counts[(0, 2)] == int(SAT_COUNT)
    assert counts[(1, 2)] == 1  # off the cycle: still exact


def test_finite_counts_beside_divergent_ones():
    """The divergence gfp only stamps entries that depend on a cycle —
    pairs unreachable from the cycle stay exact in the same closure."""
    graph = _graph(
        [(0, "a", 1), (1, "a", 1), (2, "a", 3), (3, "a", 4), (2, "a", 4)]
    )
    counts = evaluate_count(graph, LINEAR, "S")
    assert counts[(0, 1)] == int(SAT_COUNT)
    assert counts[(2, 4)] == 2  # direct hop + the 2-hop path
    assert counts[(2, 3)] == 1 and counts[(3, 4)] == 1


def test_masked_equals_allpairs_on_mask_rows():
    graph = random_labeled_graph(6, 12, ["a"], seed=3)
    n = padded_size(graph.n_nodes)
    tables = ProductionTables.from_grammar(LINEAR)
    C0 = count_base(graph, LINEAR, pad_to=n)
    C_all = np.asarray(count_closure(C0, tables))
    import jax.numpy as jnp

    src = jnp.zeros((n,), bool).at[0].set(True)
    C_m, M, overflow, _ = masked_count_closure(
        C0, C0, tables, src, row_capacity=n
    )
    assert not bool(overflow)
    rows = np.asarray(M)
    assert np.array_equal(np.asarray(C_m)[:, rows, :], C_all[:, rows, :])


# --------------------------------------------------------------------- #
# Differential battery: engine == oracle, every backend
# --------------------------------------------------------------------- #
def _diff_cases():
    cases = [("diamond", DIAMOND)]
    for t in range(3):
        # forward-only random DAGs: finite path counts, oracle-checkable
        rng = np.random.default_rng(10 + t)
        n = 6
        edges = []
        for _ in range(10):
            i = int(rng.integers(0, n - 1))
            j = int(rng.integers(i + 1, n))
            edges.append((i, "a", j))
        cases.append((f"dag{t}", _graph(edges)))
    cases.append(
        ("chain", _graph([(i, "a", i + 1) for i in range(5)]))
    )
    return cases


DIFF_CASES = _diff_cases()


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_differential_vs_oracle(engine):
    for name, graph in DIFF_CASES:
        oracle = brute_count(graph, LINEAR, "S")
        assert evaluate_count(graph, LINEAR, "S") == oracle, name

        eng = _engine(graph, engine)
        cold = eng.query(Query(LINEAR, "S", semantics="count"))
        assert cold.counts == oracle, (engine, name)
        assert cold.pairs == set(oracle)
        assert cold.stats.cache == "miss"
        assert cold.stats.semantics == "count"
        warm = eng.query(Query(LINEAR, "S", semantics="count"))
        assert warm.counts == oracle, (engine, name)
        assert warm.stats.cache == "hit"  # no closure ran the second time
        src = eng.query(Query(LINEAR, "S", sources=(0,), semantics="count"))
        assert src.counts == {k: v for k, v in oracle.items() if k[0] == 0}


def test_engine_saturation_golden():
    eng = _engine(_graph([(0, "a", 0), (0, "a", 1)]))
    r = eng.query(Query(LINEAR, "S", semantics="count"))
    assert r.counts == {(0, 0): int(SAT_COUNT), (0, 1): int(SAT_COUNT)}


def test_nullable_start_counts_empty_path():
    g = Grammar.from_text("S -> a S | ").to_cnf()
    graph = _graph([(0, "a", 1)])
    oracle = brute_count(graph, g, "S")
    assert oracle[(0, 0)] == 1 and oracle[(1, 1)] == 1
    assert evaluate_count(graph, g, "S") == oracle
    r = _engine(graph).query(Query(g, "S", semantics="count"))
    assert r.counts == oracle


def test_count_aliasing_collapses_plan_keys():
    """Every backend keys its counting plans under the one dense
    executable, so a shared plans cache compiles exactly one count
    executable per (grammar, n, capacity)."""
    for engine in sorted(MASKED_ENGINES):
        assert count_engine_name(engine) == "dense"
    plans = CompiledClosureCache()
    for engine in sorted(MASKED_ENGINES):
        eng = QueryEngine(
            DIAMOND, plans=plans, config=EngineConfig(engine=engine)
        )
        r = eng.query(Query(LINEAR, "S", semantics="count"))
        assert r.counts == brute_count(DIAMOND, LINEAR, "S")
    assert plans.stats.compile_misses == 1


def test_count_requires_cnf_grammar():
    from repro.core.conjunctive import ConjunctiveGrammar

    conj = ConjunctiveGrammar.from_rules(
        {"a": ["A"]}, [("S", [("A", "A")])]
    )
    eng = _engine(DIAMOND)
    with pytest.raises(ValueError, match="does not match"):
        eng.query(Query(conj, "S", semantics="count"))


# --------------------------------------------------------------------- #
# Bounded all-path enumeration
# --------------------------------------------------------------------- #
def _closure_of(graph: Graph, g) -> np.ndarray:
    T0 = init_matrix(graph, g, pad_to=padded_size(graph.n_nodes))
    return np.asarray(dense_closure(T0, ProductionTables.from_grammar(g)))


def test_extract_paths_diamond_distinct_witnesses():
    T = _closure_of(DIAMOND, LINEAR)
    paths = extract_paths(T, DIAMOND, LINEAR, "S", 0, 3, k=10, max_len=8)
    assert len(paths) == 2
    assert len({tuple(p) for p in paths}) == 2  # distinct
    for p in paths:
        assert_path_witness(DIAMOND, LINEAR, "S", 0, 3, p)
        assert len(p) <= 8


def test_extract_paths_count_consistency_on_dags():
    """On a DAG the count matrix and the enumerator agree: asking for
    more paths than exist returns exactly the counted number."""
    for name, graph in DIFF_CASES:
        counts = evaluate_count(graph, LINEAR, "S")
        T = _closure_of(graph, LINEAR)
        for (i, j), c in counts.items():
            paths = extract_paths(
                T, graph, LINEAR, "S", i, j, k=c + 5,
                max_len=graph.n_nodes,
            )
            assert len(paths) == c, (name, i, j)
            assert len({tuple(p) for p in paths}) == c
            for p in paths:
                assert_path_witness(graph, LINEAR, "S", i, j, p)


def test_extract_paths_bounds_respected_on_cycle():
    """A cycle admits infinitely many paths; enumeration must stop at k
    distinct witnesses, all within the length bound."""
    loop = _graph([(0, "a", 0)])
    T = _closure_of(loop, LINEAR)
    paths = extract_paths(T, loop, LINEAR, "S", 0, 0, k=5, max_len=6)
    assert len(paths) == 5
    assert len({tuple(p) for p in paths}) == 5
    for p in paths:
        assert 1 <= len(p) <= 6
        assert_path_witness(loop, LINEAR, "S", 0, 0, p)


def test_extract_paths_nullable_empty_path():
    g = Grammar.from_text("S -> a S | ").to_cnf()
    graph = _graph([(0, "a", 1)])
    T = _closure_of(graph, g)
    paths = extract_paths(T, graph, g, "S", 0, 0, k=3, max_len=4)
    assert paths[0] == []  # the empty path witnesses (0, 0)
    paths01 = extract_paths(T, graph, g, "S", 0, 1, k=3, max_len=4)
    assert paths01 == [[(0, "a", 1)]]


def test_engine_extract_paths_and_invalidation():
    graph = _graph([(0, "a", 1), (1, "a", 3)])
    eng = _engine(graph)
    paths = eng.extract_paths(LINEAR, "S", 0, 3, k=10, max_len=8)
    assert len(paths) == 1
    # a delta must invalidate the cached derivation index: the second
    # parallel branch appears in the next enumeration
    eng.apply_delta(insert=[(0, "a", 2), (2, "a", 3)])
    paths = eng.extract_paths(LINEAR, "S", 0, 3, k=10, max_len=8)
    assert len(paths) == 2
    for p in paths:
        assert_path_witness(eng.graph, LINEAR, "S", 0, 3, p)


# --------------------------------------------------------------------- #
# Delta contract: insert = recount affected rows, delete = full drop
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ["auto", "dense"])
def test_delta_interleaving_vs_oracle(engine):
    graph = _graph([(0, "a", 1), (1, "a", 3)])
    eng = _engine(graph, engine)
    q = Query(LINEAR, "S", semantics="count")
    assert eng.query(q).counts == evaluate_count(eng.graph, LINEAR, "S")

    # epoch 1: insert-only -> recount affected rows in place
    st1 = eng.apply_delta(insert=[(0, "a", 2)])
    assert st1.count_repairs == 1 and st1.count_drops == 0
    r = eng.query(q)
    assert r.stats.cache == "hit"  # repaired in place, no re-closure
    assert r.counts == evaluate_count(eng.graph, LINEAR, "S")

    # epoch 2: the second parallel branch doubles (0, 3)
    st2 = eng.apply_delta(insert=[(2, "a", 3)])
    assert st2.count_repairs == 1
    r = eng.query(q)
    assert r.stats.cache == "hit"
    assert r.counts == evaluate_count(eng.graph, LINEAR, "S")
    assert r.counts[(0, 3)] == 2

    # epoch 3: any delete -> full drop (no subtractive inverse in the
    # saturating semiring), next query recounts from scratch
    st3 = eng.apply_delta(delete=[(1, "a", 3)])
    assert st3.count_drops == 1 and st3.count_repairs == 0
    assert st3.rows_evicted > 0
    r = eng.query(q)
    assert r.stats.cache == "miss"
    assert r.counts == evaluate_count(eng.graph, LINEAR, "S")

    # epoch 4: mixed insert+delete in one delta also drops
    st4 = eng.apply_delta(insert=[(1, "a", 3)], delete=[(0, "a", 1)])
    assert st4.count_drops == 1 and st4.count_repairs == 0
    assert eng.query(q).counts == evaluate_count(eng.graph, LINEAR, "S")


def test_delta_repair_matches_fresh_engine_bitwise():
    """Insert-interleaved counts equal a cold engine at every epoch —
    the recount path introduces no drift, including into saturation."""
    eng = _engine(_graph([(0, "a", 1)], n=4))
    q = Query(LINEAR, "S", semantics="count")
    eng.query(q)
    inserts = [
        [(1, "a", 2)],
        [(0, "a", 2)],  # second path 0 -> 2
        [(2, "a", 2)],  # self-loop: saturation enters through repair
        [(2, "a", 3)],
    ]
    for ins in inserts:
        eng.apply_delta(insert=ins)
        repaired = eng.query(q).counts
        fresh = _engine(eng.graph).query(q).counts
        assert repaired == fresh == evaluate_count(eng.graph, LINEAR, "S")


def test_mixed_relational_count_batch():
    eng = _engine(DIAMOND)
    r_cnt, r_rel = eng.query_batch(
        [
            Query(LINEAR, "S", semantics="count"),
            Query(LINEAR, "S", semantics="relational"),
        ]
    )
    assert r_cnt.counts == brute_count(DIAMOND, LINEAR, "S")
    assert r_rel.pairs == set(r_cnt.counts)
    assert r_rel.counts is None
    assert r_cnt.stats.batch_total == 2
    assert r_cnt.stats.batch_groups == 2


# --------------------------------------------------------------------- #
# Serving loop: count queries coalesce through CFPQServer
# --------------------------------------------------------------------- #
def test_count_through_server():
    from repro.serve import CFPQServer, ServeConfig

    eng = _engine(DIAMOND)
    oracle = brute_count(DIAMOND, LINEAR, "S")

    async def main():
        async with CFPQServer(
            eng, ServeConfig(max_batch=8, batch_window_s=0.005)
        ) as srv:
            outs = await asyncio.gather(
                *[
                    srv.submit(
                        Query(LINEAR, "S", sources=(i,), semantics="count")
                    )
                    for i in range(3)
                ]
            )
            return outs, srv.stats

    outs, stats = asyncio.run(main())
    for i, r in enumerate(outs):
        assert r.counts == {k: v for k, v in oracle.items() if k[0] == i}
    assert any(k.endswith("+count") for k in stats.planner_routes), (
        stats.planner_routes
    )


# --------------------------------------------------------------------- #
# Regression sweep: the three satellite bugfixes
# --------------------------------------------------------------------- #
def test_iteration_cap_reaches_deep_fixpoints():
    """The divergence guard used to be n*N iterations, which truncates
    BEFORE the fixpoint on deep-derivation inputs (one iteration can add
    a single entry, and there are n^2 N of them).  worst_case_graph(17)
    with the balanced grammar needs a^m b^m for m up to lcm(17, 18) =
    306 — derivation height ~2m, far past the old cap of 512."""
    graph = worst_case_graph(17)
    n = padded_size(graph.n_nodes)
    tables = ProductionTables.from_grammar(BALANCED)
    T0 = init_matrix(graph, BALANCED, pad_to=n)
    a0 = BALANCED.index_of("S")

    old_cap = n * BALANCED.n_nonterms  # the buggy limit, forced explicitly
    T_old = np.asarray(dense_closure(T0, tables, max_iters=old_cap))
    T_new = np.asarray(dense_closure(T0, tables))  # paper bound n^2 N
    assert not T_old[a0, 0, 0]  # the old cap silently truncated this
    assert T_new[a0, 0, 0]
    # monotonicity sanity: the deeper run only adds entries
    assert not (T_old & ~T_new).any()


@pytest.mark.parametrize("engine", sorted(MASKED_ENGINES))
def test_iteration_cap_masked_engines(engine):
    """Every masked backend (which inherits the same limit, plus mask
    headroom) reaches the deep fixpoint too."""
    graph = worst_case_graph(17)
    eng = _engine(graph, engine)
    r = eng.query(Query(BALANCED, "S", sources=(0,)))
    assert (0, 0) in r.pairs, engine


def test_iteration_cap_conjunctive():
    """conjunctive_closure carried the same n*N guard; a single-conjunct
    conjunctive grammar is an ordinary CFG, so the worst-case pair must
    appear there as well."""
    from repro.core.conjunctive import ConjunctiveGrammar, evaluate

    g = ConjunctiveGrammar.from_rules(
        terminal_rules={"a": ["A"], "b": ["B"]},
        conjunctive_rules=[
            ("S", [("A", "X")]),
            ("S", [("A", "B")]),
            ("X", [("S", "B")]),
        ],
    )
    graph = worst_case_graph(17)
    assert (0, 0) in evaluate(graph, g, "S")


def test_random_labeled_graph_dedupes_and_stays_deterministic():
    """Colliding draws used to survive into ``Graph.edges``, inflating
    the edge count past the number of *distinct* edges (and skewing
    every density-derived feature)."""
    g1 = random_labeled_graph(4, 1000, ["a", "b"], seed=5)
    # clamped to the number of possible distinct edges, all distinct
    assert len(g1.edges) == 4 * 4 * 2
    assert len(set(g1.edges)) == len(g1.edges)
    g2 = random_labeled_graph(4, 1000, ["a", "b"], seed=5)
    assert g1.edges == g2.edges  # seeded determinism preserved
    g3 = random_labeled_graph(12, 40, ["a"], seed=9)
    assert len(g3.edges) == 40
    assert len(set(g3.edges)) == 40


def test_graph_constructors_collapse_duplicate_edges():
    dup = [(0, "a", 1), (0, "a", 1), (1, "a", 2), (0, "a", 1)]
    g = Graph(3, list(dup))
    assert g.edges == [(0, "a", 1), (1, "a", 2)]  # first-seen order
    g2 = Graph.from_triples(dup, add_inverse=False)
    assert g2.edges == [(0, "a", 1), (1, "a", 2)]
    # duplicate edges are a single edge: counting must see exactly one
    assert evaluate_count(g2, LINEAR, "S")[(0, 1)] == 1


def test_metric_children_are_thread_safe():
    """value += x is a load/add/store; unsynchronized children lost
    updates under contention.  Hammer one child of each kind from many
    threads and assert the exact totals."""
    from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry

    reg = MetricsRegistry()
    counter = Counter("hammer_total", "x", registry=reg)
    gauge = Gauge("hammer_gauge", "x", registry=reg)
    hist = Histogram("hammer_hist", "x", buckets=(0.5, 1.5), registry=reg)
    n_threads, per_thread = 8, 2500

    def work():
        for _ in range(per_thread):
            counter.inc()
            gauge.inc(2.0)
            hist.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert counter.value == total
    assert gauge.value == 2.0 * total
    child = hist._only()
    assert child.count == total
    assert child.sum == 1.0 * total
    assert child.counts[1] == total  # every observation in the 1.5 bucket
