"""Block-sparse closure differential battery (core/blocksparse.py).

Sparsity bugs are *silent* — a skipped block just drops paths — so the
block-sparse engine is proven, not assumed: every test here pits it
against an independent oracle (the dense masked closure, the Hellings
worklist baseline, or a from-scratch engine per epoch) and asserts
bit-identity, across both semantics, capacity/growth boundaries, and
delta-repair interleavings.  The hypothesis property suites are marked
``slow`` (the tier-1 quick lane runs ``-m "not slow"``; the scheduled CI
lane runs everything).

Beyond this file, registering ``blocksparse`` in ``MASKED_ENGINES``
auto-enrolls it in the engine/delta/single-path/planner batteries
(tests/test_engine.py, test_delta.py, test_single_path.py,
test_planner.py parametrize over ``sorted(MASKED_ENGINES)``) — the
"all mesh-free engines" leg of the differential battery runs there.
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:  # optional test dependency: pip install -e .[test]
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    given = settings = st = None

from repro.baselines import hellings_cfpq
from repro.core import closure
from repro.core.blocksparse import (
    DEFAULT_TILE,
    BlockSparseState,
    blocksparse_closure_state,
    masked_blocksparse_closure,
    masked_blocksparse_repair_closure,
    occupied_block_count,
    occupied_blocks_of_edges,
)
from repro.core.grammar import Grammar
from repro.core.graph import Graph, random_labeled_graph
from repro.core.matrices import (
    ProductionTables,
    init_matrix,
    relations_from_matrix,
)
from repro.core.semantics import evaluate_relational
from repro.engine import EngineConfig, Query, QueryEngine
from repro.engine.planner import PlanFeatures, Planner
from helpers import (
    SPARSE_FAMILIES,
    assert_path_witness,
    chain_graph,
    community_graph,
    power_law_graph,
    random_cnf,
    random_graph,
    sparse_graph,
)


def _allpairs_dense(T0, tables):
    return np.asarray(closure.dense_closure(T0, tables))


def _bs_ladder(T0, tables, seed, cap, tile, max_restarts=30):
    """Run the block-sparse closure through the engine-style warm-restart
    ladder from block capacity ``cap`` (doubling on overflow); returns the
    final (T, M) and the number of restarts taken."""
    n = T0.shape[-1]
    T, M, overflow = jnp.asarray(T0), np.asarray(seed), True
    restarts = -1
    while bool(overflow):
        restarts += 1
        assert restarts < max_restarts, "ladder did not terminate"
        T, M, overflow, _ = masked_blocksparse_closure(
            T, tables, np.asarray(M), row_capacity=cap, tile=tile
        )
        cap = min(n, max(2 * cap, 2))
    return np.asarray(T), np.asarray(M), restarts


# ---------------------------------------------------------------------- #
# Fixed-seed differential backstop: blocksparse vs dense vs Hellings
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("tile", [32, 128])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fixed_seed_differential(tile, seed):
    """All-pairs block-sparse closure is bit-identical to the dense
    closure and agrees with the Hellings worklist baseline on random
    ragged graphs (the padded n exercises both single- and multi-tile
    grids per tile size)."""
    rng = np.random.default_rng(seed)
    g = random_cnf(rng)
    graph = random_graph(
        rng,
        n_nodes=int(rng.integers(5, 14)),
        n_edges=int(rng.integers(8, 32)),
    )
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    dense = _allpairs_dense(T0, tables)
    Tb, Mb, ob, _ = masked_blocksparse_closure(
        T0, tables, jnp.ones((n,), jnp.bool_), row_capacity=n, tile=tile
    )
    assert not bool(ob)
    np.testing.assert_array_equal(np.asarray(Tb), dense)
    assert Mb.all()
    rel = relations_from_matrix(np.asarray(Tb), g, graph.n_nodes)
    assert rel == hellings_cfpq(graph, g)


@pytest.mark.parametrize("family", SPARSE_FAMILIES)
def test_sparse_families_differential(family):
    """The shared sparse-graph generators (chain/community/power-law —
    also driven by benchmarks/bench_scaling.py) all close identically
    under blocksparse and dense."""
    rng = np.random.default_rng(5)
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = sparse_graph(family, rng, 40, density=1.5)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    dense = _allpairs_dense(T0, tables)
    Tb, _, ob, _ = masked_blocksparse_closure(
        T0, tables, jnp.ones((n,), jnp.bool_), row_capacity=n, tile=32
    )
    assert not bool(ob)
    np.testing.assert_array_equal(np.asarray(Tb), dense)


def test_masked_rows_exact_under_sparse_mask():
    """With a restricted seed, every row the block-sparse engine reports
    in M equals the all-pairs closure row, and M covers the dense masked
    engine's M (block masks are coarser, never smaller)."""
    rng = np.random.default_rng(9)
    for _ in range(3):
        g = random_cnf(rng)
        graph = random_graph(rng, n_nodes=12, n_edges=30)
        tables = ProductionTables.from_grammar(g)
        T0 = init_matrix(graph, g)
        n = T0.shape[-1]
        seed = np.zeros(n, dtype=bool)
        seed[:3] = True
        Td, Md, _, _ = closure.masked_closure(
            T0, tables, jnp.asarray(seed), row_capacity=n
        )
        Tb, Mb, ob, _ = masked_blocksparse_closure(
            T0, tables, seed, row_capacity=n, tile=32
        )
        assert not bool(ob)
        Mdh, Mbh = np.asarray(Md), np.asarray(Mb)
        assert (Mdh <= Mbh).all()
        full = _allpairs_dense(T0, tables)
        np.testing.assert_array_equal(np.asarray(Tb)[:, Mbh, :], full[:, Mbh, :])


# ---------------------------------------------------------------------- #
# Engine dispatch, both semantics
# ---------------------------------------------------------------------- #


def test_relational_dispatch_matches_dense():
    rng = np.random.default_rng(21)
    g = random_cnf(rng)
    graph = random_graph(rng, n_nodes=11, n_edges=26)
    start = g.nonterms[0]
    assert evaluate_relational(graph, g, start, engine="blocksparse") == (
        evaluate_relational(graph, g, start, engine="dense")
    )


def test_single_path_served_through_blocksparse_pin():
    """Pinned ``engine="blocksparse"`` serves single-path queries through
    the documented dense alias (sp_engine_name): same pairs as dense, and
    every witness path is a real derivation."""
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = random_labeled_graph(18, 40, ["a", "b"], seed=4)
    eb = QueryEngine(graph, config=EngineConfig(engine="blocksparse"))
    ed = QueryEngine(graph, config=EngineConfig(engine="dense"))
    q = Query(g, "S", sources=(0, 1, 2, 3), semantics="single_path")
    rb, rd = eb.query(q), ed.query(q)
    assert rb.pairs == rd.pairs
    for (i, j), path in rb.paths.items():
        assert_path_witness(graph, g, "S", i, j, path)


# ---------------------------------------------------------------------- #
# Warm-restart / block-growth boundaries
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("tile", [32, 128])
@pytest.mark.parametrize("cap_kind", ["one", "B-1", "B", "n"])
def test_capacity_boundary_ladder(tile, cap_kind):
    """Block capacities at the growth boundaries R ∈ {1, B-1, B, n}: the
    doubling ladder always terminates and lands on the exact closure
    (capacity >= n runs unbounded, so the top rung can never overflow)."""
    rng = np.random.default_rng(13)
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = random_labeled_graph(20, 46, ["a", "b"], seed=13)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    cap = {"one": 1, "B-1": tile - 1, "B": tile, "n": n}[cap_kind]
    seed = np.zeros(n, dtype=bool)
    seed[: graph.n_nodes] = True
    T, M, restarts = _bs_ladder(T0, tables, seed, cap, tile)
    if cap_kind == "n":
        assert restarts == 0  # unbounded: one call reaches fixpoint
    full = _allpairs_dense(T0, tables)
    np.testing.assert_array_equal(T[:, M, :], full[:, M, :])
    assert M[: graph.n_nodes].all()


def test_overflow_returns_monotone_partial_state():
    """An overflowing call must still return usable progress: a superset
    of the input state, a mask that includes the seed, and overflow=True
    — the monotone warm-restart contract every masked engine honors."""
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = random_labeled_graph(24, 60, ["a", "b"], seed=2)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    seed = np.zeros(n, dtype=bool)
    seed[: graph.n_nodes] = True
    T1, M1, ov, _ = masked_blocksparse_closure(
        T0, tables, seed, row_capacity=1, tile=32
    )
    assert bool(ov)
    T0h, T1h = np.asarray(T0), np.asarray(T1)
    assert (T0h <= T1h).all()
    assert (seed <= np.asarray(M1)).all()


# ---------------------------------------------------------------------- #
# State construction / validation / gauges
# ---------------------------------------------------------------------- #


def test_from_graph_matches_init_matrix():
    rng = np.random.default_rng(17)
    for _ in range(3):
        g = random_cnf(rng)
        graph = random_graph(rng, n_nodes=13, n_edges=28)
        T0 = np.asarray(init_matrix(graph, g))
        state = BlockSparseState.from_graph(graph, g, tile=32)
        np.testing.assert_array_equal(state.to_dense(), T0)
        # materialized payload is proportional to occupied blocks only
        assert state.nbytes() == state.occupied * 32 * 1 * 4
        assert state.occupied == occupied_block_count(T0, 32)


def test_standalone_state_closure_never_densifies():
    """The million-node entry point: closure computed on the compacted
    state from the edge list equals the dense all-pairs closure."""
    rng = np.random.default_rng(23)
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = sparse_graph("community", rng, 48, density=1.0)
    tables = ProductionTables.from_grammar(g)
    full = _allpairs_dense(init_matrix(graph, g), tables)
    state = blocksparse_closure_state(graph, g, tile=32)
    np.testing.assert_array_equal(state.to_dense(), full)
    assert state.occupied == occupied_block_count(full, 32)


def test_tile_validation():
    g = Grammar.from_text("S -> a").to_cnf()
    graph = Graph(3, [(0, "a", 1)])
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)  # padded n is a multiple of 128
    n = T0.shape[-1]
    ones = np.ones(n, dtype=bool)
    with pytest.raises(ValueError):  # tile must divide n
        masked_blocksparse_closure(T0, tables, ones, tile=96)
    with pytest.raises(ValueError):  # tile must be a multiple of 32
        BlockSparseState(n, 1, tile=48)
    with pytest.raises(ValueError):  # config-level validation
        EngineConfig(engine="blocksparse", tile=31)


def test_zero_production_grammar_passthrough():
    """The masked-engine contract for trivial grammars: state unchanged,
    all-ones mask, no overflow."""
    g = Grammar.from_text("S -> a").to_cnf()
    graph = Graph(4, [(0, "a", 1), (1, "a", 2)])
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    T, M, ov, _ = masked_blocksparse_closure(
        T0, tables, np.zeros(n, dtype=bool)
    )
    np.testing.assert_array_equal(np.asarray(T), np.asarray(T0))
    assert np.asarray(M).all() and not bool(ov)


def test_occupied_blocks_of_edges_counts_base_grid():
    graph = Graph(300, [(0, "a", 1), (0, "a", 200), (150, "b", 299)])
    # tiles of 128: blocks (0,0), (0,1), (1,2) -> 3 distinct
    assert occupied_blocks_of_edges(300, graph.edges, 128) == 3


def test_blocksparse_occupied_block_gauge_set():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = random_labeled_graph(16, 36, ["a", "b"], seed=6)
    eng = QueryEngine(
        graph, config=EngineConfig(engine="blocksparse"), metrics=reg
    )
    eng.query(Query(g, "S", sources=(0, 1)))
    snap = reg.collect()
    assert snap["blocksparse_occupied_blocks"]["series"][0]["value"] > 0


# ---------------------------------------------------------------------- #
# Kernel path: the Pallas tile program vs the jnp oracle
# ---------------------------------------------------------------------- #


def test_tile_bitmm_kernel_matches_ref():
    """Small pair batches run the actual Pallas tile program (interpret
    mode off-TPU); they must match the jnp reference bit-for-bit."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    rng = np.random.default_rng(31)
    for p, B in [(1, 32), (3, 32), (2, 128)]:
        lhs = jnp.asarray(
            rng.integers(0, 2**32, size=(p, B, B // 32), dtype=np.uint32)
        )
        rhs = jnp.asarray(
            rng.integers(0, 2**32, size=(p, B, B // 32), dtype=np.uint32)
        )
        np.testing.assert_array_equal(
            np.asarray(kops.tile_bitmm(lhs, rhs)),
            np.asarray(kref.bitmm_ref(lhs, rhs)),
        )


def test_closure_use_kernel_path_matches_oracle_path():
    """The fixpoint with use_kernel=True (tile_bitmm; Pallas for small
    chunks) equals use_kernel=False (pure jnp reference) — the two device
    paths can never drift."""
    rng = np.random.default_rng(37)
    g = random_cnf(rng)
    graph = random_graph(rng, n_nodes=10, n_edges=24)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    ones = jnp.ones((n,), jnp.bool_)
    Tk, _, _, _ = masked_blocksparse_closure(
        T0, tables, ones, row_capacity=n, tile=32, use_kernel=True
    )
    Tr, _, _, _ = masked_blocksparse_closure(
        T0, tables, ones, row_capacity=n, tile=32, use_kernel=False
    )
    np.testing.assert_array_equal(np.asarray(Tk), np.asarray(Tr))


# ---------------------------------------------------------------------- #
# Planner: occupied-block pricing and gating
# ---------------------------------------------------------------------- #


def test_planner_picks_blocksparse_at_low_density():
    p = Planner()
    f = PlanFeatures(
        n=4096, seed_rows=16, new_rows=16, density=1.0, n_prods=2,
        n_nonterms=3, occupied_blocks=40, tile=DEFAULT_TILE,
    )
    d = p.decide(f)
    assert d.engine == "blocksparse"
    assert "blocksparse:masked" in d.candidates


def test_planner_rejects_blocksparse_when_dense_or_small():
    p = Planner()
    dense_graph = PlanFeatures(
        n=4096, seed_rows=16, new_rows=16, density=50.0, n_prods=2,
        n_nonterms=3, occupied_blocks=1024, tile=DEFAULT_TILE,
    )
    assert p.decide(dense_graph).engine != "blocksparse"
    small = PlanFeatures(
        n=256, seed_rows=16, new_rows=16, density=1.0, n_prods=2,
        n_nonterms=3, occupied_blocks=4, tile=DEFAULT_TILE,
    )
    assert "blocksparse:masked" not in p.decide(small).candidates


def test_planner_ignores_blocksparse_without_occupancy_feature():
    """Callers that don't measure occupancy (calibration decision grids,
    legacy feature builders) must see exactly the pre-blocksparse
    candidate set — the backend is gated on its feature being present."""
    p = Planner()
    f = PlanFeatures(
        n=4096, seed_rows=16, new_rows=16, density=1.0, n_prods=2,
        n_nonterms=3,
    )
    d = p.decide(f)
    assert not any("blocksparse" in k for k in d.candidates)


def test_planner_pin_blocksparse_always_allowed():
    """Pinning short-circuits candidate gating — a pinned blocksparse
    decision works even without occupancy features."""
    p = Planner()
    f = PlanFeatures(
        n=256, seed_rows=4, new_rows=4, density=9.0, n_prods=2, n_nonterms=3
    )
    d = p.decide(f, pin="blocksparse")
    assert d.engine == "blocksparse" and d.pinned


# ---------------------------------------------------------------------- #
# Delta repair: interleavings, frozen-block identity, compaction floor
# ---------------------------------------------------------------------- #


def test_blocksparse_delta_interleaving_vs_per_epoch_oracle():
    """Random insert/delete interleavings on a long-lived blocksparse
    engine match a from-scratch dense engine rebuilt at every epoch."""
    rng = np.random.default_rng(41)
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    n = 24
    graph = random_labeled_graph(n, 50, ["a", "b"], seed=8)
    graph.edges[:] = sorted(set(graph.edges))
    eng = QueryEngine(graph, config=EngineConfig(engine="blocksparse"))

    def random_edge():
        return (
            int(rng.integers(0, n)),
            ["a", "b"][int(rng.integers(0, 2))],
            int(rng.integers(0, n)),
        )

    for step in range(10):
        op = rng.random()
        if op < 0.35 and graph.edges:
            victim = graph.edges[int(rng.integers(0, len(graph.edges)))]
            eng.apply_delta(delete=[victim])
        elif op < 0.7:
            eng.apply_delta(insert=[random_edge() for _ in range(2)])
        sources = tuple(
            sorted(set(int(s) for s in rng.integers(0, n, size=3)))
        )
        got = eng.query(Query(g, "S", sources=sources))
        oracle = QueryEngine(
            Graph(n, list(graph.edges)), config=EngineConfig(engine="dense")
        )
        want = oracle.query(Query(g, "S", sources=sources))
        assert got.pairs == want.pairs, (step, sources)


def test_frozen_blocks_bit_identical_after_insert_repair():
    """Rows outside the insertion's ancestor set (whole frozen blocks
    included) come back byte-for-byte identical from a blocksparse
    repair — never 'recomputed to the same value'."""
    from repro.delta.repair import plan_repair

    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    graph = random_labeled_graph(20, 44, ["a", "b"], seed=19)
    eng = QueryEngine(graph, config=EngineConfig(engine="blocksparse"))
    eng.query(Query(g, "S"))
    (state,) = eng._states.values()
    T_before = state.T_host.copy()
    mask_before = state.mask.copy()
    v0 = graph.version
    insert = [(2, "a", 11), (7, "b", 3)]
    eng.apply_delta(insert=insert)
    plan = plan_repair(eng.graph, eng.graph.delta_since(v0), eng.n)
    frozen = mask_before & ~plan.affected
    assert frozen.any()
    np.testing.assert_array_equal(
        state.T_host[:, frozen, :], T_before[:, frozen, :]
    )
    # and the repaired state still answers exactly
    r = eng.query(Query(g, "S", sources=(0, 1, 2)))
    full = evaluate_relational(graph, g, "S", engine="dense")
    assert r.pairs == {(i, j) for (i, j) in full if i in (0, 1, 2)}


def test_blocksparse_repair_mask_excludes_frozen_rows():
    """Direct contract check on the repair wrapper: M never includes a
    frozen row, and frozen rows are bit-identical in the output."""
    rng = np.random.default_rng(43)
    g = random_cnf(rng)
    graph = random_graph(rng, n_nodes=12, n_edges=30)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    full = _allpairs_dense(T0, tables)
    frozen = np.zeros(n, dtype=bool)
    frozen[::2] = True
    seed = np.zeros(n, dtype=bool)
    seed[1:7:2] = True
    Tb, Mb, ov, _ = masked_blocksparse_repair_closure(
        jnp.asarray(full), tables, seed, frozen, row_capacity=n, tile=32
    )
    assert not bool(ov)
    Mbh = np.asarray(Mb)
    assert not (Mbh & frozen).any()
    np.testing.assert_array_equal(
        np.asarray(Tb)[:, frozen, :], full[:, frozen, :]
    )


def test_blocksparse_full_drop_below_compaction_floor():
    """A blocksparse engine whose version predates Graph.compact_log's
    floor cannot read a delta — it must resynchronize with a clean full
    drop (cache=miss) and still answer exactly."""
    graph = Graph(3, [(0, "a", 1)])
    g = Grammar.from_text("S -> a").to_cnf()
    eng = QueryEngine(graph, config=EngineConfig(engine="blocksparse"))
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1)}
    graph.insert_edges([(0, "a", 2)])
    graph.compact_log(graph.version)  # engine's version is now pre-floor
    r = eng.query(Query(g, "S", sources=(0,)))
    assert r.stats["cache"] == "miss"  # full invalidation, not repair
    assert r.pairs == {(0, 1), (0, 2)}


# ---------------------------------------------------------------------- #
# Sparse generator sanity (shared with benchmarks)
# ---------------------------------------------------------------------- #


def test_sparse_generators_shapes_and_density():
    rng = np.random.default_rng(47)
    chain = chain_graph(100)
    assert chain.n_edges == 99 and chain.n_nodes == 100
    com = community_graph(rng, 128, n_communities=4, intra_density=2.0)
    assert com.n_nodes == 128 and com.n_edges > 128
    pl = power_law_graph(rng, 200, 300)
    assert pl.n_nodes == 200 and pl.n_edges == 300
    # hubs exist: the most popular source is well above uniform share
    srcs = np.array([i for i, _, _ in pl.edges])
    assert np.bincount(srcs, minlength=200).max() > 3


# ---------------------------------------------------------------------- #
# Hypothesis property suites (slow lane)
# ---------------------------------------------------------------------- #

if st is not None:

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from([32, 64, 128]))
    def test_property_blocksparse_vs_dense_vs_hellings(seed, tile):
        """Relational: random ragged graph + random CNF grammar, any legal
        tile — blocksparse all-pairs == dense closure == Hellings."""
        rng = np.random.default_rng(seed)
        g = random_cnf(rng)
        graph = random_graph(
            rng,
            n_nodes=int(rng.integers(2, 12)),
            n_edges=int(rng.integers(1, 24)),
        )
        tables = ProductionTables.from_grammar(g)
        T0 = init_matrix(graph, g)
        n = T0.shape[-1]
        dense = _allpairs_dense(T0, tables)
        Tb, _, ob, _ = masked_blocksparse_closure(
            T0, tables, jnp.ones((n,), jnp.bool_), row_capacity=n, tile=tile
        )
        assert not bool(ob)
        np.testing.assert_array_equal(np.asarray(Tb), dense)
        rel = relations_from_matrix(np.asarray(Tb), g, graph.n_nodes)
        assert rel == hellings_cfpq(graph, g)

    @pytest.mark.slow
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_masked_growth_boundaries(seed):
        """Random seeds + random block capacity (including the R ∈
        {1, B-1, B, n} boundaries): the doubling ladder always lands on
        rows bit-identical to the all-pairs closure."""
        rng = np.random.default_rng(seed)
        g = random_cnf(rng)
        graph = random_graph(rng, n_nodes=int(rng.integers(4, 12)), n_edges=20)
        tables = ProductionTables.from_grammar(g)
        T0 = init_matrix(graph, g)
        n = T0.shape[-1]
        tile = 32
        cap = int(
            rng.choice([1, tile - 1, tile, n, int(rng.integers(1, n + 1))])
        )
        seed_mask = np.zeros(n, dtype=bool)
        seed_mask[rng.integers(0, graph.n_nodes or 1, size=3)] = True
        T, M, _ = _bs_ladder(T0, tables, seed_mask, cap, tile)
        full = _allpairs_dense(T0, tables)
        np.testing.assert_array_equal(T[:, M, :], full[:, M, :])

    @pytest.mark.slow
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000))
    def test_property_single_path_through_blocksparse(seed):
        """Single-path semantics served under a blocksparse pin: pairs
        match the dense engine and every witness is a real derivation."""
        rng = np.random.default_rng(seed)
        g = Grammar.from_text("S -> a S b | a b").to_cnf()
        graph = random_labeled_graph(
            int(rng.integers(4, 16)), 24, ["a", "b"], seed=seed % 1000
        )
        sources = tuple(
            sorted(set(int(s) for s in rng.integers(0, graph.n_nodes, 3)))
        )
        eb = QueryEngine(graph, config=EngineConfig(engine="blocksparse"))
        ed = QueryEngine(graph, config=EngineConfig(engine="dense"))
        q = Query(g, "S", sources=sources, semantics="single_path")
        rb, rd = eb.query(q), ed.query(q)
        assert rb.pairs == rd.pairs
        for (i, j), path in rb.paths.items():
            assert_path_witness(graph, g, "S", i, j, path)

else:  # property tests skip cleanly on a bare checkout

    @pytest.mark.slow
    def test_property_blocksparse_vs_dense_vs_hellings():
        pytest.importorskip("hypothesis")

    @pytest.mark.slow
    def test_property_masked_growth_boundaries():
        pytest.importorskip("hypothesis")

    @pytest.mark.slow
    def test_property_single_path_through_blocksparse():
        pytest.importorskip("hypothesis")
