"""Delta subsystem: edge-log mutation layer, row-level closure repair,
epoch-snapshot consistency.

The load-bearing test is the differential one: a random interleaving of
inserts / deletes / queries against one long-lived engine must match a
from-scratch engine on the same graph at every step, for all three masked
backends — plus the bit-identical repair contract on the cached state
itself.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import closure
from repro.core.grammar import Grammar, query1_grammar
from repro.core.graph import Graph, ontology_graph, random_labeled_graph
from repro.core.matrices import (
    ProductionTables,
    init_matrix,
    init_matrix_entries,
)
from repro.core.semantics import evaluate_relational
from repro.delta.repair import (
    ENTRY_BUCKET_MIN,
    pad_entries,
    reverse_reach_rows,
    row_surgery,
)
from repro.delta.txn import EpochClock, Snapshot, StaleSnapshotError
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES
from helpers import assert_path_witness

ENGINES = sorted(MASKED_ENGINES)


# ---------------------------------------------------------------------- #
# Mutation layer (core/graph.py)
# ---------------------------------------------------------------------- #


def test_edge_log_versions_and_net_delta():
    g = Graph(4, [(0, "a", 1), (1, "b", 2)])
    assert g.version == 0
    v0 = g.version
    g.insert_edges([(2, "a", 3)])
    assert g.version == 1 and (2, "a", 3) in g.edges
    g.insert_edges([(2, "a", 3)])  # duplicate: no-op, no version bump
    assert g.version == 1
    g.delete_edges([(0, "a", 1)])
    assert g.version == 2 and (0, "a", 1) not in g.edges
    g.delete_edges([(0, "a", 1)])  # absent: no-op
    assert g.version == 2
    d = g.delta_since(v0)
    assert set(d.inserted) == {(2, "a", 3)}
    assert set(d.deleted) == {(0, "a", 1)}
    assert d.inserted_sources == {2} and d.deleted_sources == {0}


def test_edge_log_cancellation():
    g = Graph(3, [(0, "a", 1)])
    v0 = g.version
    g.insert_edges([(1, "a", 2)])
    g.delete_edges([(1, "a", 2)])  # insert then delete: net no-op
    g.delete_edges([(0, "a", 1)])
    g.insert_edges([(0, "a", 1)])  # delete then re-insert: net no-op
    d = g.delta_since(v0)
    assert not d and d.inserted == () and d.deleted == ()
    # a consumer at an intermediate version still sees the tail
    d1 = g.delta_since(v0 + 1)
    assert set(d1.deleted) == {(1, "a", 2)}


def test_edge_mutation_validates_nodes():
    g = Graph(2, [])
    with pytest.raises(ValueError):
        g.insert_edges([(0, "a", 5)])
    with pytest.raises(ValueError):
        g.delete_edges([(-1, "a", 0)])
    with pytest.raises(ValueError):
        g.delta_since(99)


def test_delete_removes_duplicate_occurrences():
    g = Graph(2, [(0, "a", 1), (0, "a", 1)])
    g.delete_edges([(0, "a", 1)])
    assert (0, "a", 1) not in g.edges and g.n_edges == 0


def test_init_matrix_rows_matches_full_matrix_slices():
    """The touched rows' base entries are exactly the set entries of the
    full base matrix's row slices."""
    graph = ontology_graph(20, 40, seed=9)
    g = query1_grammar().to_cnf()
    full = np.asarray(init_matrix(graph, g))
    idx = np.array([0, 3, 17, graph.n_nodes - 1])
    a, i, j = init_matrix_entries(graph, g, idx)
    rows = np.zeros_like(full)
    rows[a, i, j] = True
    want = np.zeros_like(full)
    want[:, idx, :] = full[:, idx, :]
    np.testing.assert_array_equal(rows, want)
    assert init_matrix_entries(graph, g, []).shape == (3, 0)


# ---------------------------------------------------------------------- #
# Row surgery on the device (delta/repair.py:row_surgery)
# ---------------------------------------------------------------------- #


def _dense_compose(old, base, ev, lengths):
    """The dense host compose the device surgery replaces: a touched row
    merged with its base row (``ev`` broadcast over the evicted rows)."""
    if not lengths:
        return np.where(ev, base, old | base)
    keep = np.isfinite(old) & ~ev  # freeze: never overwrite finite
    return np.where(keep, old, np.where(base, np.float32(1), np.inf))


SURGERY_CASES = {
    # name: (evicted rows, inserted sources)
    "evict_only": ([2, 5, 6, 11], []),
    "insert_only": ([], [1, 9]),
    "evict_and_insert": ([0, 4, 7, 13], [3, 9]),
    "inserted_source_evicted": ([3, 8, 12], [3, 10]),
    "finite_length_kept": ([], [1]),
    "padding_only": ([15], []),  # no base edge leaves row 15
}


@pytest.mark.parametrize("case", sorted(SURGERY_CASES))
@pytest.mark.parametrize("semantics", ["relational", "single_path"])
def test_row_surgery_bit_identical_to_dense_compose(semantics, case):
    """The device surgery from padded base entries writes exactly what the
    dense host compose of the touched rows wrote, every other row
    untouched, for both result algebras."""
    rng = np.random.default_rng(sorted(SURGERY_CASES).index(case))
    n, lengths = 16, semantics == "single_path"
    g = query1_grammar().to_cnf()
    labels = sorted(g.term_prods)
    graph = Graph(n, sorted({
        (int(i), labels[int(x)], int(j))
        for i, x, j in zip(rng.integers(0, 15, 60),
                           rng.integers(0, len(labels), 60),
                           rng.integers(0, n, 60))
    }))
    shape = (g.n_nonterms, n, n)
    if lengths:
        old = np.where(rng.random(shape) < 0.4,
                       rng.integers(1, 6, shape), np.inf).astype(np.float32)
    else:
        old = rng.random(shape) < 0.4
    evicted, inserted = SURGERY_CASES[case]
    evict = np.zeros(n, dtype=bool)
    evict[evicted] = True
    touched = evict.copy()
    touched[inserted] = True
    idx = np.nonzero(touched)[0]
    entries = init_matrix_entries(graph, g, idx)
    if case == "finite_length_kept":
        a, i, j = entries[:, 0]
        old[a, i, j] = 3 if lengths else True  # a base entry, annotated
    if case == "padding_only":
        assert entries.shape == (3, 0)
    padded = pad_entries(entries, n)
    assert padded.shape[1] == ENTRY_BUCKET_MIN
    assert (padded[1, entries.shape[1]:] == n).all()

    got = np.asarray(row_surgery(
        jnp.asarray(old), jnp.asarray(evict), jnp.asarray(padded)
    ))
    base = np.asarray(init_matrix(graph, g, pad_to=n))[:, idx, :]
    want = old.copy()
    want[:, idx, :] = _dense_compose(
        old[:, idx, :], base, evict[idx][None, :, None], lengths
    )
    assert got.dtype == old.dtype
    np.testing.assert_array_equal(got, want)
    if case == "finite_length_kept" and lengths:
        assert got[a, i, j] == 3  # the finite length survives


def test_pad_entries_buckets_are_powers_of_two():
    for k, size in [(0, 1024), (1024, 1024), (1025, 2048), (5000, 8192)]:
        padded = pad_entries(np.ones((3, k), np.int32), 64)
        assert padded.shape == (3, size) and padded.dtype == np.int32
        assert (padded[1, k:] == 64).all() and (padded[:, :k] == 1).all()


@pytest.mark.parametrize("semantics", ["relational", "single_path"])
def test_successive_deletes_compile_the_surgery_once(semantics):
    """Deletes that evict slightly different row counts reuse one surgery
    executable: the only compile under ``repair.upload`` is the first
    delete's ``row_surgery``."""
    from repro.obs.trace import Tracer

    g = Grammar.from_text("S -> a S | a").to_cnf()
    n = 40
    graph = Graph(n, [(k, "a", k + 1) for k in range(n - 1)])
    tracer = Tracer()
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"),
                      tracer=tracer)
    eng.query(Query(g, "S", semantics=semantics))
    row_surgery.clear_cache()
    tracer.clear()
    for k in (30, 28, 26):  # evicts rows 0..k: 31, 29, 27 rows
        eng.apply_delta(delete=[(k, "a", k + 1)])
    plans = [s for s in tracer.spans if s.name == "repair.plan"]
    assert [s.attrs["evict"] for s in plans] == [31, 29, 27]
    compiles = [ev["args"]["fun_name"] for s in tracer.spans
                if s.name == "repair.upload"
                for ev in s.events if ev["name"] == "compile"]
    assert len(compiles) == 1 and "row_surgery" in compiles[0], compiles
    base = [s for s in tracer.spans if s.name == "repair.base_rows"]
    # two base entries (S and the CNF's `a` nonterminal) per kept edge
    assert [s.attrs["entries"] for s in base] == [60, 56, 52]
    assert {s.attrs["bytes"] for s in base} == {3 * 4 * ENTRY_BUCKET_MIN}


# ---------------------------------------------------------------------- #
# Reverse-reachability sweeps (host BFS vs device fixpoint)
# ---------------------------------------------------------------------- #


def test_reverse_reach_host_matches_device_sweep():
    rng = np.random.default_rng(3)
    n = 60
    graph = random_labeled_graph(n, 150, ["a", "b"], seed=3)
    adj = np.zeros((n, n), dtype=bool)
    for i, _, j in graph.edges:
        adj[i, j] = True
    for seeds in [(0,), (5, 17), tuple(rng.integers(0, n, size=6).tolist())]:
        host = reverse_reach_rows(n, graph.edges, seeds)
        seed_m = np.zeros(n, dtype=bool)
        seed_m[list(seeds)] = True
        dev = np.asarray(
            closure.reverse_reachable_mask(
                jnp.asarray(adj), jnp.asarray(seed_m)
            )
        )
        np.testing.assert_array_equal(host, dev)
    # empty seeds -> empty mask
    assert not reverse_reach_rows(n, graph.edges, ()).any()


# ---------------------------------------------------------------------- #
# Repair correctness through the service
# ---------------------------------------------------------------------- #


def _pairs_for(graph, g, sources):
    full = evaluate_relational(graph, g, "S")
    return {(i, j) for (i, j) in full if i in sources}


@pytest.mark.parametrize("engine", ENGINES)
def test_insert_repair_matches_scratch(engine):
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=1)
    eng = QueryEngine(graph, config=EngineConfig(engine=engine))
    src = (0, 3, 7)
    eng.query(Query(g, "S", sources=src))
    st = eng.apply_delta(
        insert=[(0, "type", 5), (5, "subClassOf", 3), (9, "type_r", 2)]
    )
    assert st.rows_repaired > 0 and st.repair_iters >= 1
    r = eng.query(Query(g, "S", sources=src))
    assert r.stats["cache"] == "hit"  # repaired eagerly, not dropped
    assert r.pairs == _pairs_for(graph, g, src)


@pytest.mark.parametrize("engine", ENGINES)
def test_delete_evicts_and_recomputes(engine):
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=1)
    eng = QueryEngine(graph, config=EngineConfig(engine=engine))
    src = (0, 3, 7)
    eng.query(Query(g, "S", sources=src))
    victim = graph.edges[0]
    st = eng.apply_delta(delete=[victim])
    assert st.rows_evicted > 0
    r = eng.query(Query(g, "S", sources=src))
    assert r.stats["cache"] in ("warm", "hit")  # hit iff no src was evicted
    assert r.pairs == _pairs_for(graph, g, src)


def test_repair_contract_rows_bit_identical_to_scratch():
    """After repair, every row under the cached mask equals the same row of
    a from-scratch all-pairs closure on the mutated graph — the DELTA.md
    correctness contract, checked on the raw state."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=2)
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    eng.query(Query(g, "S", sources=(0, 5)))
    eng.apply_delta(
        insert=[(1, "subClassOf", 4), (8, "type", 3)],
        delete=[graph.edges[3]],
    )
    (state,) = eng._states.values()
    tables = ProductionTables.from_grammar(g)
    T_ref = np.asarray(
        closure.dense_closure(init_matrix(graph, g, pad_to=eng.n), tables)
    )
    M = state.mask
    assert M.any()
    np.testing.assert_array_equal(state.T_host[:, M, :], T_ref[:, M, :])


@pytest.mark.parametrize("engine", ENGINES)
def test_differential_random_interleaving(engine):
    """Acceptance: a random interleaving of inserts/deletes/queries on one
    long-lived engine yields pair sets identical to a from-scratch engine
    on the current graph, at every step."""
    rng = np.random.default_rng(ENGINES.index(engine))  # reproducible
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    n = 24
    graph = random_labeled_graph(n, 50, ["a", "b"], seed=7)
    graph.edges[:] = sorted(set(graph.edges))  # dedup for clean deletes
    eng = QueryEngine(graph, config=EngineConfig(engine=engine))
    plans = CompiledClosureCache()  # shared by the scratch references

    def random_edge():
        return (
            int(rng.integers(0, n)),
            ["a", "b"][int(rng.integers(0, 2))],
            int(rng.integers(0, n)),
        )

    for step in range(12):
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
        scratch = QueryEngine(
            Graph(n, list(graph.edges)), plans=plans,
            config=EngineConfig(engine=engine),
        )
        want = scratch.query(Query(g, "S", sources=sources))
        assert got.pairs == want.pairs, (engine, step, sources)


# ---------------------------------------------------------------------- #
# Single-path (T, L) states: repaired, not dropped
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("engine", ENGINES)
def test_single_path_insert_repair_not_dropped(engine):
    """Acceptance: after apply_delta (inserts), cached single-path states
    are repaired in place — the next query is a pure cache hit and still
    yields oracle-valid witnesses for the mutated graph."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=1)
    eng = QueryEngine(graph, config=EngineConfig(engine=engine))
    src = (0, 3, 7)
    eng.query(Query(g, "S", sources=src, semantics="single_path"))
    st = eng.apply_delta(
        insert=[(0, "type", 5), (5, "subClassOf", 3), (9, "type_r", 2)]
    )
    assert st.rows_repaired > 0 and st.repair_iters >= 1
    r = eng.query(Query(g, "S", sources=src, semantics="single_path"))
    assert r.stats["cache"] == "hit"  # repaired eagerly, not dropped
    assert r.pairs == _pairs_for(graph, g, src)
    for (i, j), path in r.paths.items():
        assert_path_witness(graph, g, "S", i, j, path)


def test_single_path_repair_freezes_unaffected_rows_bit_identical():
    """Rows outside the insert's ancestor set keep their length rows
    bit-identical through the repair (the frozen-row contract on L).  Two
    disjoint communities: an insert into one must leave the other's rows
    untouched."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(15, 25, seed=2).repeat(2)
    half = graph.n_nodes // 2
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    eng.query(Query(g, "S", semantics="single_path"))
    (state,) = eng._states.values()
    L_before = np.array(state.sp_L_host, copy=True)
    mask_before = np.array(state.sp_mask, copy=True)
    from repro.delta.repair import plan_repair

    insert = [(1, "subClassOf", 4), (8, "type", 3)]  # community 0 only
    eng.apply_delta(insert=insert)
    plan = plan_repair(eng.graph, eng.graph.delta_since(0), eng.n)
    frozen = mask_before & ~plan.affected
    assert frozen[half:graph.n_nodes].any()  # community 1 stayed frozen
    np.testing.assert_array_equal(
        state.sp_L_host[:, frozen, :], L_before[:, frozen, :]
    )
    # and previously-finite entries anywhere are never rewritten (freeze)
    was = np.isfinite(L_before)
    np.testing.assert_array_equal(state.sp_L_host[was], L_before[was])


@pytest.mark.parametrize("engine", ENGINES)
def test_differential_single_path_interleaving(engine):
    """Single-path extension of the differential acceptance test: under a
    random write/read interleaving, the repaired (T, L) state must match
    drop-and-recompute on T (pair sets) and still yield oracle-valid
    witnesses.  Lengths may legitimately differ from a fresh closure's, so
    validity is asserted, not equality."""
    rng = np.random.default_rng(100 + ENGINES.index(engine))
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    n = 24
    graph = random_labeled_graph(n, 50, ["a", "b"], seed=8)
    graph.edges[:] = sorted(set(graph.edges))
    eng = QueryEngine(graph, config=EngineConfig(engine=engine))
    plans = CompiledClosureCache()

    def random_edge():
        return (
            int(rng.integers(0, n)),
            ["a", "b"][int(rng.integers(0, 2))],
            int(rng.integers(0, n)),
        )

    a0 = g.index_of("S")
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
        got = eng.query(
            Query(g, "S", sources=sources, semantics="single_path")
        )
        scratch = QueryEngine(
            Graph(n, list(graph.edges)), plans=plans,
            config=EngineConfig(engine=engine),
        )
        want = scratch.query(Query(g, "S", sources=sources))
        assert got.pairs == want.pairs, (engine, step, sources)
        (state,) = eng._states.values()
        L = state.sp_L_host
        for (i, j), path in got.paths.items():
            ann = None if not path else int(L[a0, i, j])
            assert_path_witness(graph, g, "S", i, j, path, length=ann)


def test_sharded_state_repair_evict_mechanics():
    """Delta mechanics on a mesh-backed opt engine (both semantics): an
    insert repairs the cached sharded states in place through the
    single-device repair path (next query is a pure *hit* matching
    scratch), a delete evicts ancestor rows (*warm* recompute re-shards
    the state), and witnesses stay oracle-valid throughout.  Runs on a
    1x1 host mesh; the write/read interleaving differential across real
    multi-device meshes is
    tests/test_distributed_masked.py::test_sharded_engine_delta_interleaving
    (whose 1x1 case also runs under tier-1)."""
    from repro.shard import make_mesh

    mesh = make_mesh((1, 1))
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=1)
    eng = QueryEngine(graph, config=EngineConfig(engine="opt", mesh=mesh))
    src = (0, 3, 7)
    eng.query(Query(g, "S", sources=src))
    eng.query(Query(g, "S", sources=src, semantics="single_path"))

    st = eng.apply_delta(
        insert=[(0, "type", 5), (5, "subClassOf", 3), (9, "type_r", 2)]
    )
    assert st.rows_repaired > 0 and st.repair_iters >= 1
    r = eng.query(Query(g, "S", sources=src))
    assert r.stats["cache"] == "hit"  # repaired in place, not dropped
    assert r.pairs == _pairs_for(graph, g, src)
    r_sp = eng.query(Query(g, "S", sources=src, semantics="single_path"))
    assert r_sp.stats["cache"] == "hit" and r_sp.pairs == r.pairs

    victim = next(e for e in graph.edges if e[0] == 0)  # evicts a src row
    st2 = eng.apply_delta(delete=[victim])
    assert st2.rows_evicted > 0
    r2 = eng.query(Query(g, "S", sources=src))
    assert r2.stats["cache"] == "warm"  # evicted rows recompute + re-shard
    assert r2.pairs == _pairs_for(graph, g, src)
    r2_sp = eng.query(Query(g, "S", sources=src, semantics="single_path"))
    assert r2_sp.pairs == r2.pairs
    for (i, j), path in r2_sp.paths.items():
        assert_path_witness(graph, g, "S", i, j, path)


# ---------------------------------------------------------------------- #
# Edge-log compaction (core/graph.py)
# ---------------------------------------------------------------------- #


def test_compact_log_truncates_and_errors_cleanly():
    g = Graph(5, [(0, "a", 1)])
    g.insert_edges([(1, "a", 2)])  # v1
    g.insert_edges([(2, "a", 3)])  # v2
    g.delete_edges([(0, "a", 1)])  # v3
    assert g.compact_log(2) == 2  # v1 + v2 entries dropped
    # deltas from the floor onward still work
    d = g.delta_since(2)
    assert set(d.deleted) == {(0, "a", 1)} and not d.inserted
    assert not g.delta_since(3)
    # pre-compaction versions error cleanly instead of returning a
    # silently-partial delta
    with pytest.raises(ValueError, match="compacted"):
        g.delta_since(0)
    with pytest.raises(ValueError, match="compacted"):
        g.delta_since(1)
    # compacting beyond the graph's version is refused
    with pytest.raises(ValueError):
        g.compact_log(99)
    # idempotent / monotone floor
    assert g.compact_log(1) == 0
    with pytest.raises(ValueError):
        g.delta_since(1)


def test_compaction_of_noop_tail_resyncs_without_drop_or_crash():
    """Regression: compacting a net no-op log tail past the engine's
    version must not strand the engine at a pre-floor version — the next
    apply_delta would crash in delta_since — nor drop valid caches when
    the served content is unchanged."""
    graph = Graph(3, [(0, "a", 1)])
    g = Grammar.from_text("S -> a").to_cnf()
    eng = QueryEngine(graph)
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1)}
    graph.insert_edges([(1, "a", 2)])
    graph.delete_edges([(1, "a", 2)])  # net no-op, version advanced to 2
    graph.compact_log(graph.version)  # engine's version is now pre-floor
    r = eng.query(Query(g, "S", sources=(0,)))
    assert r.stats["cache"] == "hit"  # content unchanged: cache survives
    eng.apply_delta(insert=[(0, "a", 2)])  # must not raise
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1), (0, 2)}


def test_engine_falls_back_to_full_drop_after_compaction():
    """A consumer whose version predates the compaction floor cannot read
    a delta; the engine must resynchronize from the snapshot (full drop)
    instead of crashing or serving stale rows."""
    graph = Graph(3, [(0, "a", 1)])
    g = Grammar.from_text("S -> a").to_cnf()
    eng = QueryEngine(graph)
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1)}
    graph.insert_edges([(0, "a", 2)])
    graph.compact_log(graph.version)  # engine's version is now pre-floor
    r = eng.query(Query(g, "S", sources=(0,)))
    assert r.stats["cache"] == "miss"  # full invalidation, not repair
    assert r.pairs == {(0, 1), (0, 2)}


# ---------------------------------------------------------------------- #
# Epoch snapshots (delta/txn.py)
# ---------------------------------------------------------------------- #


def test_epoch_clock_unit():
    clock = EpochClock(version=5)
    snap = clock.snapshot()
    clock.validate(snap)
    clock.validate(None)
    assert clock.advance(7) == 1
    assert clock.snapshot() == Snapshot(1, 7)
    with pytest.raises(StaleSnapshotError):
        clock.validate(snap)


def test_apply_delta_never_serves_stale_rows_under_snapshot():
    """Acceptance: a batch pinned to a pre-delta snapshot errors instead of
    returning stale rows, and post-delta queries always reflect the
    mutated graph at the advanced epoch."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=4)
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    src = (0, 2)
    r0 = eng.query(Query(g, "S", sources=src))
    assert r0.stats["epoch"] == 0
    snap = eng.snapshot()
    eng.apply_delta(insert=[(0, "type", 9)])
    with pytest.raises(StaleSnapshotError):
        eng.query(Query(g, "S", sources=src), snapshot=snap)
    r1 = eng.query(Query(g, "S", sources=src), snapshot=eng.snapshot())
    assert r1.stats["epoch"] == 1
    assert r1.pairs == _pairs_for(graph, g, src)
    # a delta committed via the graph API (not apply_delta) is ingested at
    # the next batch and also invalidates older snapshots
    snap1 = eng.snapshot()
    graph.insert_edges([(1, "type", 9)])
    with pytest.raises(StaleSnapshotError):
        eng.query(Query(g, "S", sources=src), snapshot=snap1)
    r2 = eng.query(Query(g, "S", sources=src))
    assert r2.stats["epoch"] == 2
    assert r2.pairs == _pairs_for(graph, g, src)


def test_out_of_band_edit_still_invalidates_and_advances_epoch():
    graph = Graph(3, [(0, "a", 1)])
    g = Grammar.from_text("S -> a").to_cnf()
    eng = QueryEngine(graph)
    snap = eng.snapshot()
    assert eng.query(Query(g, "S", sources=(0,))).pairs == {(0, 1)}
    graph.edges.append((0, "a", 2))  # bypasses the log entirely
    r = eng.query(Query(g, "S", sources=(0,)))
    assert r.stats["cache"] == "miss"  # full drop, legacy path
    assert r.pairs == {(0, 1), (0, 2)}
    with pytest.raises(StaleSnapshotError):
        eng.query(Query(g, "S", sources=(0,)), snapshot=snap)


def test_out_of_band_edit_concurrent_with_logged_edit_not_masked():
    """Regression: an out-of-band edit arriving in the same window as a
    logged edit must still force full invalidation — the repaired-in-place
    cache would otherwise silently miss the unlogged edge."""
    graph = Graph(8, [(0, "a", 1)])
    g = Grammar.from_text("S -> a | b").to_cnf()
    eng = QueryEngine(graph)
    assert eng.query(Query(g, "S", sources=(5,))).pairs == set()
    graph.edges.append((5, "a", 6))  # out-of-band
    graph.insert_edges([(6, "b", 7)])  # logged, same window
    r = eng.query(Query(g, "S", sources=(5, 6)))
    assert r.stats["cache"] == "miss"  # full drop, not masked by repair
    assert r.pairs == {(5, 6), (6, 7)}


def test_delta_stats_surfaced_in_query_results():
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=5)
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    eng.query(Query(g, "S", sources=(0,)))
    eng.apply_delta(insert=[(0, "type", 3)])
    eng.apply_delta(delete=[graph.edges[0]])
    stats = eng.query(Query(g, "S", sources=(0,))).stats
    assert stats["rows_repaired"] > 0
    assert stats["rows_evicted"] > 0
    assert stats["repair_iters"] >= 1
    assert stats["epoch"] == 2


def test_noop_delta_does_not_advance_epoch_or_drop_cache():
    g = query1_grammar().to_cnf()
    graph = ontology_graph(30, 60, seed=6)
    eng = QueryEngine(graph, config=EngineConfig(engine="dense"))
    eng.query(Query(g, "S", sources=(0,)))
    st = eng.apply_delta(insert=[graph.edges[0]])  # already present
    assert st.rows_repaired == 0 and eng.clock.epoch == 0
    assert eng.query(Query(g, "S", sources=(0,))).stats["cache"] == "hit"
