"""Distributed masked closures for the `opt` engine (ISSUE 5 tentpole).

Differentially locks the sharded masked closures — ``masked_opt_closure``
and ``masked_opt_single_path_closure`` — against the single-device masked
engines and the Hellings worklist baseline, for every mesh shape in
{1x1, 2x1, 4x2}, plus the sharded-state repair/evict path through a
mesh-backed ``QueryEngine``.

These tests run *in-process*: under the tier-1 suite (one device) only
the 1x1 shapes run and the larger meshes skip; the dedicated multi-device
CI lane (`distributed` job in .github/workflows/ci.yml) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest
starts, so the full mesh matrix runs on every PR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test dependency: pip install -e .[test]
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    given = settings = st = None

from repro.baselines import hellings_cfpq
from repro.core import closure
from repro.core.grammar import Grammar, query1_grammar
from repro.core.graph import Graph, ontology_graph, random_labeled_graph
from repro.core.matrices import LANE, ProductionTables, init_matrix
from repro.core.semantics import PathExtractor, base_lengths
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.shard import make_mesh
from helpers import (
    assert_path_witness,
    masked_oracle_run,
    random_cnf,
    random_graph,
)

MESH_SHAPES = [(1, 1), (2, 1), (4, 2)]


def mesh_params():
    """Every mesh shape, with the ones this process cannot host skipped
    (the multi-device CI lane forces 8 host devices and runs them all)."""
    return [
        pytest.param(
            s,
            marks=pytest.mark.skipif(
                s[0] * s[1] > jax.device_count(),
                reason=f"needs {s[0] * s[1]} devices "
                "(runs in the multi-device CI lane)",
            ),
            id=f"{s[0]}x{s[1]}",
        )
        for s in MESH_SHAPES
    ]


#: shared across the module so mesh-keyed plans compile once per shape
PLANS = CompiledClosureCache()


def _mesh(shape):
    return make_mesh(shape)


# ---------------------------------------------------------------------- #
# Differential: masked_opt == masked == Hellings, per mesh shape
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("mesh_shape", mesh_params())
@pytest.mark.parametrize("seed", range(3))
def test_masked_opt_matches_masked_and_hellings(mesh_shape, seed):
    """Acceptance: on random graphs/grammars, rows of the sharded opt
    closure under its mask are bit-identical to the single-device masked
    closure AND set-equal to the Hellings worklist baseline, for every
    mesh shape."""
    rng = np.random.default_rng(seed)
    g = random_cnf(rng)
    graph = random_graph(rng, n_nodes=10, n_edges=24)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    sources = sorted(set(int(s) for s in rng.integers(0, graph.n_nodes, 3)))
    src = np.zeros(n, bool)
    src[sources] = True

    ref_T, ref_M, ovf, _ = closure.masked_closure(
        T0, tables, jnp.asarray(src), row_capacity=n
    )
    assert not bool(ovf)
    ref_T, ref_M = np.asarray(ref_T), np.asarray(ref_M)
    base = hellings_cfpq(graph, g)

    T, M, _ = masked_oracle_run(
        T0, tables, src, mesh_shape=mesh_shape, row_capacity=n
    )
    np.testing.assert_array_equal(M, ref_M)
    np.testing.assert_array_equal(T[:, M, :], ref_T[:, M, :])
    nn = graph.n_nodes
    for a, name in enumerate(g.nonterms):
        got = {
            (int(i), int(j))
            for i, j in zip(*np.nonzero(T[a, :nn, :nn]))
            if M[i]
        }
        want = {(i, j) for (i, j) in base[name] if M[i]}
        assert got == want, (mesh_shape, seed, name)


@pytest.mark.parametrize("mesh_shape", mesh_params())
def test_masked_opt_single_path_matches_masked_and_oracle(mesh_shape):
    """The sharded single-path closure: isfinite(L) rows under the mask
    equal the Boolean masked closure rows, and extracted witnesses pass
    the path oracle with the frozen length annotation, per mesh shape."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(20, 40, seed=5)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    n = T0.shape[-1]
    src = np.zeros(n, bool)
    src[[0, 7]] = True

    ref_T, ref_M, _, _ = closure.masked_closure(
        T0, tables, jnp.asarray(src), row_capacity=n
    )
    ref_T, ref_M = np.asarray(ref_T), np.asarray(ref_M)

    L, M, _ = masked_oracle_run(
        base_lengths(T0),
        tables,
        src,
        mesh_shape=mesh_shape,
        row_capacity=n,
        single_path=True,
    )
    np.testing.assert_array_equal(M, ref_M)
    np.testing.assert_array_equal(np.isfinite(L)[:, M, :], ref_T[:, M, :])
    ex = PathExtractor(graph, g)
    a0 = g.index_of("S")
    for m in (0, 7):
        for j in np.nonzero(np.isfinite(L[a0, m, : graph.n_nodes]))[0]:
            path = ex.extract(L, "S", m, int(j))
            assert_path_witness(
                graph, g, "S", m, int(j), path, length=int(L[a0, m, j])
            )


# ---------------------------------------------------------------------- #
# Ragged source sets + bucket-growth warm restarts (property test)
# ---------------------------------------------------------------------- #

#: fixed grammar so hypothesis examples share compiled executables
_RAGGED_G = Grammar.from_text("S -> a S b | a b").to_cnf()
_RAGGED_TABLES = ProductionTables.from_grammar(_RAGGED_G)


def _assert_ragged_invariants(graph, sources, row_capacity, mesh_shape):
    """Oracle-runner assertions shared by the hypothesis property and its
    fixed-seed fallback: the warm-restart ladder starting at
    ``row_capacity`` reaches the same fixpoint as the single-shot
    full-capacity run, already-converged Boolean rows / finite lengths
    are bit-identical across restarts, and mesh shapes agree."""
    T0 = init_matrix(graph, _RAGGED_G)
    n = T0.shape[-1]
    src = np.zeros(n, bool)
    src[sources] = True

    ref_T, ref_M, ovf, _ = closure.masked_closure(
        T0, _RAGGED_TABLES, jnp.asarray(src), row_capacity=n
    )
    assert not bool(ovf)
    ref_T, ref_M = np.asarray(ref_T), np.asarray(ref_M)

    T, M, snaps = masked_oracle_run(
        T0, _RAGGED_TABLES, src, mesh_shape=mesh_shape,
        row_capacity=row_capacity,
    )
    np.testing.assert_array_equal(M, ref_M)
    np.testing.assert_array_equal(T[:, M, :], ref_T[:, M, :])
    # monotone warm restarts: entries never retract across the ladder
    for (t_a, m_a), (t_b, m_b) in zip(snaps, snaps[1:]):
        assert not (t_a & ~t_b).any(), "restart lost a Boolean entry"
        assert not (m_a & ~m_b).any(), "restart lost a mask row"
        # rows already at the all-pairs fixpoint are frozen: bit-identical
        done = m_a & (t_a == ref_T).all(axis=(0, 2))
        np.testing.assert_array_equal(t_b[:, done, :], t_a[:, done, :])

    # single-path: finite entries are frozen across restarts + mesh shapes
    L, ML, lsnaps = masked_oracle_run(
        base_lengths(T0), _RAGGED_TABLES, src, mesh_shape=mesh_shape,
        row_capacity=row_capacity, single_path=True,
    )
    np.testing.assert_array_equal(ML, ref_M)
    np.testing.assert_array_equal(np.isfinite(L)[:, ML, :], ref_T[:, ML, :])
    for (l_a, _), (l_b, _) in zip(lsnaps, lsnaps[1:]):
        was = np.isfinite(l_a)
        np.testing.assert_array_equal(l_b[was], l_a[was])


if st is not None:

    @pytest.mark.parametrize("mesh_shape", mesh_params())
    @settings(max_examples=8, deadline=None)
    @given(data=st.data())
    def test_ragged_sources_warm_restart_property(mesh_shape, data):
        """Hypothesis-driven ragged source sets on the opt path: source
        counts spanning 1 … n and row capacities spanning {1, LANE-1,
        LANE, n} must all reach the single-shot fixpoint with frozen rows
        bit-identical across bucket-growth restarts."""
        seed = data.draw(st.integers(0, 2**31 - 1), label="graph_seed")
        n_nodes = data.draw(st.integers(2, 24), label="n_nodes")
        graph = random_labeled_graph(
            n_nodes, max(1, 2 * n_nodes), ["a", "b"], seed=seed
        )
        n_src = data.draw(st.integers(1, n_nodes), label="n_sources")
        rng = np.random.default_rng(seed)
        sources = sorted(
            set(int(s) for s in rng.integers(0, n_nodes, size=n_src))
        )
        n = init_matrix(graph, _RAGGED_G).shape[-1]
        row_capacity = data.draw(
            st.sampled_from([1, LANE - 1, LANE, n]), label="row_capacity"
        )
        _assert_ragged_invariants(graph, sources, row_capacity, mesh_shape)

else:  # property test skips cleanly on a bare checkout

    @pytest.mark.parametrize("mesh_shape", mesh_params())
    def test_ragged_sources_warm_restart_property(mesh_shape):
        pytest.importorskip("hypothesis")


@pytest.mark.parametrize("mesh_shape", mesh_params())
@pytest.mark.parametrize("row_capacity", [1, LANE - 1, LANE, 256])
def test_ragged_capacity_ladder_fixed_seeds(mesh_shape, row_capacity):
    """Deterministic backstop for the hypothesis property (runs on bare
    checkouts too), including R == n > LANE (130 nodes pad to 256)."""
    graph = ontology_graph(40, 90, seed=3)  # 130 nodes -> padded n = 256
    sources = [0, 1, graph.n_nodes - 1]
    _assert_ragged_invariants(graph, sources, row_capacity, mesh_shape)


# ---------------------------------------------------------------------- #
# Sharded-state delta repair/evict through the service
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("mesh_shape", mesh_params())
def test_sharded_engine_delta_interleaving(mesh_shape):
    """A mesh-backed opt engine under a random write/read interleaving
    (both semantics) matches a from-scratch dense engine at every step:
    inserts repair the sharded state row-wise (through the single-device
    repair path), deletes evict, and the next sharded query re-shards."""
    rng = np.random.default_rng(mesh_shape[0] * 10 + mesh_shape[1])
    g = Grammar.from_text("S -> a S b | a b").to_cnf()
    n = 24
    graph = random_labeled_graph(n, 50, ["a", "b"], seed=11)
    graph.edges[:] = sorted(set(graph.edges))
    eng = QueryEngine(
        graph, plans=PLANS,
        config=EngineConfig(engine="opt", mesh=_mesh(mesh_shape)),
    )
    scratch_plans = CompiledClosureCache()

    def random_edge():
        return (
            int(rng.integers(0, n)),
            ["a", "b"][int(rng.integers(0, 2))],
            int(rng.integers(0, n)),
        )

    for step in range(6):
        op = rng.random()
        if op < 0.35 and graph.edges:
            victim = graph.edges[int(rng.integers(0, len(graph.edges)))]
            eng.apply_delta(delete=[victim])
        elif op < 0.7:
            eng.apply_delta(insert=[random_edge() for _ in range(2)])
        sources = tuple(
            sorted(set(int(s) for s in rng.integers(0, n, size=3)))
        )
        scratch = QueryEngine(
            Graph(n, list(graph.edges)), plans=scratch_plans,
            config=EngineConfig(engine="dense"),
        )
        want = scratch.query(Query(g, "S", sources=sources))
        got = eng.query(Query(g, "S", sources=sources))
        assert got.pairs == want.pairs, (mesh_shape, step, sources)
        got_sp = eng.query(
            Query(g, "S", sources=sources, semantics="single_path")
        )
        assert got_sp.pairs == want.pairs, (mesh_shape, step, sources)
        for (i, j), path in got_sp.paths.items():
            assert_path_witness(graph, g, "S", i, j, path)


@pytest.mark.parametrize("mesh_shape", mesh_params())
def test_sharded_repair_freezes_unaffected_rows_bit_identical(mesh_shape):
    """The frozen-row repair contract holds for mesh-sharded states: an
    insert into one community leaves the other community's cached rows
    (Boolean and length) bit-identical after the repair."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(15, 25, seed=2).repeat(2)
    half = graph.n_nodes // 2
    eng = QueryEngine(
        graph, plans=PLANS,
        config=EngineConfig(engine="opt", mesh=_mesh(mesh_shape)),
    )
    eng.query(Query(g, "S"))
    eng.query(Query(g, "S", semantics="single_path"))
    (state,) = eng._states.values()
    T_before = np.array(state.T_host, copy=True)
    L_before = np.array(state.sp_L_host, copy=True)
    mask_before = np.array(state.mask, copy=True)

    from repro.delta.repair import plan_repair

    eng.apply_delta(insert=[(1, "subClassOf", 4), (8, "type", 3)])
    plan = plan_repair(eng.graph, eng.graph.delta_since(0), eng.n)
    frozen = mask_before & ~plan.affected
    assert frozen[half : graph.n_nodes].any()  # community 1 stayed frozen
    np.testing.assert_array_equal(
        state.T_host[:, frozen, :], T_before[:, frozen, :]
    )
    np.testing.assert_array_equal(
        state.sp_L_host[:, frozen, :], L_before[:, frozen, :]
    )
    was = np.isfinite(L_before)
    np.testing.assert_array_equal(state.sp_L_host[was], L_before[was])
