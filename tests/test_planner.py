"""Cost-based planner: auto routing equals every pinned backend, the
mid-closure fallback re-dispatches correctly, profiles round-trip through
JSON without changing decisions, and the legacy kwarg spelling warns.

The differential tests are the planner's correctness contract: whatever
the cost model picks, results must be *identical* to every pinned
backend — the planner may only ever change the price, never the answer.
"""
from __future__ import annotations

import asyncio

import pytest

from repro.core.grammar import query1_grammar
from repro.core.graph import ontology_graph, paper_example_graph
from repro.core.semantics import evaluate_relational
from repro.engine import (
    EngineConfig,
    PlanFeatures,
    Planner,
    PlannerProfile,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES
from repro.engine.planner import PROFILE_VERSION
from repro.serve import CFPQServer, ServeConfig

from helpers import assert_path_witness

ENGINES = sorted(MASKED_ENGINES)


# --------------------------------------------------------------------- #
# differential: auto == every pinned backend
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("pinned", ENGINES)
def test_auto_matches_pinned_relational(pinned):
    """Masked and all-pairs relational results under auto equal every
    pinned backend's, on the paper example and an ontology graph."""
    g = query1_grammar().to_cnf()
    for graph_fn in (
        lambda: paper_example_graph(),
        lambda: ontology_graph(40, 99, seed=2),
    ):
        graph = graph_fn()
        auto = QueryEngine(graph)
        pin = QueryEngine(graph_fn(), config=EngineConfig(engine=pinned))
        nn = graph.n_nodes
        for sources in [(0,), tuple({1 % nn, 2 % nn}), None]:
            qa = auto.query(Query(g, "S", sources=sources))
            qp = pin.query(Query(g, "S", sources=sources))
            assert qa.pairs == qp.pairs, (pinned, sources)


@pytest.mark.parametrize("pinned", ENGINES)
def test_auto_matches_pinned_single_path(pinned):
    """Single-path support sets under auto equal every pinned backend's,
    and every auto witness is a valid derivation."""
    g = query1_grammar().to_cnf()
    graph = ontology_graph(15, 25, seed=2)
    auto = QueryEngine(graph)
    pin = QueryEngine(
        ontology_graph(15, 25, seed=2), config=EngineConfig(engine=pinned)
    )
    qa = auto.query(Query(g, "S", semantics="single_path"))
    qp = pin.query(Query(g, "S", semantics="single_path"))
    assert qa.pairs == qp.pairs
    for (i, j), path in qa.paths.items():
        assert_path_witness(graph, g, "S", i, j, path)


def test_decision_recorded_in_stats():
    g = query1_grammar().to_cnf()
    eng = QueryEngine(ontology_graph(40, 99, seed=2))
    r = eng.query(Query(g, "S", sources=(0,)))
    d = r.stats.planner
    assert d is not None and not d["pinned"]
    assert d["engine"] in MASKED_ENGINES
    assert d["mode"] in ("masked", "allpairs")
    assert d["label"].startswith(d["engine"])
    assert d["candidates"]  # every considered executable was priced
    assert r.stats["engine"] == d["engine"]  # no fallback on this run
    # cache hits plan nothing (no closure ran) but keep the served-by tag
    r2 = eng.query(Query(g, "S", sources=(0,)))
    assert r2.stats["cache"] == "hit"
    assert r2.stats.planner is None
    assert r2.stats["engine"] == d["engine"]


def test_pinned_decision_recorded_and_never_falls_back():
    g = query1_grammar().to_cnf()
    profile = PlannerProfile(fallback_active_frac=0.0, fallback_max_calls=0)
    eng = QueryEngine(
        ontology_graph(40, 99, seed=2),
        config=EngineConfig(engine="dense", profile=profile),
    )
    # the reachable set (139 rows) overflows the 128 bucket — observation
    # points occur, but a pinned engine must never re-dispatch
    r = eng.query(Query(g, "S", sources=(0, 5, 17)))
    assert r.stats["active_rows"] > 128
    assert r.stats.planner["pinned"]
    assert r.stats.fallback is None
    assert eng.planner.stats.fallbacks == 0


# --------------------------------------------------------------------- #
# forced fallback: threshold 0 arms the re-dispatch at the first overflow
# --------------------------------------------------------------------- #
def test_forced_fallback_redispatches_and_stays_correct():
    g = query1_grammar().to_cnf()
    graph = ontology_graph(40, 99, seed=2)
    want = evaluate_relational(graph, g, "S")
    sources = (0, 5, 17)
    # reach_factor=1 keeps the initial pick at the 128 bucket; the 139-row
    # reachable set overflows it, and a zero active-row threshold turns
    # that first overflow observation into a forced fallback.  The
    # coefficients are shaped so dense wins the masked bucket but
    # bitpacked wins at full capacity (dense work grows with cap², packed
    # work only with cap) — giving the decision a distinct fallback target.
    profile = PlannerProfile(
        fallback_active_frac=0.0,
        reach_factor=1.0,
        coef={
            "dense": (1e-3, 0.0),
            "bitpacked": (25e-3, 0.0),
            "frontier": (1.0, 1.0),
        },
    )
    eng = QueryEngine(graph, config=EngineConfig(profile=profile))
    r = eng.query(Query(g, "S", sources=sources))
    fb = r.stats.fallback
    assert fb is not None, "overflow point must have forced the fallback"
    assert fb["trigger"] == "active_rows"
    assert fb["to"] == r.stats.planner["fallback_engine"]
    assert fb["to"] != r.stats.planner["engine"]
    assert r.stats["engine"] == fb["to"]  # served by the fallback backend
    assert eng.planner.stats.fallbacks == 1
    # the re-dispatched closure is the same monotone fixpoint: exact rows
    assert r.pairs == {(i, j) for (i, j) in want if i in sources}


def test_should_fallback_thresholds():
    planner = Planner(
        PlannerProfile(
            fallback_active_frac=0.5,
            fallback_max_calls=3,
            # dense wins masked, bitpacked wins full capacity — so the
            # decision carries a distinct fallback target (see the forced
            # fallback test for the work-scaling argument)
            coef={
                "dense": (1e-3, 0.0),
                "bitpacked": (25e-3, 0.0),
                "frontier": (1.0, 1.0),
            },
        )
    )
    f = PlanFeatures(
        n=256, seed_rows=4, new_rows=4, density=2.0, n_prods=2, n_nonterms=2
    )
    d = planner.decide(f)
    assert d.fallback_engine is not None
    assert planner.should_fallback(d, active_rows=10, n=256, calls=1) is None
    assert (
        planner.should_fallback(d, active_rows=128, n=256, calls=1)
        == "active_rows"
    )
    assert planner.should_fallback(d, active_rows=10, n=256, calls=3) == "calls"
    pinned = planner.decide(f, pin="dense")
    assert planner.should_fallback(pinned, 256, 256, 99) is None


# --------------------------------------------------------------------- #
# profile persistence
# --------------------------------------------------------------------- #
def test_profile_round_trip_same_decisions(tmp_path):
    profile = PlannerProfile(
        host="test-host",
        fitted=True,
        coef={"dense": (3e-4, 2e-3), "bitpacked": (1e-3, 1e-3)},
        reach_factor=8.0,
    )
    path = profile.save(tmp_path / "profile.json")
    reloaded = PlannerProfile.load(path)
    assert reloaded == profile
    grid = [
        PlanFeatures(
            n=n, seed_rows=r, new_rows=r, density=2.0, n_prods=2,
            n_nonterms=2, semantics=sem,
        )
        for n in (256, 1024)
        for r in (1, 64, 256)
        for sem in ("relational", "single_path")
    ]
    a, b = Planner(profile), Planner(reloaded)
    for f in grid:
        assert a.decide(f).to_dict() == b.decide(f).to_dict()


def test_profile_version_mismatch_raises(tmp_path):
    bad = dict(PlannerProfile().to_json(), version=PROFILE_VERSION + 1)
    import json

    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="version"):
        PlannerProfile.load(p)


# --------------------------------------------------------------------- #
# API surface: legacy kwargs warn, config wins, serve stats tally routes
# --------------------------------------------------------------------- #
def test_legacy_kwargs_raise_deprecation_warning():
    graph = paper_example_graph()
    with pytest.warns(DeprecationWarning, match="EngineConfig"):
        eng = QueryEngine(graph, engine="dense")
    assert eng.engine == "dense"  # legacy spelling keeps the legacy default
    with pytest.warns(DeprecationWarning):
        eng = QueryEngine(graph, row_capacity=128)
    assert eng.engine == "dense"  # partial legacy kwargs: still legacy


def test_config_and_legacy_kwargs_are_exclusive():
    graph = paper_example_graph()
    with pytest.raises(ValueError, match="EngineConfig"):
        QueryEngine(graph, engine="dense", config=EngineConfig())


def test_bare_constructor_defaults_to_auto_without_warning(recwarn):
    eng = QueryEngine(paper_example_graph())
    assert eng.engine == "auto"
    assert not [
        w for w in recwarn.list if issubclass(w.category, DeprecationWarning)
    ]


def test_engine_config_validation():
    with pytest.raises(ValueError, match="unknown engine"):
        EngineConfig(engine="nope")
    with pytest.raises(ValueError, match="row_capacity"):
        EngineConfig(row_capacity=0)


def test_explicit_axis_mesh_is_refused_up_front():
    """``with_sharding_constraint`` refuses Explicit mesh axes; the engine
    says so at construction, not inside the first sharded compile."""
    import jax
    from jax.sharding import AxisType

    from repro.shard import make_mesh

    explicit = jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Explicit,) * 2
    )
    with pytest.raises(ValueError, match="must be Auto"):
        QueryEngine(
            paper_example_graph(),
            config=EngineConfig(engine="opt", mesh=explicit),
        )
    QueryEngine(  # the repo's own meshes are Auto
        paper_example_graph(),
        config=EngineConfig(engine="opt", mesh=make_mesh((1, 1))),
    )


def test_serve_stats_tally_planner_routes():
    g = query1_grammar().to_cnf()
    eng = QueryEngine(ontology_graph(40, 99, seed=2))

    async def run():
        async with CFPQServer(
            eng, ServeConfig(max_batch=4, batch_window_s=0.001)
        ) as srv:
            rs = await asyncio.gather(
                *[srv.submit(Query(g, "S", sources=(m,))) for m in (0, 3, 7)]
            )
            return rs, dict(srv.stats.planner_routes), srv.stats.fallbacks

    rs, routes, fallbacks = asyncio.run(run())
    assert len(rs) == 3
    # at least the first flushed window ran a planned closure; later ones
    # may be pure cache hits (tallying nothing) — but every tallied label
    # is a real decision label
    assert sum(routes.values()) >= 1
    assert all(":" in label for label in routes)
    assert fallbacks == 0
