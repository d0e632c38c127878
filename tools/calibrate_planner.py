"""Fit the planner's per-host cost profile and persist it as JSON.

    PYTHONPATH=src python -m tools.calibrate_planner --out planner_profile.json
    PYTHONPATH=src python -m tools.calibrate_planner --smoke --out /tmp/p.json

The planner (``repro.engine.planner``) prices each candidate executable
as ``cost_s ≈ beta + alpha · work_Munits``.  This tool *measures* those
coefficients on the current host instead of trusting the built-in
defaults: for every executable family it runs real pinned closures over
an (n, sources) grid on the community workload (the same graph family the
engine benchmarks use), records ``(work, seconds)`` observations, and
least-squares fits ``(alpha, beta)`` per family.  ``reach_factor`` — how
far the active set outgrows its seed — is measured from the same runs.
The ``move`` family (placement-mismatch penalty) is timed as the host
round-trip of a cached state tensor.

The fitted :class:`~repro.engine.planner.PlannerProfile` is persisted
versioned (``PROFILE_VERSION``); engines pick it up via
``EngineConfig(profile=...)`` or the ``REPRO_PLANNER_PROFILE`` env var.

Every run ends with the **calibration round-trip check**: the profile is
saved, reloaded, and the reloaded planner must make byte-identical
decisions across a feature grid — persistence can never change routing.
Exit status is nonzero if the round-trip fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    PlanFeatures,
    Planner,
    PlannerProfile,
    Query,
    QueryEngine,
)
from repro.engine.planner import _DEFAULT_COEF, _work_munits, host_fingerprint

GRAMMAR = "S -> up S down | up down"
COMMUNITY = 128  # nodes per disjoint tree community


def community_graph(n: int, branching: int = 3, seed: int = 0) -> Graph:
    """A forest of n/COMMUNITY disjoint trees with up/down edge pairs
    (bench_engine's workload: single-source reach stays in-community)."""
    rng = np.random.default_rng(seed)
    edges: list[tuple[int, str, int]] = []
    for c in range(1, COMMUNITY):
        p = int(rng.integers(max(0, (c - 1) // branching), c))
        edges.append((c, "up", p))
        edges.append((p, "down", c))
    return Graph(COMMUNITY, edges).repeat(n // COMMUNITY)


def _time(fn) -> tuple[object, float]:
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def measure_backend(
    backend: str,
    semantics: str,
    sizes: list[int],
    source_counts: list[int | str],
    plans: CompiledClosureCache,
) -> tuple[list[tuple[float, float]], list[float]]:
    """``(work_Munits, seconds)`` observations for one (backend, semantics)
    family over the measurement grid, plus observed active/seed reach
    ratios.  Each point is a cold pinned query (compiles pre-warmed on a
    throwaway engine, so the timing is closure work, not tracing)."""
    g = Grammar.from_text(GRAMMAR).to_cnf()
    family = f"sp_{backend}" if semantics == "single_path" else backend
    obs: list[tuple[float, float]] = []
    reach: list[float] = []
    for n in sizes:
        graph = community_graph(n)
        cfg = EngineConfig(engine=backend)
        for r_spec in source_counts:
            r = n if r_spec == "n" else min(int(r_spec), n // COMMUNITY)
            if r_spec == "n":
                q = Query(g, "S", semantics=semantics)  # all-pairs
                seed = graph.n_nodes
            else:
                srcs = tuple(t * COMMUNITY + 1 for t in range(r))
                q = Query(g, "S", sources=srcs, semantics=semantics)
                seed = r
            QueryEngine(graph, plans=plans, config=cfg).query(q)  # warm
            eng = QueryEngine(graph, plans=plans, config=cfg)
            res, secs = _time(lambda: eng.query(q))
            active = res.stats["active_rows"]
            # the decision prices one fixpoint run at the planner's
            # predicted capacity; regress against the capacity the run
            # actually needed so alpha reflects converged work
            cap = res.stats.planner["row_capacity"] if res.stats.planner else n
            cap = max(cap, active)
            work = _work_munits(
                family, max(len(g.binary_prods), 1), cap, n, 1
            )
            obs.append((work, secs))
            if r_spec != "n":
                reach.append(active / max(seed, 1))
    return obs, reach


def measure_move(sizes: list[int]) -> list[tuple[float, float]]:
    """Host round-trip cost of a cached state tensor (the placement
    penalty the cost model charges when a state lives elsewhere)."""
    import jax.numpy as jnp

    g = Grammar.from_text(GRAMMAR).to_cnf()
    obs: list[tuple[float, float]] = []
    for n in sizes:
        T = jnp.zeros((g.n_nonterms, n, n), dtype=jnp.bool_)
        T.block_until_ready()
        _, secs = _time(
            lambda: jnp.asarray(np.asarray(T)).block_until_ready()
        )
        obs.append((g.n_nonterms * n * n / 1e6, secs))
    return obs


def fit_affine(obs: list[tuple[float, float]]) -> tuple[float, float]:
    """Least-squares ``seconds ≈ beta + alpha · work``, clamped positive
    (a negative intercept/slope would invert the cost ranking)."""
    a = np.array([[w, 1.0] for w, _ in obs])
    y = np.array([s for _, s in obs])
    alpha, beta = np.linalg.lstsq(a, y, rcond=None)[0]
    return max(float(alpha), 1e-9), max(float(beta), 1e-6)


def calibrate(
    sizes: list[int],
    source_counts: list[int | str],
    backends: list[str],
    log=print,
) -> PlannerProfile:
    plans = CompiledClosureCache()  # shared: compiles amortize across points
    coef: dict[str, tuple[float, float]] = {}
    reach_all: list[float] = []
    for semantics, names in (
        ("relational", backends),
        ("single_path", [b for b in backends if b != "bitpacked"]),
    ):
        for backend in names:
            obs, reach = measure_backend(
                backend, semantics, sizes, source_counts, plans
            )
            family = (
                f"sp_{backend}" if semantics == "single_path" else backend
            )
            coef[family] = fit_affine(obs)
            reach_all.extend(reach)
            log(
                f"[calibrate] {family}: alpha={coef[family][0]:.3e} "
                f"beta={coef[family][1]:.3e} ({len(obs)} points)"
            )
    coef["move"] = fit_affine(measure_move(sizes))
    log(
        f"[calibrate] move: alpha={coef['move'][0]:.3e} "
        f"beta={coef['move'][1]:.3e}"
    )
    # families not measured on this host (e.g. opt without a mesh) keep
    # the built-in defaults so the profile stays complete and versioned
    for family, ab in _DEFAULT_COEF.items():
        coef.setdefault(family, ab)
    reach = float(np.median(reach_all)) if reach_all else 16.0
    return PlannerProfile(
        host=host_fingerprint(),
        fitted=True,
        coef=coef,
        reach_factor=max(reach, 1.0),
    )


def decision_grid(profile: PlannerProfile) -> list[dict]:
    """Planner decisions across a canonical feature grid — the round-trip
    equivalence check (fit → persist → reload → same decisions) compares
    these between the in-memory and reloaded profiles."""
    planner = Planner(profile)
    out = []
    for n in (256, 1024, 4096):
        for seed_rows in (1, 8, 128, n):
            for semantics in ("relational", "single_path"):
                for mesh_devices in (0, 2):
                    f = PlanFeatures(
                        n=n,
                        seed_rows=seed_rows,
                        new_rows=seed_rows,
                        density=2.0,
                        n_prods=2,
                        n_nonterms=2,
                        semantics=semantics,
                        mesh_devices=mesh_devices,
                    )
                    out.append(planner.decide(f).to_dict())
    return out


def verify_round_trip(profile: PlannerProfile, path) -> bool:
    """Persist → reload → identical decisions on the canonical grid."""
    reloaded = PlannerProfile.load(path)
    return decision_grid(profile) == decision_grid(reloaded)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="planner_profile.json")
    ap.add_argument("--sizes", type=int, nargs="+", default=[256, 512, 1024])
    ap.add_argument(
        "--sources",
        nargs="+",
        default=["1", "4", "n"],
        help="source counts per size; 'n' means all-pairs",
    )
    ap.add_argument(
        "--backends", nargs="+", default=["dense", "frontier", "bitpacked"]
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny grid (n=256, two points/backend): seconds, for CI",
    )
    args = ap.parse_args(argv)
    sizes = [256] if args.smoke else args.sizes
    sources: list[int | str] = ["1", "n"] if args.smoke else args.sources
    sources = [s if s == "n" else int(s) for s in sources]

    profile = calibrate(sizes, sources, args.backends)
    path = profile.save(args.out)
    print(f"[calibrate] profile -> {path}")
    if not verify_round_trip(profile, path):
        print("[calibrate] ROUND-TRIP FAILED: reloaded profile decides differently")
        return 1
    print("[calibrate] round-trip OK: reloaded profile makes identical decisions")
    print(json.dumps(profile.to_json(), indent=2))
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
