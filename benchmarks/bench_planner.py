"""Planner benchmark: does ``engine="auto"`` actually pick well?

    PYTHONPATH=src python -m benchmarks.bench_planner
    PYTHONPATH=src python -m benchmarks.bench_planner --smoke
    PYTHONPATH=src python -m benchmarks.bench_planner --profile prof.json

Two sections, emitted as ONE JSON object on stdout:

``points`` — the regret gate.  The host profile is calibrated in-process
(``tools/calibrate_planner.py``; ``--profile`` reuses a saved one), then
every grid point (n × source-count R, R ∈ {1, small, n}) is served cold
by the auto engine AND by every pinned backend.  Per point we report the
planner's pick, the best/worst pinned backend, and
``auto_vs_best = auto_s / best_pinned_s``.  The acceptance gate is
``auto_vs_best <= 1.10`` on every calibrated point — auto must be within
10% of the best pinned backend (it may *beat* pinned: the planner can
jump straight to all-pairs capacity where a pin walks the ladder).

``mixed`` — the adaptivity gate.  A mixed-traffic open-loop serving
scenario (interleaved single-source and all-pairs-heavy queries over
both semantics) driven through ``CFPQServer`` once per engine setting.
A single pinned backend must commit to one executable family for ALL of
it; auto routes per closure-call group.  The gate is
``auto >= 2x`` the *worst* pinned backend's wall time on at least one
scenario, with the routing visible in ``ServeStats.planner_routes``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    PlannerProfile,
    Query,
    QueryEngine,
)
from repro.serve import ServeConfig, drive_open_loop, poisson_arrivals
from tools.calibrate_planner import calibrate, community_graph, COMMUNITY

GRAMMAR = "S -> up S down | up down"

BACKENDS = ["dense", "frontier", "bitpacked"]


def _time(fn) -> tuple[object, float]:
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _grid_query(g, n: int, r_spec) -> Query:
    if r_spec == "n":
        return Query(g, "S")  # all-pairs
    r = min(int(r_spec), n // COMMUNITY)
    return Query(g, "S", sources=tuple(t * COMMUNITY + 1 for t in range(r)))


def bench_points(
    profile: PlannerProfile, sizes: list[int], source_counts: list
) -> list[dict]:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    plans = CompiledClosureCache()
    out = []
    for n in sizes:
        graph = community_graph(n)
        for r_spec in source_counts:
            q = _grid_query(g, n, r_spec)
            timings: dict[str, float] = {}
            for backend in BACKENDS:
                cfg = EngineConfig(engine=backend)
                QueryEngine(graph, plans=plans, config=cfg).query(q)  # warm
                eng = QueryEngine(graph, plans=plans, config=cfg)
                _, timings[backend] = _time(lambda: eng.query(q))
            auto_cfg = EngineConfig(engine="auto", profile=profile)
            QueryEngine(graph, plans=plans, config=auto_cfg).query(q)  # warm
            eng = QueryEngine(graph, plans=plans, config=auto_cfg)
            res, auto_s = _time(lambda: eng.query(q))
            best = min(timings, key=timings.get)
            worst = max(timings, key=timings.get)
            out.append(
                {
                    "n": n,
                    "sources": r_spec,
                    "auto_s": round(auto_s, 4),
                    "auto_pick": res.stats.planner["label"],
                    "best_pinned": best,
                    "best_pinned_s": round(timings[best], 4),
                    "worst_pinned": worst,
                    "worst_pinned_s": round(timings[worst], 4),
                    "auto_vs_best": round(auto_s / max(timings[best], 1e-9), 3),
                    "within_10pct": auto_s <= 1.10 * timings[best],
                }
            )
    return out


def _mixed_workload(g, n: int, n_requests: int, rng) -> list[Query]:
    """Interleaved traffic no single pin is best for: mostly tiny
    single-source lookups (masked-ladder territory) with periodic
    all-pairs relational sweeps and single-path requests."""
    workload: list[Query] = []
    n_comm = n // COMMUNITY
    for i in range(n_requests):
        if i % 8 == 5:
            workload.append(Query(g, "S"))  # all-pairs sweep
        elif i % 8 == 7:
            c = int(rng.integers(0, n_comm))
            workload.append(
                Query(
                    g,
                    "S",
                    sources=(c * COMMUNITY + 1,),
                    semantics="single_path",
                )
            )
        else:
            c = int(rng.integers(0, n_comm))
            workload.append(Query(g, "S", sources=(c * COMMUNITY + 1,)))
    return workload


def bench_mixed(
    profile: PlannerProfile, n: int, n_requests: int, qps: float
) -> dict:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = community_graph(n)
    rng = np.random.default_rng(0)
    workload = _mixed_workload(g, n, n_requests, rng)
    arrivals = poisson_arrivals(n_requests, qps, np.random.default_rng(1))
    cfg = ServeConfig(max_batch=8, batch_window_s=0.005, max_queue_depth=4096)

    async def _drive(eng):
        return await drive_open_loop(eng, workload, arrivals, cfg)

    plans = CompiledClosureCache()
    settings: dict[str, EngineConfig] = {
        b: EngineConfig(engine=b) for b in BACKENDS
    }
    settings["auto"] = EngineConfig(engine="auto", profile=profile)
    runs: dict[str, dict] = {}
    for label, ecfg in settings.items():
        # warm the shared compile cache untimed so wall time is closure
        # work + queueing, not tracing
        warm = QueryEngine(graph, plans=plans, config=ecfg)
        for q in {(_q.sources, _q.semantics): _q for _q in workload}.values():
            warm.query(q)
        eng = QueryEngine(graph, plans=plans, config=ecfg)
        run = asyncio.run(_drive(eng))
        runs[label] = {
            "wall_s": round(run.wall_s, 4),
            "served": len(run.results),
            "busy_s": round(run.busy_s, 4),
            "mean_batch": round(run.stats.mean_batch, 2),
            "planner_routes": dict(run.stats.planner_routes),
            "fallbacks": run.stats.fallbacks,
        }
    pinned_busy = {b: runs[b]["busy_s"] for b in BACKENDS}
    worst = max(pinned_busy, key=pinned_busy.get)
    best = min(pinned_busy, key=pinned_busy.get)
    auto_busy = runs["auto"]["busy_s"]
    return {
        "n": n,
        "n_requests": n_requests,
        "qps_offered": qps,
        "runs": runs,
        "best_pinned": best,
        "worst_pinned": worst,
        "auto_vs_worst_x": round(pinned_busy[worst] / max(auto_busy, 1e-9), 2),
        "auto_vs_best_x": round(pinned_busy[best] / max(auto_busy, 1e-9), 2),
        "auto_2x_over_worst": pinned_busy[worst] >= 2.0 * auto_busy,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", type=int, nargs="+", default=[256, 1024, 4096])
    ap.add_argument(
        "--sources", nargs="+", default=["1", "8", "n"],
        help="source counts per size; 'n' means all-pairs",
    )
    ap.add_argument("--profile", default=None, help="reuse a saved profile")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=64.0)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid + short mixed run: seconds, for CI")
    args = ap.parse_args(argv)
    sizes = [256] if args.smoke else args.sizes
    sources = ["1", "n"] if args.smoke else args.sources
    n_requests = 24 if args.smoke else args.requests

    if args.profile:
        profile = PlannerProfile.load(args.profile)
    else:
        # calibrate in-process on a small grid (the fit is what the
        # decisions gate on; bigger grids only sharpen it)
        profile = calibrate(
            [256] if args.smoke else [256, 512],
            ["1", "n"] if args.smoke else ["1", "4", "n"],
            BACKENDS,
            log=lambda *a: print(*a, file=sys.stderr),
        )
    points = bench_points(profile, sizes, sources)
    mixed = bench_mixed(profile, max(sizes[0], 256), n_requests, args.qps)
    report = {
        "profile_host": profile.host,
        "profile_fitted": profile.fitted,
        "points": points,
        "points_all_within_10pct": all(p["within_10pct"] for p in points),
        "mixed": mixed,
    }
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
