"""Serving-loop benchmark: coalescing throughput and the batch-window knob.

    PYTHONPATH=src python -m benchmarks.bench_serving
    PYTHONPATH=src python -m benchmarks.bench_serving --smoke

Two sections, emitted as ONE JSON object on stdout:

``coalescing`` — the throughput gate.  An open-loop Poisson arrival
process (default 64 qps offered) of single-source queries, each hitting
its own small "community" (an 8-node up/down chain), so every request
needs real device closure work and none is amortized by the materialized
row cache.  The same workload and arrival process run twice: ``max_batch=1``
(single-query submission: one closure call per request) vs the coalescing
server (``max_batch=16``): the batch window packs concurrent arrivals into
one masked-closure call whose cost is set by the row-capacity *bucket*,
not the batch size, so ``throughput_speedup`` approaches the mean batch
size.  The acceptance gate is ``throughput_speedup >= 3`` at offered load
>= 64 qps.

``window_sweep`` — the latency/throughput tradeoff of ``batch_window_s``
(numbers quoted in SERVING.md).  A hot workload (sources from a small
repeated set, served from the materialized cache) swept over window
deadlines: larger windows coalesce more per call (higher ``mean_batch``,
fewer engine calls) but every query waits up to the deadline, so p50 rises
with the window while p99 stays bounded by
``window + one closure call latency`` (+ scheduling slop) as long as the
server keeps up — the ``p99_within_bound`` flag checks exactly that.
"""
from __future__ import annotations

import argparse
import asyncio
import json

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.serve import ServeConfig, drive_open_loop, poisson_arrivals

GRAMMAR = "S -> up S down | up down"
COMMUNITY = 8  # nodes per chain community (bounds each query's reach)

# the coalescing gate compares submission policies with the executable
# held fixed — engine pinned to dense so planner routing (benchmarked in
# bench_planner.py) can't move the baseline
_ENGINE = EngineConfig(engine="dense")


def chain_communities(n: int) -> Graph:
    """n/COMMUNITY disjoint up/down chains: reach from any node is its own
    community, so distinct-community queries can't serve each other."""
    edges: list[tuple[int, str, int]] = []
    for c in range(COMMUNITY - 1):
        edges.append((c + 1, "up", c))
        edges.append((c, "down", c + 1))
    return Graph(COMMUNITY, edges).repeat(n // COMMUNITY)


async def _drive(
    eng: QueryEngine,
    workload: list[Query],
    arrivals: np.ndarray,
    cfg: ServeConfig,
) -> dict:
    """One open-loop run (shared driver: repro.serve.loadgen), reduced to
    the latency/throughput/batching metrics this benchmark reports."""
    run = await drive_open_loop(eng, workload, arrivals, cfg)
    e2e, execs = run.e2e_s, run.batch_exec_s
    return {
        "served": len(run.results),
        "shed": run.shed,
        "wall_s": round(run.wall_s, 4),
        "busy_s": round(run.busy_s, 4),
        "throughput_qps": round(run.throughput_qps, 1),
        "p50_ms": round(float(np.median(e2e)) * 1e3, 2) if e2e else None,
        "p99_ms": round(float(np.percentile(e2e, 99)) * 1e3, 2) if e2e else None,
        "max_exec_ms": round(max(execs) * 1e3, 2) if execs else None,
        "batches": run.stats.batches,
        "mean_batch": round(run.stats.mean_batch, 2),
    }


def bench_coalescing(
    n: int, n_requests: int, qps: float, max_batch: int, plans
) -> dict:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = chain_communities(n)
    # one query per distinct community: all device work, no cache reuse
    workload = [
        Query(g, "S", sources=(k * COMMUNITY + COMMUNITY - 1,))
        for k in range(n_requests)
    ]
    arrivals = poisson_arrivals(n_requests, qps, np.random.default_rng(0))

    # populate the shared plan cache untimed (the sequential pattern walks
    # every capacity bucket both trials will use)
    warm = QueryEngine(graph, plans=plans, config=_ENGINE)
    for q in workload:
        warm.query(q)

    def trial(mb: int, window_s: float) -> dict:
        eng = QueryEngine(graph, plans=plans, config=_ENGINE)
        cfg = ServeConfig(
            max_batch=mb, batch_window_s=window_s, max_queue_depth=4096
        )
        return asyncio.run(_drive(eng, workload, arrivals, cfg))

    single = trial(1, 0.0)
    coalesced = trial(max_batch, 0.005)
    return {
        "qps_offered": qps,
        "n_requests": n_requests,
        "graph_nodes": graph.n_nodes,
        "single": single,
        "coalesced": coalesced,
        "throughput_speedup": round(
            coalesced["throughput_qps"] / single["throughput_qps"], 2
        ),
        "busy_speedup": round(single["busy_s"] / max(coalesced["busy_s"], 1e-9), 2),
    }


def bench_window_sweep(
    n: int, n_requests: int, qps: float, windows_ms: list[float], plans
) -> list[dict]:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = chain_communities(n)
    rng = np.random.default_rng(1)
    hot = [
        int(h) * COMMUNITY + COMMUNITY - 1
        for h in rng.integers(0, 4, size=n_requests)
    ]
    workload = [Query(g, "S", sources=(s,)) for s in hot]
    arrivals = poisson_arrivals(n_requests, qps, rng)

    warm = QueryEngine(graph, plans=plans, config=_ENGINE)
    for q in workload:
        warm.query(q)

    out = []
    for w_ms in windows_ms:
        eng = QueryEngine(graph, plans=plans, config=_ENGINE)
        # re-materialize every distinct hot community untimed so the
        # timed run is all cache hits, whatever order the workload draws
        for c in range(4):
            eng.query(Query(g, "S", sources=(c * COMMUNITY + COMMUNITY - 1,)))
        cfg = ServeConfig(
            max_batch=16, batch_window_s=w_ms / 1e3, max_queue_depth=4096
        )
        m = asyncio.run(_drive(eng, workload, arrivals, cfg))
        bound_ms = w_ms + m["max_exec_ms"] + 5.0  # +5ms scheduling slop
        out.append(
            {
                "window_ms": w_ms,
                "qps_offered": qps,
                **m,
                "p99_bound_ms": round(bound_ms, 2),
                "p99_within_bound": m["p99_ms"] <= bound_ms,
            }
        )
    return out


def bench_observed(
    n: int,
    n_requests: int,
    qps: float,
    max_batch: int,
    trace_out: str | None,
    metrics_out: str | None,
) -> dict:
    """One fully observed open-loop run (repro.obs; OBSERVABILITY.md):
    a live tracer captures the span tree admission → window → planner →
    closure (with per-iteration events from instrumented executables) and
    a private registry collects the serving/engine metric families.  Runs
    on its own engine and plan cache — instrumented executables are
    distinct PlanKeys, so the gated trials above stay untraced — and
    writes the Chrome trace / metrics snapshot to the requested paths."""
    from repro.obs.chrome import write_chrome_trace
    from repro.obs.export import write_metrics_json
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer

    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = chain_communities(n)
    workload = [
        Query(g, "S", sources=(k * COMMUNITY + COMMUNITY - 1,))
        for k in range(n_requests)
    ]
    arrivals = poisson_arrivals(n_requests, qps, np.random.default_rng(2))

    tracer = Tracer()
    registry = MetricsRegistry()
    eng = QueryEngine(graph, config=_ENGINE)
    cfg = ServeConfig(
        max_batch=max_batch, batch_window_s=0.005, max_queue_depth=4096
    )
    run = asyncio.run(
        drive_open_loop(
            eng, workload, arrivals, cfg, tracer=tracer, metrics=registry
        )
    )
    iteration_events = sum(
        1
        for sp in tracer.spans
        for ev in sp.events
        if ev["name"] == "iteration"
    )
    summary = {
        "served": len(run.results),
        "spans": len(tracer.spans),
        "iteration_events": iteration_events,
        "dropped_spans": tracer.dropped,
        "trace_out": trace_out,
        "metrics_out": metrics_out,
    }
    if trace_out:
        write_chrome_trace(trace_out, tracer)
    if metrics_out:
        write_metrics_json(
            metrics_out,
            registry=registry,
            serve_stats=run.stats,
            extra={"bench": "bench_serving.observed", "n_requests": n_requests},
        )
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=96.0)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument(
        "--windows-ms", type=float, nargs="+", default=[0.0, 2.0, 10.0, 25.0]
    )
    ap.add_argument("--smoke", action="store_true", help="tiny CI config")
    ap.add_argument(
        "--trace-out",
        default=None,
        help="also run one traced pass; write Chrome trace JSON here",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        help="write the traced pass's metrics snapshot JSON here",
    )
    args = ap.parse_args()
    if args.smoke:
        args.requests = 48
        args.windows_ms = [0.0, 10.0]

    plans = CompiledClosureCache()
    out = {
        "engine": "dense",
        "community": COMMUNITY,
        "coalescing": bench_coalescing(
            args.n, args.requests, args.qps, args.max_batch, plans
        ),
        "window_sweep": bench_window_sweep(
            args.n, args.requests, args.qps, args.windows_ms, plans
        ),
        "plans_compiled": plans.stats.compile_misses,
    }
    if args.trace_out or args.metrics_out:
        out["observed"] = bench_observed(
            args.n,
            args.requests,
            args.qps,
            args.max_batch,
            args.trace_out,
            args.metrics_out,
        )
    print(json.dumps(out, indent=2))
    if out["coalescing"]["throughput_speedup"] < 3.0:
        raise SystemExit(
            "coalescing throughput gate failed: "
            f"{out['coalescing']['throughput_speedup']}x < 3x"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
