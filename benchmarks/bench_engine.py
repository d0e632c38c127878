"""Query-engine benchmark: batched single-source vs all-pairs closure.

    PYTHONPATH=src python -m benchmarks.bench_engine
    PYTHONPATH=src python -m benchmarks.bench_engine --sizes 256 1024
    PYTHONPATH=src python -m benchmarks.bench_engine --mesh 2x1

Workload model: a graph of disjoint "communities" (the paper's g1-g3
repeat construction — one ~128-node ontology tree repeated n/128 times)
queried with the same-generation grammar.  A single-source request only
needs the closure rows of its own community, so the masked engine does
|P|·R²·n work against the all-pairs |P|·n³; the gap widens with n while
the answer stays identical.

``--mesh DxM`` adds a distributed section: the masked-opt engine sharded
over a (data=D, model=M) host mesh vs the single-device masked engine on
the same batch (ROADMAP "masked closure for the opt engine").  The
process re-execs itself with ``--xla_force_host_platform_device_count``
when it does not already see enough devices.

Emits ONE JSON object on stdout:
  {"engine": ..., "sources": k, "results": [
     {"n": 256, "allpairs_s": ..., "batch_miss_s": ..., "batch_hit_s": ...,
      "per_query_miss_s": ..., "active_rows": ..., "speedup": ...}, ...],
   "mesh": {"shape": "2x1", "results": [...]}}   # with --mesh
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.grammar import Grammar
from repro.core.graph import Graph
from repro.core.matrices import ProductionTables, init_matrix
from repro.core.semantics import closure_engines
from repro.engine import (
    CompiledClosureCache,
    EngineConfig,
    Query,
    QueryEngine,
)
from repro.engine.plan import MASKED_ENGINES
from repro.shard import make_mesh

#: same-generation query over a class hierarchy (paper Query 1 shape,
#: single label pair to keep |P| small and the workload uniform)
GRAMMAR = "S -> up S down | up down"

COMMUNITY = 128  # nodes per disjoint community (tree)


def community_graph(n: int, branching: int = 3, seed: int = 0) -> Graph:
    """A forest of n/COMMUNITY disjoint trees with up/down edge pairs."""
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


def parse_mesh(spec: str) -> tuple[int, int]:
    """'2x1' -> (2, 1) — the (data, model) host-mesh shape."""
    try:
        d, m = (int(p) for p in spec.lower().split("x"))
        if d < 1 or m < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(f"--mesh wants DxM (e.g. 2x1), got {spec!r}")
    return d, m


def ensure_host_devices(need: int, module: str, argv: list[str]) -> None:
    """Re-exec ``python -m module argv`` with enough forced host devices.

    Only on the CPU backend: XLA fixes the host device count at backend
    init (which module imports already triggered), so the flag cannot be
    set in-process; when the current CPU process is short, replace it with
    one that has the flag — stdout (the JSON) passes straight through.  On
    any other platform the devices are real and too few is an error, never
    a quiet switch to the CPU.  One-shot: a re-exec that still comes up
    short errors out instead of exec-looping.
    """
    import jax

    if jax.device_count() >= need:
        return
    platform = jax.devices()[0].platform
    if platform != "cpu" or os.environ.get("_REPRO_MESH_REEXEC"):
        raise SystemExit(
            f"--mesh needs {need} devices but only {jax.device_count()} "
            f"{platform} device(s) are visible"
        )
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={need}".strip()
    )
    env.setdefault("JAX_PLATFORMS", "cpu")  # host devices: CPU-only trick
    env["_REPRO_MESH_REEXEC"] = "1"
    os.execve(
        sys.executable, [sys.executable, "-m", module, *argv], env
    )


def bench_mesh_size(
    n: int,
    mesh_shape: tuple[int, int],
    n_sources: int,
    semantics: str = "relational",
) -> dict:
    """Masked-opt on a (data, model) host mesh vs the single-device masked
    engine, same coalesced single-source batch of either semantics
    (differentially checked).  Shared with bench_single_path."""
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = community_graph(n)
    n_sources = min(n_sources, n // COMMUNITY)
    sources = tuple(t * COMMUNITY + 1 for t in range(n_sources))
    queries = [
        Query(g, "S", sources=(m,), semantics=semantics) for m in sources
    ]
    mesh = make_mesh(mesh_shape)

    timings: dict[str, tuple[float, float]] = {}
    results: dict[str, list] = {}
    for label, cfg in (
        ("masked_opt", EngineConfig(engine="opt", mesh=mesh)),
        ("masked", EngineConfig(engine="dense")),
    ):
        plans = CompiledClosureCache()
        QueryEngine(graph, plans=plans, config=cfg).query_batch(queries)  # warm
        eng = QueryEngine(graph, plans=plans, config=cfg)
        rs, miss_s = _time(lambda: eng.query_batch(queries))
        _, hit_s = _time(lambda: eng.query_batch(queries))
        timings[label] = (miss_s, hit_s)
        results[label] = rs
    for a, b in zip(results["masked_opt"], results["masked"]):
        assert a.pairs == b.pairs, f"masked-opt {semantics} mismatch n={n}"
    miss_s, hit_s = timings["masked_opt"]
    out = {
        "n": n,
        "n_sources": n_sources,
        "masked_opt_miss_s": round(miss_s, 4),
        "masked_opt_hit_s": round(hit_s, 6),
        "masked_miss_s": round(timings["masked"][0], 4),
        "active_rows": results["masked_opt"][0].stats["active_rows"],
        "opt_vs_masked_x": round(timings["masked"][0] / max(miss_s, 1e-9), 2),
    }
    if semantics == "single_path":
        out["witnesses"] = sum(len(r.paths) for r in results["masked_opt"])
    return out


def mesh_setup(args, module: str, argv: list[str] | None) -> tuple | None:
    """Shared ``--mesh`` front half: parse the shape and secure enough
    host devices (may re-exec the process — call before any timing
    work).  Returns the (data, model) shape, or None without ``--mesh``."""
    if not args.mesh:
        return None
    shape = parse_mesh(args.mesh)
    ensure_host_devices(
        shape[0] * shape[1],
        module,
        list(argv) if argv is not None else sys.argv[1:],
    )
    return shape


def bench_size(n: int, engine: str, n_sources: int) -> dict:
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = community_graph(n)
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    assert T0.shape[-1] == n, "sizes must be multiples of 128"

    # --- all-pairs reference (AOT-compiled so compile time is excluded) ---
    fn = closure_engines()[engine]
    exe = fn.lower(T0, tables).compile()
    T_all = exe(T0)
    T_all.block_until_ready()
    T_all, allpairs_s = _time(lambda: exe(T0).block_until_ready())
    T_all = np.asarray(T_all)

    # --- batched single-source through the service ---
    # one source per community: the realistic "which nodes does user m
    # reach" workload, coalesced into a single masked-closure call
    n_sources = min(n_sources, n // COMMUNITY)
    sources = tuple(t * COMMUNITY + 1 for t in range(n_sources))
    queries = [Query(g, "S", sources=(m,)) for m in sources]
    plans = CompiledClosureCache()
    # populate the plan cache (compile) with a throwaway engine instance,
    # then time a fresh instance sharing the warm plans: the measured miss
    # is pure closure work, no tracing/compilation
    QueryEngine(graph, plans=plans, config=EngineConfig(engine=engine)).query_batch(queries)
    eng = QueryEngine(graph, plans=plans, config=EngineConfig(engine=engine))
    rs, batch_miss_s = _time(lambda: eng.query_batch(queries))
    _, batch_hit_s = _time(lambda: eng.query_batch(queries))

    a0 = g.index_of("S")
    for r in rs:  # single-source answers == rows of the all-pairs closure
        (m,) = r.query.sources
        expect = {
            (m, int(j)) for j in np.nonzero(T_all[a0, m, : graph.n_nodes])[0]
        }
        assert r.pairs == expect, f"mismatch at n={n} source={m}"

    return {
        "n": n,
        "n_nodes": graph.n_nodes,
        "n_edges": graph.n_edges,
        "allpairs_s": round(allpairs_s, 4),
        "batch_miss_s": round(batch_miss_s, 4),
        "batch_hit_s": round(batch_hit_s, 6),
        "per_query_miss_s": round(batch_miss_s / n_sources, 4),
        "active_rows": rs[0].stats["active_rows"],
        "speedup": round(allpairs_s / max(batch_miss_s, 1e-9), 1),
    }


def bench_retrace(n: int, engine: str) -> dict:
    """Bucket-growth retrace cost (ROADMAP "quantify retrace cost").

    A cold multi-community query whose active set (~4 communities, ~512
    rows) overflows the first capacity bucket is served twice: starting at
    capacity 128 (the default ladder: compile at 128, overflow, 256, ...)
    and starting directly at capacity n (one big executable, no overflow
    restarts).  Reports compiles x wall for both, so the ladder's retrace
    overhead is a number instead of a guess.
    """
    g = Grammar.from_text(GRAMMAR).to_cnf()
    graph = community_graph(n)
    k = min(4, n // COMMUNITY)
    sources = tuple(t * COMMUNITY + 1 for t in range(k))
    out: dict = {"n": n, "touched_communities": k}
    for label, cap0 in (("cap128", 128), ("capn", n)):
        plans = CompiledClosureCache()
        eng = QueryEngine(
            graph, plans=plans,
            config=EngineConfig(engine=engine, row_capacity=cap0),
        )
        r, cold_s = _time(
            lambda: eng.query(Query(g, "S", sources=sources))
        )
        _, steady_s = _time(
            lambda: eng.query(Query(g, "S", sources=sources))
        )
        out[label] = {
            "compiles": plans.stats.compile_misses,
            "cold_s": round(cold_s, 4),
            "hit_s": round(steady_s, 6),
            "active_rows": r.stats["active_rows"],
        }
    out["cold_overhead_x"] = round(
        out["cap128"]["cold_s"] / max(out["capn"]["cold_s"], 1e-9), 2
    )
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--sizes", type=int, nargs="+", default=[256, 1024, 4096]
    )
    ap.add_argument("--engine", default="dense", choices=sorted(MASKED_ENGINES))
    ap.add_argument("--sources", type=int, default=8)
    ap.add_argument(
        "--mesh",
        default=None,
        metavar="DxM",
        help="add a masked-opt vs single-device-masked section on a "
        "(data=D, model=M) host mesh (re-execs with forced host devices)",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny CI config: n=256 only, 2 sources",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.sizes, args.sources = [256], 2
    shape = mesh_setup(args, "benchmarks.bench_engine", argv)
    out = {
        "engine": args.engine,
        "sources": args.sources,
        "grammar": GRAMMAR,
        "results": [bench_size(n, args.engine, args.sources) for n in args.sizes],
        "retrace": [bench_retrace(n, args.engine) for n in args.sizes],
    }
    if shape:
        out["mesh"] = {
            "shape": args.mesh,
            "results": [
                bench_mesh_size(n, shape, args.sources) for n in args.sizes
            ],
        }
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
