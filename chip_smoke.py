#!/usr/bin/env python3
"""Drive the CFPQ serving path on a TPU once and check every answer.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded opt engine only

It needs a TPU.  On any other platform it exits nonzero with a message
that names the platform it found, and prints no result.  Everything runs
in this one process, through the entry points a user calls:
``CFPQServer.submit`` in front of a ``QueryEngine``.  Each answer is
checked against a reference that shares no code with the matrix engines:
the Hellings worklist, CYK on witness labels, ``core.conjunctive.evaluate``
and ``evaluate_count``.  A mismatch, a failed or shed request, or any
exception ends the run with a nonzero exit.

One chip, each phase at the largest n whose executables fit one chip's
16 GiB in a compile for a described v5e (tests/test_tpu_compile.py):
  relational   paper Query 2 over the seeded RDF-ontology graph
               ``ontology_graph(4000, 12000)`` (16,000 nodes) padded with
               isolated nodes to n = 16,384: a 1.25 GiB Boolean state.
               Coalesced single-source batches plus one all-pairs query,
               served with ``engine="auto"`` and pinned to every backend,
               then one insert and one delete through
               ``CFPQServer.apply_delta`` and the queries again.  One more
               bitpacked run on the unpadded graph (n = 16,000) drives
               the kernel's ragged grid.
  conjunctive  a two-conjunct Query 2 variant on the same graph, auto
               and pinned to bitpacked.
  single_path  ``ontology_graph(2000, 6000)`` padded to n = 8,192: at
               16,384 its f32 masked closure does not fit.
  count        ``ontology_graph(500, 1500)`` padded to n = 2,048: at
               4,096 the all-pairs ``evaluate_count`` reference does not
               fit.
Four chips: one batch of relational queries at n = 16,384 and one of
single-path queries at n = 8,192, served by the opt engine sharded over a
2x2 (data, model) mesh and by the single-device dense engine; the answers
must be equal, and the sharded state must be split over all four chips.

Every line but the last describes a phase.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: per scale: (classes, instances) of the seeded ontology, the padded n,
#: and the engines' row-capacity floor — a bucket that holds every class
#: row Query 2 reaches, so each backend compiles one masked bucket, not
#: the whole ladder up to it (the CPU tests cover the ladder)
RELATIONAL = (4000, 12000, 16384, 4096)  # also conjunctive
SINGLE_PATH = (2000, 6000, 8192, 2048)
COUNT = (500, 1500, 2048, 512)
SEED = 0
ENGINES = ("auto", "dense", "frontier", "bitpacked", "opt", "blocksparse")

#: conjunctive Query 2 variant: S holds for (c, parent(c)) where c has a
#: subclass — B = subClassOf_r^k subClassOf^k, S = (B . U) & (U . B)
CONJ_RULES = [
    ("S", [("B", "U"), ("U", "B")]),
    ("B", [("D", "U")]),
    ("B", [("D", "Y")]),
    ("Y", [("B", "U")]),
]
CONJ_TERMS = {"subClassOf": ["U"], "subClassOf_r": ["D"]}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileMeter:
    """Host wall seconds, backend compile seconds and persistent-cache
    hits/misses, the last two read from JAX's monitoring events (a cache
    hit's retrieval counts as compile)."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, float, int, int]:
        return time.perf_counter(), self.seconds, self.hits, self.misses

    def since(self, snap) -> str:
        t, s, h, m = snap
        return (
            f"wall_s={time.perf_counter() - t:.2f} "
            f"compile_s={self.seconds - s:.2f} "
            f"cache_hits={self.hits - h} cache_misses={self.misses - m}"
        )


def require_tpu(count: int):
    """The TPU devices, or exit nonzero naming the platform found."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's platform is {platform!r} "
            f"({len(devices)} device(s)); there is no CPU fallback"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} TPU chips, found {len(devices)}"
        )
    return devices[:count]


def padded_ontology(n_classes: int, n_instances: int, n: int | None):
    """The seeded ontology graph, with isolated nodes up to ``n``."""
    from repro.core.graph import Graph, ontology_graph

    g = ontology_graph(n_classes, n_instances, seed=SEED)
    if n is None:
        return g
    if n < g.n_nodes:
        raise ValueError(f"cannot pad {g.n_nodes} nodes down to {n}")
    return Graph(n, list(g.edges))


def graph_of(scale):
    """A maker of fresh graphs (each engine mutates its own on a delta)."""
    return lambda: padded_ontology(*scale[:3])


def class_sources(graph, k: int, seed: int) -> list[int]:
    """``k`` distinct class nodes (the ones with subClassOf edges)."""
    import numpy as np

    classes = sorted({i for i, x, _ in graph.edges if x == "subClassOf"})
    rng = np.random.default_rng(seed)
    return [int(s) for s in rng.choice(classes, size=k, replace=False)]


def rdf_delta(graph, seed: int):
    """One inserted and one deleted subClassOf triple (each with its
    inverse edge): the edits an ontology update makes."""
    import numpy as np

    rng = np.random.default_rng(seed)
    sub = sorted(e for e in graph.edges if e[1] == "subClassOf")
    c, _, parent = sub[int(rng.integers(len(sub)))]
    delete = [(c, "subClassOf", parent), (parent, "subClassOf_r", c)]
    edges = set(graph.edges)
    classes = sorted({e[0] for e in sub} | {e[2] for e in sub})
    while True:
        a, b = (int(x) for x in rng.choice(classes, size=2, replace=False))
        if (a, "subClassOf", b) not in edges:
            break
    insert = [(a, "subClassOf", b), (b, "subClassOf_r", a)]
    return insert, delete


def device_bytes(device) -> tuple[int, int]:
    stats = device.memory_stats() or {}
    return stats.get("bytes_in_use", -1), stats.get("peak_bytes_in_use", -1)


async def serve(engine, batches, delta=None, after=()):
    """Serve ``batches`` (each submitted together, so it coalesces into
    one window), then optionally commit ``delta`` = (insert, delete) and
    serve ``after``.  Returns (results per batch, delta stats, ServeStats).
    """
    from repro.serve import CFPQServer, ServeConfig

    size = max(len(b) for b in [*batches, *after])
    cfg = ServeConfig(
        max_batch=size, batch_window_s=0.05, max_queue_depth=4 * size
    )
    out, dstats = [], None
    async with CFPQServer(engine, cfg) as srv:
        for b in batches:
            out.append(await asyncio.gather(*[srv.submit(q) for q in b]))
        if delta is not None:
            dstats = await srv.apply_delta(*delta)
            for b in after:
                out.append(await asyncio.gather(*[srv.submit(q) for q in b]))
        stats = srv.stats
    return out, dstats, stats


def expected_pairs(rel: set, q, nullable) -> set:
    rows = None if q.sources is None else set(q.sources)
    want = {(i, j) for i, j in rel if rows is None or i in rows}
    if q.start in nullable and rows is not None:
        want |= {(m, m) for m in rows}
    return want


def check(got, want, what: str) -> None:
    if got != want:
        raise SystemExit(
            f"chip_smoke: {what}: {len(got ^ want)} pair(s) differ from the "
            f"reference (got {len(got)}, want {len(want)})"
        )


def check_witness(graph, g, q, i, j, path) -> None:
    from repro.baselines import cyk_recognize

    edges = graph.edge_set()
    at = i
    for e in path:
        if e[0] != at or e not in edges:
            raise SystemExit(f"chip_smoke: witness {i}->{j} breaks at {e}")
        at = e[2]
    if at != j or not cyk_recognize(g, q.start, [x for _, x, _ in path]):
        raise SystemExit(f"chip_smoke: witness {i}->{j} is not derived")


def phase_line(phase, engine, eng, stats, meter, snap, device, extra=""):
    states = list(eng._states.values())
    N = max(s.tables.n_nonterms for s in states)
    state_bytes = sum(
        a.nbytes for s in states for a in (s.T, s.sp_L, s.cnt_C)
        if a is not None
    )
    _, peak = device_bytes(device)
    log(
        f"phase={phase} engine={engine} device={device.device_kind} "
        f"n={eng.n} N={N} state_bytes={state_bytes} "
        f"{meter.since(snap)} served={stats.served} failed={stats.failed} "
        f"shed={stats.shed} routes={json.dumps(stats.planner_routes)} "
        f"peak_bytes_in_use={peak}{extra}"
    )
    if stats.failed or stats.shed:
        raise SystemExit(f"chip_smoke: {phase}/{engine} failed or shed")


def kernel_calls(eng, engine: str) -> int:
    """Pallas kernel calls in the compiled plans of ``engine``."""
    return sum(
        exe.as_text().count("tpu_custom_call")
        for key, exe in eng.plans._exe.items()
        if key.engine == engine
    )


def tile_kernel_calls(eng) -> int:
    """Pallas kernel calls in the block-sparse engine's jitted chunk
    contraction, lowered as the engine calls it (``use_kernel=True``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.blocksparse import _contract_chunk

    B = eng.config.tile
    i32 = jax.ShapeDtypeStruct((32,), jnp.int32)
    lowered = _contract_chunk.lower(
        jax.ShapeDtypeStruct((64, B, B // 32), jnp.uint32), i32, i32, i32,
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.bool_), n_out=8, use_kernel=True,
    )
    return lowered.compile().as_text().count("tpu_custom_call")


def run_relational(make_graph, engines, device, meter, *, n_batches=3,
                   batch=8, delta=True, rows=128) -> None:
    """Relational phase: per engine, coalesced single-source batches plus
    one all-pairs query, then (``delta``) an insert + delete and again."""
    from repro.baselines import hellings_cfpq
    from repro.core.grammar import query2_grammar
    from repro.engine import EngineConfig, Query, QueryEngine

    g = query2_grammar().to_cnf()
    base = make_graph()
    sources = class_sources(base, n_batches * batch, SEED)
    batches = [
        [Query(g, "S", sources=(s,)) for s in sources[k : k + batch]]
        for k in range(0, len(sources), batch)
    ]
    batches.append([Query(g, "S")])  # all pairs
    edit = rdf_delta(base, SEED) if delta else None
    before = hellings_cfpq(base, g)["S"]
    after_graph = make_graph()
    if edit is not None:
        after_graph.delete_edges(edit[1])
        after_graph.insert_edges(edit[0])
    after = hellings_cfpq(after_graph, g)["S"]
    again = [batches[0], batches[-1]] if delta else []
    for engine in engines:
        snap = meter.snapshot()
        cfg = EngineConfig(engine=engine, row_capacity=rows)
        eng = QueryEngine(make_graph(), config=cfg)
        out, dstats, stats = asyncio.run(serve(eng, batches, edit, again))
        refs = [before] * len(batches) + [after] * len(again)
        for results, ref in zip(out, refs):
            for r in results:
                check(
                    r.pairs,
                    expected_pairs(ref, r.query, g.nullable),
                    f"relational/{engine} {r.query.sources}",
                )
        extra = ""
        if dstats is not None:
            repairs = sum(1 for k in eng.plans._exe if k.repair)
            extra += (
                f" delta_rows_repaired={dstats.rows_repaired}"
                f" delta_rows_evicted={dstats.rows_evicted}"
                f" repair_executables={repairs}"
            )
        if engine == "bitpacked":
            extra += f" pallas_calls={kernel_calls(eng, 'bitpacked')}"
        elif engine == "blocksparse":
            extra += f" pallas_calls_per_chunk={tile_kernel_calls(eng)}"
        phase_line("relational", engine, eng, stats, meter, snap, device,
                   extra)
        del eng
        gc.collect()


def run_single_path(make_graph, device, meter, *, batch=8, rows=128) -> None:
    from repro.baselines import hellings_cfpq
    from repro.core.grammar import query2_grammar
    from repro.engine import EngineConfig, Query, QueryEngine

    g = query2_grammar().to_cnf()
    graph = make_graph()
    qs = [
        Query(g, "S", sources=(s,), semantics="single_path")
        for s in class_sources(graph, 2 * batch, SEED + 1)
    ]
    ref = hellings_cfpq(graph, g)["S"]
    snap = meter.snapshot()
    cfg = EngineConfig(engine="auto", row_capacity=rows)
    eng = QueryEngine(graph, config=cfg)
    out, _, stats = asyncio.run(serve(eng, [qs[:batch], qs[batch:]]))
    n_paths = 0
    for results in out:
        for r in results:
            want = expected_pairs(ref, r.query, g.nullable)
            check(r.pairs, want, f"single_path {r.query.sources}")
            check(set(r.paths), want, f"single_path {r.query.sources}")
            for (i, j), path in r.paths.items():
                check_witness(graph, g, r.query, i, j, path)
                n_paths += 1
    phase_line("single_path", "auto", eng, stats, meter, snap, device,
               f" witnesses_checked={n_paths}")
    del eng
    gc.collect()


def run_conjunctive(make_graph, device, meter, *, batch=8, rows=128) -> None:
    from repro.core.conjunctive import ConjunctiveGrammar, evaluate
    from repro.engine import EngineConfig, Query, QueryEngine

    cg = ConjunctiveGrammar.from_rules(CONJ_TERMS, CONJ_RULES)
    graph = make_graph()
    qs = [
        Query(cg, "S", sources=(s,), semantics="conjunctive")
        for s in class_sources(graph, 2 * batch, SEED + 2)
    ]
    ref = evaluate(graph, cg, "S")
    for engine in ("auto", "bitpacked"):
        snap = meter.snapshot()
        cfg = EngineConfig(engine=engine, row_capacity=rows)
        eng = QueryEngine(make_graph(), config=cfg)
        out, _, stats = asyncio.run(serve(eng, [qs[:batch], qs[batch:]]))
        for results in out:
            for r in results:
                check(r.pairs, expected_pairs(ref, r.query, frozenset()),
                      f"conjunctive/{engine} {r.query.sources}")
        extra = f" reference_pairs={len(ref)}"
        if engine == "bitpacked":
            extra += f" pallas_calls={kernel_calls(eng, 'bitpacked')}"
        phase_line("conjunctive", engine, eng, stats, meter, snap, device,
                   extra)
        del eng
        gc.collect()


def run_count(make_graph, device, meter, *, batch=8, rows=128) -> None:
    from repro.core.grammar import query2_grammar
    from repro.core.semantics import evaluate_count
    from repro.engine import EngineConfig, Query, QueryEngine

    g = query2_grammar().to_cnf()
    graph = make_graph()
    qs = [
        Query(g, "S", sources=(s,), semantics="count")
        for s in class_sources(graph, 2 * batch, SEED + 3)
    ]
    snap = meter.snapshot()
    cfg = EngineConfig(engine="auto", row_capacity=rows)
    eng = QueryEngine(graph, config=cfg)
    out, _, stats = asyncio.run(serve(eng, [qs[:batch], qs[batch:]]))
    phase_line("count", "auto", eng, stats, meter, snap, device)
    del eng  # the reference's all-pairs state needs the memory
    gc.collect()
    ref = evaluate_count(graph, g, "S")
    for results in out:
        for r in results:
            src = set(r.query.sources)
            want = {p: c for p, c in ref.items() if p[0] in src}
            if r.counts != want:
                raise SystemExit(
                    f"chip_smoke: count {r.query.sources}: counts differ "
                    "from evaluate_count"
                )
    log(f"phase=count reference=evaluate_count pairs={len(ref)} equal=true")


def run_sharded(make_graph, semantics, devices, meter, *, batch=8,
                rows=128) -> None:
    """Four chips: one batch through the opt engine sharded over a 2x2
    (data, model) mesh and through the single-device dense engine; the
    answers must be equal and the sharded state must span every chip."""
    from repro.baselines import hellings_cfpq
    from repro.core.grammar import query2_grammar
    from repro.engine import EngineConfig, Query, QueryEngine
    from repro.shard import make_mesh

    g = query2_grammar().to_cnf()
    graph = make_graph()
    qs = [
        Query(g, "S", sources=(s,), semantics=semantics)
        for s in class_sources(graph, batch, SEED)
    ]
    ref = hellings_cfpq(graph, g)["S"]
    mesh = make_mesh((2, 2), devices=devices)
    answers = {}
    for label, cfg in (
        ("opt+mesh",
         EngineConfig(engine="opt", mesh=mesh, row_capacity=rows)),
        ("dense", EngineConfig(engine="dense", row_capacity=rows)),
    ):
        snap = meter.snapshot()
        eng = QueryEngine(make_graph(), config=cfg)
        (results,), _, stats = asyncio.run(serve(eng, [qs]))
        for r in results:
            check(r.pairs, expected_pairs(ref, r.query, g.nullable),
                  f"{semantics}/{label} {r.query.sources}")
            for (i, j), path in (r.paths or {}).items():
                check_witness(graph, g, r.query, i, j, path)
        answers[label] = [r.pairs for r in results]
        (state,) = eng._states.values()
        placed = state.sp_L if semantics == "single_path" else state.T
        spans = len(placed.sharding.device_set)
        split = not placed.sharding.is_fully_replicated
        want = (len(devices), True) if label == "opt+mesh" else (1, False)
        if (spans, split) != want:
            raise SystemExit(
                f"chip_smoke: {label} state on {spans} devices "
                f"(split={split}), want {want}"
            )
        shard = placed.sharding.shard_shape(placed.shape)
        phase_line(f"sharded_{semantics}", label, eng, stats, meter, snap,
                   devices[0], f" state_devices={spans} shard_shape={shard}")
        for d in devices:
            used, peak = device_bytes(d)
            log(f"device={d.id} bytes_in_use={used} peak_bytes_in_use={peak}")
        del eng, state, placed
        gc.collect()
    if answers["opt+mesh"] != answers["dense"]:
        raise SystemExit(f"chip_smoke: sharded {semantics} answers differ")
    log(f"phase=sharded_{semantics} equal_answers=true")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the sharded opt phase on a 2x2 mesh",
    )
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    dev = devices[0]
    log(
        f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}"
    )
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(graph_of(RELATIONAL), "relational", devices, meter,
                    rows=RELATIONAL[3])
        log(
            f"sharded single_path at n={SINGLE_PATH[2]}: at "
            f"n={RELATIONAL[2]} the single-device dense engine it is "
            "compared with needs more than one chip's 16 GiB"
        )
        run_sharded(graph_of(SINGLE_PATH), "single_path", devices, meter,
                    rows=SINGLE_PATH[3])
    else:
        run_relational(graph_of(RELATIONAL), ENGINES, dev, meter,
                       rows=RELATIONAL[3])
        unpadded = (*RELATIONAL[:2], None)
        run_relational(graph_of(unpadded), ("bitpacked",), dev, meter,
                       delta=False, rows=RELATIONAL[3])
        run_conjunctive(graph_of(RELATIONAL), dev, meter,
                        rows=RELATIONAL[3])
        log(
            f"single_path at n={SINGLE_PATH[2]}: at n={RELATIONAL[2]} its "
            "masked closure needs more than one chip's 16 GiB"
        )
        run_single_path(graph_of(SINGLE_PATH), dev, meter,
                        rows=SINGLE_PATH[3])
        log(
            f"count at n={COUNT[2]}: at n=4096 the all-pairs "
            "evaluate_count reference needs more than one chip's 16 GiB"
        )
        run_count(graph_of(COUNT), dev, meter, rows=COUNT[3])
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
