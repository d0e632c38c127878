"""Observability subsystem tests (repro.obs; OBSERVABILITY.md).

Four layers:

* tracer/metrics primitives — spans, context nesting, explicit clocks,
  the allocation-free histogram path, Prometheus text rendering;
* the **zero-overhead contract** — a disabled tracer records nothing and
  the engine compiles only *uninstrumented* PlanKeys (the hot path is
  bit-for-bit the one that existed before this subsystem);
* the stable JSON schema — ``QueryStats.to_dict`` round-trips through
  ``repro.obs.export.snapshot`` with serve-only fields omitted when the
  request never went through the serving loop;
* end-to-end — a traced ``CFPQServer`` run keeps the exactly-once
  accounting (``served+failed+cancelled == admitted``), nests
  closure-execute spans under window → request, and carries per-iteration
  events with active-row counts; the HTTP endpoint serves both formats.
"""
from __future__ import annotations

import asyncio
import json

import pytest

from repro.core.grammar import query1_grammar
from repro.core.graph import ontology_graph
from repro.engine import Query, QueryEngine
from repro.engine.stats import QueryStats
from repro.obs.chrome import to_chrome_trace
from repro.obs.export import render_prometheus, snapshot
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer
from repro.serve import CFPQServer, ServeConfig


# --------------------------------------------------------------------- #
# tracer primitives
# --------------------------------------------------------------------- #
def test_tracer_spans_nest_and_close():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    with tr.span("outer", cat="x") as outer:
        t[0] = 1.0
        with tr.span("inner") as inner:
            t[0] = 3.0
            tr.event("tick", k=1)
        t[0] = 5.0
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert inner.t_start == 1.0 and inner.t_end == 3.0
    assert outer.t_end == 5.0 and outer.duration_s == 5.0
    assert inner.events == [{"name": "tick", "t": 3.0, "args": {"k": 1}}]


def test_tracer_finish_idempotent_and_explicit_lifecycle():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    sp = tr.start_span("request", cat="serve", src=4)
    t[0] = 2.0
    tr.finish(sp, outcome="served")
    t[0] = 9.0
    tr.finish(sp, outcome="late")  # no-op: already closed
    assert sp.t_end == 2.0 and sp.attrs["outcome"] == "served"
    assert sp.attrs["src"] == 4


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    sp = tr.start_span("x")
    assert sp is NULL_SPAN and not sp  # falsy: callers can gate on it
    with tr.span("y") as sp2:
        tr.event("e")
        sp2.set(a=1).add_event("n", 0.0)
    assert tr.spans == [] and tr.current() is None
    assert not tr.wants_iterations
    # wrap degrades to the bare callable
    fn = lambda: 42  # noqa: E731
    assert tr.wrap(NULL_SPAN, fn) is fn


def test_tracer_max_spans_bound():
    tr = Tracer(max_spans=2)
    a, b, c = tr.start_span("a"), tr.start_span("b"), tr.start_span("c")
    assert len(tr.spans) == 2 and tr.dropped == 1
    assert c is NULL_SPAN
    tr.clear()
    assert tr.spans == [] and tr.dropped == 0


def test_tracer_wrap_carries_parent_across_threads():
    import threading

    tr = Tracer()
    parent = tr.start_span("window")
    seen = {}

    def job():
        seen["current"] = tr.current()

    th = threading.Thread(target=tr.wrap(parent, job))
    th.start()
    th.join()
    assert seen["current"] is parent
    assert tr.current() is None  # never leaked into this thread


# --------------------------------------------------------------------- #
# metrics primitives
# --------------------------------------------------------------------- #
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = Counter("c_total", "c", registry=reg)
    g = Gauge("g", "g", registry=reg)
    h = Histogram("h_seconds", "h", buckets=(0.1, 1.0), registry=reg)
    c.inc()
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    g.set(5)
    g.dec(2)
    for v in (0.05, 0.5, 3.0):
        h.observe(v)
    snap = reg.collect()
    assert snap["c_total"]["series"][0]["value"] == 3
    assert snap["g"]["series"][0]["value"] == 3
    hs = snap["h_seconds"]["series"][0]
    assert hs["count"] == 3 and hs["sum"] == pytest.approx(3.55)
    assert hs["buckets"] == {"0.1": 1, "1.0": 2}  # cumulative


def test_labels_and_registry_rules():
    reg = MetricsRegistry()
    c = Counter("routes_total", "r", labelnames=("route",), registry=reg)
    c.labels(route="dense").inc()
    c.labels(route="dense").inc()
    c.labels(route="opt").inc()
    vals = {
        s["labels"]["route"]: s["value"]
        for s in reg.collect()["routes_total"]["series"]
    }
    assert vals == {"dense": 2, "opt": 1}
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):  # duplicate family name
        Counter("routes_total", "again", registry=reg)


def test_render_prometheus_text_format():
    reg = MetricsRegistry()
    Counter("reqs_total", "Requests", registry=reg).inc(7)
    h = Histogram("lat_seconds", "Latency", buckets=(0.5,), registry=reg)
    h.observe(0.2)
    h.observe(2.0)
    text = render_prometheus(reg)
    assert "# HELP reqs_total Requests" in text
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 7" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_sum 2.2" in text
    assert "lat_seconds_count 2" in text
    assert text.endswith("\n")


# --------------------------------------------------------------------- #
# stable JSON schema: QueryStats.to_dict through snapshot
# --------------------------------------------------------------------- #
def test_querystats_snapshot_roundtrip_omits_unset_serve_fields():
    reg = MetricsRegistry()
    plain = QueryStats(latency_s=0.5, cache="miss", engine="dense")
    served = QueryStats(
        latency_s=0.5,
        cache="hit",
        engine="dense",
        queue_delay_s=0.01,
        batch_exec_s=0.002,
        flush_reason="size",
        window_batch=4,
    )
    snap = json.loads(
        json.dumps(snapshot(reg, query_stats=[plain, served]))
    )
    assert snap["schema"] == 1
    row0, row1 = snap["queries"]
    # engine-only request: no serve keys at all (not nulls)
    for k in ("queue_delay_s", "batch_exec_s", "flush_reason", "window_batch"):
        assert k not in row0
        assert k in row1
    assert row1["flush_reason"] == "size" and row1["window_batch"] == 4
    # engine fields always present, and the projection is JSON-stable
    for row in (row0, row1):
        assert row["cache"] in ("hit", "warm", "miss")
        assert row == json.loads(json.dumps(row))


# --------------------------------------------------------------------- #
# zero-overhead contract
# --------------------------------------------------------------------- #
def _tiny():
    graph = ontology_graph(8, 16, seed=0)
    g = query1_grammar().to_cnf()
    return graph, g


def test_disabled_tracer_compiles_uninstrumented_plans_only(monkeypatch):
    import jax

    from repro.obs import trace as obs_trace

    entered, registered = [], []
    real_annotation = jax.profiler.TraceAnnotation

    def annotation(*a, **k):
        entered.append(a)
        return real_annotation(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    graph, g = _tiny()
    eng = QueryEngine(graph)  # default wiring: NULL_TRACER
    eng.query(Query(g, "S", sources=(1,)))
    eng.apply_delta(insert=[(0, "subClassOf", 3)])
    assert len(eng.plans) > 0
    assert all(not k.instrumented for k in eng.plans._exe)
    assert eng.tracer.spans == []
    # no profiler annotation entered, no listener registered or fed
    off = Tracer(enabled=False)
    assert entered == [] and registered == []
    assert off not in obs_trace._COMPILE_TRACERS
    assert eng.tracer not in obs_trace._COMPILE_TRACERS
    monkeypatch.undo()  # an enabled tracer registers the real listener
    # the same PlanKeys as an enabled tracer without iteration events:
    # the fourth output (the iteration count) adds no executable
    graph, g = _tiny()  # the same graph, before the write
    traced = QueryEngine(graph, tracer=Tracer(iteration_events=False))
    traced.query(Query(g, "S", sources=(1,)))
    traced.apply_delta(insert=[(0, "subClassOf", 3)])
    assert set(traced.plans._exe) == set(eng.plans._exe)


def test_disabled_span_is_one_shared_context():
    from repro.obs.trace import NULL_TRACER

    off = Tracer(enabled=False)
    assert off.span("a") is off.span("b", cat="x", k=1)
    assert NULL_TRACER.span("c") is off.span("d")
    with off.span("e") as sp:
        assert sp is NULL_SPAN


def test_enabled_span_enters_a_profiler_annotation(monkeypatch):
    import jax

    entered = []
    real_annotation = jax.profiler.TraceAnnotation

    def annotation(name, **kw):
        entered.append((name, kw))
        return real_annotation(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            pass
    explicit = tr.start_span("request")  # explicit lifecycle: none
    tr.finish(explicit)
    assert entered == [("obs.outer", {"span_id": outer.span_id}),
                       ("obs.inner", {"span_id": inner.span_id})]


def test_compile_event_names_the_jitted_function():
    import jax
    import jax.numpy as jnp

    def obs_forced_compile(x):  # a fresh function object: never cached
        return x * 3 + 1

    tr = Tracer()
    with tr.span("outer") as outer:
        jax.jit(obs_forced_compile)(jnp.arange(7)).block_until_ready()
    names = [ev["args"]["fun_name"] for ev in outer.events
             if ev["name"] == "compile"]
    assert any("obs_forced_compile" in n for n in names), outer.events
    for ev in outer.events:
        assert ev["args"]["seconds"] >= 0


def _span_chain(spans, s):
    by_id = {x.span_id: x for x in spans}
    names = []
    while s.parent_id is not None:
        s = by_id[s.parent_id]
        names.append(s.name)
    return names


def test_engine_spans_nest_under_read_and_write():
    graph, g = _tiny()
    tr = Tracer(iteration_events=False)
    eng = QueryEngine(graph, tracer=tr)
    eng.query(Query(g, "S", sources=(1,)))
    eng.query(Query(g, "S", sources=(1,), semantics="single_path"))
    eng.query(Query(g, "S", sources=(1,)))  # a hit: sliced only
    edge = next(e for e in sorted(graph.edges) if e[1] == "subClassOf")
    eng.apply_delta(insert=[(2, "type", 5)], delete=[edge])
    spans = tr.spans
    assert all(s.t_end is not None for s in spans)
    reads = [s for s in spans if s.name == "engine.read"]
    assert [(r.attrs["semantics"], r.attrs["cache"]) for r in reads] == [
        ("relational", "miss"), ("single_path", "miss"),
        ("relational", "hit")]
    assert all(r.parent_id is None and r.attrs["batch"] == 1 for r in reads)
    writes = [s for s in spans if s.name == "engine.write"]
    assert len(writes) == 1
    assert writes[0].attrs == {"inserted": 1, "deleted": 1}
    for s in spans:
        chain = _span_chain(spans, s)
        if s.name == "engine.slice":
            assert chain == ["engine.read"] and s.attrs["pairs"] >= 1
        elif s.name == "engine.mirror":
            assert chain[-1] in ("engine.read", "engine.write")
            assert s.attrs["bytes"] > 0
        elif s.name.startswith("repair."):
            assert chain == ["delta.repair", "engine.write"]
    slices = [s for s in spans if s.name == "engine.slice"]
    assert len(slices) == 3
    assert slices[1].attrs["extracted"] + slices[1].attrs["memo_hits"] >= 1
    names = {s.name for s in spans}
    assert {"repair.plan", "repair.base_rows", "repair.upload",
            "engine.mirror"} <= names
    plan = next(s for s in spans if s.name == "repair.plan")
    assert plan.attrs["evict"] >= 1
    upload = next(s for s in spans if s.name == "repair.upload")
    base = next(s for s in spans if s.name == "repair.base_rows")
    assert upload.attrs["bytes"] == base.attrs["bytes"] > 0
    # a mirror follows every read closure and every repair
    mirrors = [_span_chain(spans, s) for s in spans
               if s.name == "engine.mirror"]
    assert mirrors.count(["engine.read"]) == 2
    assert mirrors.count(["delta.repair", "engine.write"]) == 2


@pytest.mark.parametrize("engine,semantics", [
    ("dense", "relational"), ("frontier", "relational"),
    ("bitpacked", "relational"), ("blocksparse", "relational"),
    ("dense", "single_path"), ("dense", "count"),
])
def test_closure_iterations_equal_iteration_events(engine, semantics):
    from repro.engine import EngineConfig

    runs = {}
    for events in (True, False):
        graph, g = _tiny()  # each run writes to its own graph
        tr = Tracer(iteration_events=events)
        eng = QueryEngine(graph, config=EngineConfig(engine=engine),
                          tracer=tr)
        eng.query(Query(g, "S", sources=(1, 4), semantics=semantics))
        eng.apply_delta(insert=[(0, "subClassOf", 3)])
        runs[events] = [s for s in tr.spans if s.name == "closure.execute"]
    assert runs[True], "a closure ran"
    for s in runs[True]:
        ticks = [ev for ev in s.events if ev["name"] == "iteration"]
        assert s.attrs["iterations"] == len(ticks) > 0
    # the uninstrumented executables count the same iterations
    assert [s.attrs["iterations"] for s in runs[False]] == [
        s.attrs["iterations"] for s in runs[True]]


def test_enabled_tracer_requests_instrumented_plans_with_iterations():
    graph, g = _tiny()
    tr = Tracer()
    eng = QueryEngine(graph, tracer=tr)
    eng.query(Query(g, "S", sources=(1,)))
    assert any(k.instrumented for k in eng.plans._exe)
    closure_spans = [s for s in tr.spans if s.name == "closure.execute"]
    assert closure_spans
    iters = [
        ev for s in closure_spans for ev in s.events
        if ev["name"] == "iteration"
    ]
    assert iters, "instrumented closures must emit iteration events"
    for ev in iters:
        assert set(ev["args"]) >= {"iteration", "active_rows", "changed", "overflow"}
        assert ev["args"]["active_rows"] >= 0


def test_tracer_without_iteration_events_stays_uninstrumented():
    graph, g = _tiny()
    tr = Tracer(iteration_events=False)
    eng = QueryEngine(graph, tracer=tr)
    eng.query(Query(g, "S", sources=(1,)))
    # spans recorded, but the compiled hot path is the untraced one
    assert any(s.name == "closure.execute" for s in tr.spans)
    assert all(not k.instrumented for k in eng.plans._exe)
    assert all(
        ev["name"] != "iteration" for s in tr.spans for ev in s.events
    )


# --------------------------------------------------------------------- #
# end-to-end: traced serving keeps exactly-once accounting
# --------------------------------------------------------------------- #
def test_traced_server_exactly_once_and_span_nesting():
    async def main():
        graph, g = _tiny()
        tr = Tracer()
        reg = MetricsRegistry()
        eng = QueryEngine(graph)
        srv = CFPQServer(
            eng,
            ServeConfig(max_batch=4, batch_window_s=0.002),
            tracer=tr,
            metrics=reg,
        )
        async with srv:
            qs = [Query(g, "S", sources=(i,)) for i in range(6)]
            results = await asyncio.gather(*[srv.submit(q) for q in qs])
            await srv.apply_delta(insert=[(0, "subClassOf", 3)])
        st = srv.stats
        assert len(results) == 6
        assert st.served + st.failed + st.cancelled == st.admitted == 6
        # metrics agree with ServeStats
        snap = reg.collect()
        outcomes = {
            s["labels"]["outcome"]: s["value"]
            for s in snap["serve_outcomes_total"]["series"]
        }
        assert outcomes["served"] == st.served
        assert outcomes["failed"] == st.failed == 0
        assert snap["serve_admitted_total"]["series"][0]["value"] == 6
        assert snap["serve_queue_delay_seconds"]["series"][0]["count"] == 6
        assert snap["serve_batch_exec_seconds"]["series"][0]["count"] >= 1
        assert snap["planner_route_total"]["series"], "route counters present"
        # every span closed; closure spans nest under window -> request
        assert all(s.t_end is not None for s in tr.spans)
        by_id = {s.span_id: s for s in tr.spans}

        def chain(s):
            names = []
            while s.parent_id is not None:
                s = by_id[s.parent_id]
                names.append(s.name)
            return names

        read_closures = [
            s
            for s in tr.spans
            if s.name == "closure.execute"
            and "delta.repair" not in chain(s)
        ]
        assert read_closures
        for s in read_closures:
            assert "window" in chain(s) and "request" in chain(s)
        # the write path traced its repair too
        assert any(s.name == "delta.repair" for s in tr.spans)
        return tr

    tr = asyncio.run(main())
    # chrome export of the same run is structurally valid
    trace = json.loads(json.dumps(to_chrome_trace(tr)))
    evs = trace["traceEvents"]
    assert evs[0]["ph"] == "M"  # process metadata first
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {
        "request", "queue.wait", "window", "closure.execute", "scatter"
    }
    for e in xs:
        assert e["dur"] >= 0 and isinstance(e["ts"], (int, float))
    assert any(
        e["ph"] == "i" and e["name"] == "iteration" for e in evs
    )


def test_traced_server_cancelled_accounting():
    async def main():
        graph, g = _tiny()
        tr = Tracer()
        reg = MetricsRegistry()
        eng = QueryEngine(graph)
        # long window so the query parks; cancel before it flushes
        srv = CFPQServer(
            eng,
            ServeConfig(max_batch=64, batch_window_s=5.0),
            tracer=tr,
            metrics=reg,
        )
        task = asyncio.create_task(srv.submit(Query(g, "S", sources=(1,))))
        await asyncio.sleep(0.01)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        await srv.stop(drain=False)
        st = srv.stats
        assert st.admitted == 1 and st.cancelled == 1
        assert st.served + st.failed + st.cancelled == st.admitted
        outcomes = {
            s["labels"]["outcome"]: s["value"]
            for s in reg.collect()["serve_outcomes_total"]["series"]
        }
        assert outcomes["cancelled"] == 1
        req = [s for s in tr.spans if s.name == "request"]
        assert len(req) == 1 and req[0].attrs["outcome"] == "cancelled"
        assert all(s.t_end is not None for s in tr.spans)

    asyncio.run(main())


# --------------------------------------------------------------------- #
# HTTP exposition endpoint
# --------------------------------------------------------------------- #
def test_metrics_endpoint_serves_both_formats():
    async def main():
        graph, g = _tiny()
        reg = MetricsRegistry()
        eng = QueryEngine(graph)
        cfg = ServeConfig(max_batch=4, batch_window_s=0.001, metrics_port=0)
        async with CFPQServer(eng, cfg, metrics=reg) as srv:
            port = srv.metrics_port
            assert port  # ephemeral port bound
            await srv.submit(Query(g, "S", sources=(1,)))

            async def get(path):
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
                raw = await r.read()
                w.close()
                head, body = raw.split(b"\r\n\r\n", 1)
                return head.decode(), body

            head, body = await get("/metrics")
            assert "200 OK" in head
            assert b"serve_admitted_total 1" in body
            head, body = await get("/metrics.json")
            assert "200 OK" in head
            js = json.loads(body)
            assert js["serve"]["admitted"] == 1
            assert "serve_queue_delay_seconds" in js["metrics"]
            head, _ = await get("/nope")
            assert "404" in head
        assert srv.metrics_port is None  # listener torn down on stop

    asyncio.run(main())
