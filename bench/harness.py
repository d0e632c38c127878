"""Run one cell of ``BENCHMARK.json``: build, warm up, drive the window
through ``CFPQServer``, check every answer, reduce the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name.  Data: ``bench/configs/<config>.json`` and
``bench/traffic/<mix>.json``.  Code, named in those files or in
``BENCHMARK.json``:

- ``bench/graphs/<generator>.py``: the configuration's ``"generator"``,
  ``build(config, seed)``, its graph;
- ``bench/compare/<semantics>.py``: the configuration's ``"semantics"``,
  ``compare(edges, config, reads, rows)``, a read against the reference;
- ``bench/loops/<loop>.py``: the mix's ``"loop"``, its schedule, set-up
  steps and the drive of the server (``schedule``, ``warmup``, ``drive``);
- ``bench/writes/<rule>.py``: the mix's ``"writes"``, which triples are
  written (``triples``, ``warmup_triple``);
- ``bench/metrics/<metric>.py``: ``read(run)``, one metric.

A configuration's ``engine`` block may name ``"mesh": [data, model]``:
the engine then runs sharded over a mesh of exactly the cell's chips
(:func:`check_layout`, :func:`build_engine`).

This module holds only what every cell shares.  :func:`run_cell` is the
in-process entry (the tests call it on the CPU with ``need_tpu=False``);
``bench/run.py`` is the command.
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / "out"
#: seconds past the window's close that a request due in it may take
GRACE_S = 60.0


class SetupError(RuntimeError):
    """The run cannot start: missing files, or no accelerator."""


# ---------------------------------------------------------------------- #
# what a cell is made of
# ---------------------------------------------------------------------- #
@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/traffic/<mix>.json
    metrics: dict[str, dict]  # this cell's end-to-end metrics, by name
    layer_metrics: dict[str, dict]  # this cell's per-layer metrics


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Cell ``name`` of ``root/BENCHMARK.json``, its files read."""
    from bench.traffic import load_mix

    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SetupError(f"no BENCHMARK.json at {path}")
    bench = json.loads(path.read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SetupError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    chips = int(w["chips"])
    config = json.loads((root / conf["file"]).read_text())
    check_layout(name, chips, config["engine"])
    return Cell(
        name=name,
        chips=chips,
        config=config,
        mix=load_mix(w["traffic"], root / "bench" / "traffic"),
        metrics=_metrics_of(bench["end_to_end"], name, ()),
        layer_metrics=_metrics_of(
            bench["per_layer"], name, _metrics_of(bench["end_to_end"], name, ())
        ),
    )


def check_layout(cell: str, chips: int, engine: dict) -> None:
    """A cell on more than one chip runs its engine on a mesh of exactly
    those chips: the configuration's ``engine`` block names it as
    ``"mesh": [data, model]``, beside an engine that shards (``opt``, or
    ``auto``, whose planner may pick it)."""
    mesh = engine.get("mesh")
    if mesh is None:
        if chips > 1:
            raise SetupError(
                f"cell {cell!r} asks for {chips} chips, but its "
                "configuration's engine names no mesh"
            )
        return
    if not (isinstance(mesh, list) and len(mesh) == 2
            and all(isinstance(k, int) and k > 0 for k in mesh)
            and math.prod(mesh) == chips):
        raise SetupError(
            f"cell {cell!r}: mesh {mesh} is not a [data, model] shape "
            f"over the cell's {chips} chip(s)"
        )
    if engine["engine"] not in ("opt", "auto"):
        raise SetupError(
            f"cell {cell!r}: a mesh needs the engine 'opt' or 'auto', "
            f"not {engine['engine']!r}"
        )


def _metrics_of(entries, cell: str, reported) -> dict[str, dict]:
    """Entries naming ``cell`` in ``workloads``; without that key, every
    cell (a per-layer metric: every cell that reports what it moves)."""
    out = {}
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out[m["name"]] = m
        elif "moves" not in m or m["moves"] in reported:
            out[m["name"]] = m
    return out


_PLUGINS: dict[tuple[str, str], object] = {}


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once."""
    key = (kind, name)
    if key not in _PLUGINS:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise SetupError(f"no {kind} file for {name!r} at {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod  # dataclasses look their module up
        spec.loader.exec_module(mod)
        _PLUGINS[key] = mod
    return _PLUGINS[key]


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    return plugin("metrics", name).read


def require_devices(chips: int, need_tpu: bool = True):
    """The cell's chips, the first ``chips`` of JAX's devices, or
    :class:`SetupError` naming what JAX found.  ``need_tpu=False`` takes
    them from any platform (the tests on the CPU)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if need_tpu and platform != "tpu":
        raise SetupError(
            f"needs a TPU, but JAX's platform is {platform!r} "
            f"({len(devices)} device(s)); there is no CPU fallback"
        )
    if len(devices) < chips:
        raise SetupError(
            f"needs {chips} {platform.upper()} chip(s), found {len(devices)}"
        )
    if need_tpu:
        peaks_of(devices[0].device_kind)
    return devices[:chips]


def peaks_of(kind: str) -> dict:
    """The published peaks of chip ``kind`` (``bench/peaks.json``); a
    chip that is not in the table is an error, not a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        known = sorted(k for k in table if k != "source")
        raise SetupError(f"no peaks for device kind {kind!r}; have {known}")
    return table[kind]


# ---------------------------------------------------------------------- #
# what a run records
# ---------------------------------------------------------------------- #
@dataclass
class ReadRec:
    source: int
    due: float  # seconds into the window
    readback: bool = False
    late: float = 0.0  # submit time past due
    acked_before: int = 0  # writes acknowledged when it was submitted
    started_before_done: int = 0  # writes begun when it completed
    outcome: str = "lost"  # ok | shed | failed | lost
    t_done: float = math.inf
    pairs: set = field(default_factory=set)
    paths: dict | None = None
    stats: dict = field(default_factory=dict)

    @property
    def latency_s(self) -> float:
        return self.t_done - self.due if self.outcome == "ok" else math.inf


@dataclass
class WriteRec:
    due: float
    late: float = 0.0
    outcome: str = "lost"
    t_done: float = math.inf
    epoch: int = -1
    delta: dict = field(default_factory=dict)

    @property
    def latency_s(self) -> float:
        return self.t_done - self.due if self.outcome == "ok" else math.inf


@dataclass
class Call:
    """One engine call seen by the traced run's wrapper (host clock)."""

    kind: str  # "read" | "write"
    t0: float
    t1: float
    span_id: int | None  # the tracer's current span: the batch's window
    cache: str = ""


@dataclass
class Run:
    """Everything a metric reader may read."""

    reads: list[ReadRec]
    writes: list[WriteRec]
    window_s: float
    setup_s: float
    compiles_in_window: int
    spans: list = field(default_factory=list)  # repro.obs Span, traced runs
    calls: list[Call] = field(default_factory=list)
    device: object = None  # bench.trace_reduce.DeviceTrace, traced runs


def quantile(values, q: float) -> float:
    """Nearest-rank quantile; ``inf`` (a missing answer) sorts last."""
    vals = sorted(values)
    if not vals:
        return math.nan
    return vals[max(0, math.ceil(q * len(vals)) - 1)]


# ---------------------------------------------------------------------- #
# compile counting (copied from chip_smoke.CompileMeter)
# ---------------------------------------------------------------------- #
class CompileMeter:
    """Backend compiles and their seconds, from JAX's monitoring events
    (a persistent-cache hit's retrieval counts as a compile), and the
    persistent cache's misses.  A context manager: the listeners are
    removed on exit."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_misses = 0  # compiled anew, not read from the cache

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------- #
# the system under test
# ---------------------------------------------------------------------- #
def build_engine(config: dict, graph, devices):
    """The engine of ``config`` over ``graph``; where the configuration
    names a ``mesh``, sharded over that (data, model) mesh of
    ``devices``, every axis ``Auto``."""
    from repro.core.graph import Graph
    from repro.engine import EngineConfig, QueryEngine
    from repro.shard import make_mesh

    eng = config["engine"]
    mesh = (make_mesh(eng["mesh"], devices=devices)
            if "mesh" in eng else None)
    return QueryEngine(
        Graph(graph.n_nodes, list(graph.edges)),
        config=EngineConfig(
            engine=eng["engine"], mesh=mesh, row_capacity=eng["row_capacity"]
        ),
    )


def program_grammar(config: dict):
    from repro.core.grammar import Grammar

    return Grammar.from_text(config["grammar"]["text"]).to_cnf()


def wrap_calls(engine, tracer, calls: list[Call]) -> None:
    """Time each engine call on the host clock, under a profiler
    annotation, with the span it ran under (traced runs only)."""
    import jax

    query_batch, apply_delta = engine.query_batch, engine.apply_delta

    def timed(kind, fn, *a, **k):
        cur = tracer.current()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{kind}"):
            out = fn(*a, **k)
        call = Call(kind, t0, time.perf_counter(),
                    cur.span_id if cur is not None else None)
        if kind == "read" and out:
            call.cache = out[0].stats.cache
        calls.append(call)
        return out

    engine.query_batch = lambda *a, **k: timed("read", query_batch, *a, **k)
    engine.apply_delta = lambda *a, **k: timed("write", apply_delta, *a, **k)


async def warm_up(srv, steps, query_of, log=None) -> None:
    """Run ``warmup_ops`` step by step; a step's reads go together."""
    for k, step in enumerate(steps):
        t = time.perf_counter()
        for op in step:
            if op.kind == "write":
                await srv.apply_delta(op.insert, op.delete)
        reads = [srv.submit(query_of(op.source))
                 for op in step if op.kind == "read"]
        if reads:
            await asyncio.gather(*reads)
        if log is not None:
            log(f"warm-up step {k}: {time.perf_counter() - t:.3f}s")


# ---------------------------------------------------------------------- #
# one run
# ---------------------------------------------------------------------- #
def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float | None = None,
    need_tpu: bool = True,
    fault: str | None = None,
    rate: float | None = None,
    dump: Path | None = None,
    root: Path = ROOT,
    log=None,
) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``t_start`` is when the process began (set-up is counted from it).
    ``need_tpu=False`` skips the look for a chip (tests on the CPU);
    ``fault`` breaks the timed path underneath (``bench.faults``: the
    control and the fault tests); ``rate`` overrides the mix's rate (the
    knee sweep); ``dump`` names a JSON file for every request's timings
    and the traced run's spans."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(name, root)
    import jax

    devices = require_devices(cell.chips, need_tpu)
    marks = {"devices": time.perf_counter()}  # set-up's phases end here
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # bitpacked executables compile in under a second; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import faults as faults_mod
    from repro.engine import Query
    from repro.obs.trace import Tracer
    from repro.serve import CFPQServer, ServeConfig

    cfg = cell.config
    mix = dict(cell.mix, **({"rate_per_s": rate} if rate else {}))
    loop = plugin("loops", mix["loop"])
    writes = plugin("writes", mix["writes"]) if "writes" in mix else None
    compare = plugin("compare", cfg["semantics"]).compare
    graph = plugin("graphs", cfg["generator"]).build(cfg, seed)
    ops = loop.schedule(mix, graph, seed, seconds, writes)
    steps = loop.warmup(mix, graph, seed, writes)
    marks["data"] = time.perf_counter()
    grammar = program_grammar(cfg)
    start, semantics = cfg["grammar"]["start"], cfg["semantics"]

    def query_of(source: int):
        return Query(grammar, start, sources=(source,), semantics=semantics)

    engine = build_engine(cfg, graph, devices)
    if fault is not None:
        faults_mod.install(fault, engine)
    marks["engine"] = time.perf_counter()
    tracer = Tracer(iteration_events=False) if trace else None
    calls: list[Call] = []
    if trace:
        wrap_calls(engine, tracer, calls)
    trace_dir = OUT / f"trace-{name}"
    state = {}

    async def main() -> None:
        async with CFPQServer(engine, ServeConfig(**cfg["serve"]),
                              tracer=tracer) as srv:
            await warm_up(srv, steps, query_of, log)
            state["e0"] = engine.clock.epoch
            state["compiles0"] = meter.count
            state["compile_s0"] = meter.seconds
            state["misses0"] = meter.cache_misses
            if trace:
                tracer.clear()
                calls.clear()
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            t0 = time.perf_counter()
            state["setup_s"] = t0 - t_start
            marks["warmup"] = t0
            reads, wrecs = await loop.drive(srv, ops, query_of, t0, seconds)
            t1 = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
            state.update(reads=reads, writes=wrecs, t0=t0, t1=t1,
                         compiles=meter.count - state["compiles0"])

    with CompileMeter() as meter:
        asyncio.run(main())
    # the peak before anything else runs; then free the program's state
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    spans = list(tracer.spans) if trace else []
    del engine
    gc.collect()

    device_trace = None
    if trace:
        from bench.trace_reduce import reduce_trace

        device_trace = reduce_trace(trace_dir, state["t1"] - state["t0"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    reads, writes = state["reads"], state["writes"]
    run = Run(
        reads=reads, writes=writes,
        window_s=state["t1"] - state["t0"], setup_s=state["setup_s"],
        compiles_in_window=state["compiles"], spans=spans, calls=calls,
        device=device_trace,
    )
    from bench.reference.check import check_run

    t_check = time.perf_counter()
    checks, parts = check_run(cfg, graph, ops, run, state["e0"], compare)
    wanted = cell.layer_metrics if trace else cell.metrics
    metrics = {}
    for mname, entry in wanted.items():
        value = metric_reader(mname)(run)
        if value is not None:
            metrics[mname] = {"value": value, "unit": entry["unit"]}
    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    line = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(reads) + len(writes),
        "failed": sum(r.outcome != "ok" for r in [*reads, *writes]),
        "metrics": metrics,
        "device": device,
    }
    if device_trace is not None:
        device.update(busy_s=device_trace.busy_s,
                      window_s=device_trace.window_s)
        line["breakdown"] = device_trace.breakdown()
    lat = [r.late for r in reads]
    done = [x.t_done for x in [*reads, *writes] if x.outcome == "ok"]
    line["window"] = {
        "reads": len(reads), "writes": len(writes),
        "shed": sum(r.outcome == "shed" for r in reads),
        "late_p95_ms": 1e3 * quantile(lat, 0.95) if lat else 0.0,
        "late_max_ms": 1e3 * max(lat, default=0.0),
        # how long past the close the last answer came: a backlog that
        # grew through the window drains here
        "drain_s": max(done, default=seconds) - seconds,
        "window_compiles": state["compiles"],
        "setup_compiles": state["compiles0"],
        "setup_compile_s": state["compile_s0"],
        "setup_cache_misses": state["misses0"],
        # seconds from the previous phase's end (the first: from start)
        "setup_phases_s": {
            k: marks[k] - prev
            for k, prev in zip(marks, [t_start, *marks.values()])
        },
        "compile_cache": cache_dir,
        "check_s": time.perf_counter() - t_check,
        "wrong_parts": parts,
    }
    if dump is not None:
        write_dump(dump, run, state["t0"])
    line["checks"] = checks  # last: each number compared, with its limit
    log("wrong = " + " + ".join(f"{k} {v}" for k, v in parts.items()))
    for key, c in checks.items():
        log(f"check {key}={c['value']} limit={c['limit']}")
    return line


def write_dump(path: Path, run: Run, t0: float) -> None:
    """Every request's timings (and, traced, spans and engine calls) as
    JSON, times in seconds from the window's start."""
    def fin(x):
        return x if math.isfinite(x) else None

    out = {
        "reads": [
            {"due": r.due, "late": r.late, "done": fin(r.t_done),
             "outcome": r.outcome, "readback": r.readback, **r.stats}
            for r in run.reads
        ],
        "writes": [
            {"due": w.due, "late": w.late, "done": fin(w.t_done),
             "outcome": w.outcome, "epoch": w.epoch, **w.delta}
            for w in run.writes
        ],
        "spans": [
            {"name": s.name, "id": s.span_id, "parent": s.parent_id,
             "t": s.t_start - t0,
             "dur": None if s.t_end is None else s.t_end - s.t_start,
             "attrs": {k: v for k, v in s.attrs.items()
                       if isinstance(v, (int, float, str, bool))}}
            for s in run.spans
        ],
        "calls": [
            {"kind": c.kind, "t0": c.t0 - t0, "t1": c.t1 - t0,
             "span": c.span_id, "cache": c.cache}
            for c in run.calls
        ],
    }
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(out))
