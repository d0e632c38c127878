#!/usr/bin/env python3
"""The program's spans on the device clock: how much of the chip's idle
time falls inside engine calls, and which span the host was in then.

An enabled ``repro.obs`` tracer enters a profiler annotation
``obs.<name>``, with the stat ``span_id``, around each context-managed
span, so a ``jax.profiler`` trace holds the program's spans on the clock
its device operations are stamped with.  From one trace
(:func:`read_trace`):

- ``obs``: the ``obs.*`` host events, ``(name, start, end, span_id,
  thread)`` in seconds of the trace's clock (``thread``: the index of
  the host line, one per thread, the event lies on);
- ``busy``: the first chip's ``XLA Ops`` intervals, merged.

And from those:

- :func:`engine_idle_s`: the engine-bound idle time, the device's idle
  time inside the union of ``engine.read`` / ``engine.write`` events
  (``engine_idle_share``: 100 × that over the window);
- :func:`idle_by_span`: each of those idle seconds charged to the
  innermost ``obs.*`` event open over it, summed by span name;
- :func:`clock_offset`: the offset from the spans' clock
  (``time.perf_counter``) to the trace's, the median over span ids of
  (event start − ``Span.t_start``), with the quartile spread and the
  range of those offsets.

``bench/harness.py`` reduces its trace and deletes it before the metric
readers run, so none of this is in the result line yet.  This command
runs one traced cell through the harness, reads the trace before it is
deleted, and prints the result line with ``span_clock`` beside
``breakdown`` (``checks`` stays last):

    python3 bench/span_clock.py --workload rel16k-rw --seed 7 --seconds 51

``--dump`` writes every request's timings and the spans, each with its
start on the device clock (``t_dev``: seconds on the trace's clock, the
one its device operations are stamped with) and its events
(``compile``: the function compiled, and the seconds), and the ``obs``
events themselves.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the spans that hold one engine call (``QueryEngine.query_batch`` /
#: ``apply_delta``, under the engine's lock)
ENGINE_SPANS = ("engine.read", "engine.write")
#: how many span names ``idle_by_span`` keeps, the largest first
TOP = 10


@dataclass
class SpanTrace:
    obs: list = field(default_factory=list)  # (name, a, b, span_id, thread)
    busy: list = field(default_factory=list)  # first chip, merged (s)


def read_trace(trace_dir: Path) -> SpanTrace:
    """The ``obs.*`` events and the first chip's busy intervals of the
    newest trace under ``trace_dir``, in seconds."""
    from jax.profiler import ProfileData

    from bench.trace_reduce import OPS_LINE, find_xplane, union_length

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    out = SpanTrace()
    thread = 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            if out.busy:
                continue  # the first chip only
            ivs = [(ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            out.busy = union_length(ivs)[1]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread += 1
                for ev in line.events:
                    if ev.name.startswith("obs."):
                        sid = dict(ev.stats).get("span_id")
                        a = ev.start_ns / 1e9
                        out.obs.append((ev.name[4:], a,
                                        a + ev.duration_ns / 1e9,
                                        None if sid is None else int(sid),
                                        thread))
    out.obs.sort(key=lambda e: (e[1], -e[2]))
    return out


def innermost_segments(obs) -> list[tuple[float, float, str, str]]:
    """Disjoint ``(start, end, name, call)`` pieces of the engine calls,
    each named by the innermost ``obs.*`` event open over it, with the
    engine call (``engine.read`` / ``engine.write``) it lies in.  An
    engine call runs on one thread and its events nest on that thread;
    an event of another thread (the serving loop's ``scatter``) or
    outside every engine call is not counted."""
    segs: list[tuple[float, float, str, str]] = []
    roots = [e for e in obs if e[0] in ENGINE_SPANS]
    inner = [e for e in obs if e[0] not in ENGINE_SPANS]
    starts = [e[1] for e in inner]
    for call, a, b, _, thread in roots:
        stack = [(call, b)]
        t = a
        lo = bisect.bisect_left(starts, a)
        for name, s, e, _, th in inner[lo:]:
            if s >= b:
                break
            if th != thread:
                continue
            while stack[-1][1] <= s:  # close what ended first
                top, end = stack.pop()
                if end > t:
                    segs.append((t, end, top, call))
                    t = end
            if s > t:
                segs.append((t, s, stack[-1][0], call))
            t = max(t, s)
            stack.append((name, min(e, stack[-1][1])))
        while stack:
            top, end = stack.pop()
            if end > t:
                segs.append((t, end, top, call))
                t = end
    return segs


def _idle_pieces(trace: SpanTrace):
    """Each piece of :func:`innermost_segments` less the busy intervals:
    ``(seconds idle, name, call)``."""
    busy = trace.busy
    starts = [a for a, _ in busy]
    for a, b, name, call in innermost_segments(trace.obs):
        idle = b - a
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(busy) and busy[k][0] < b:
            idle -= max(0.0, min(b, busy[k][1]) - max(a, busy[k][0]))
            k += 1
        yield idle, name, call


def engine_idle_s(trace: SpanTrace) -> float:
    """Seconds in which an engine call was open and the chip idle."""
    return sum(s for s, _, _ in _idle_pieces(trace))


def idle_by_span(trace: SpanTrace, top: int = TOP,
                 call: str | None = None) -> list[list]:
    """The engine-bound idle seconds by the innermost span open over
    them: ``[[name, seconds], ...]``, the ``top`` largest; with ``call``,
    only those inside that kind of engine call."""
    by: dict[str, float] = {}
    for s, name, c in _idle_pieces(trace):
        if call is None or c == call:
            by[name] = by.get(name, 0.0) + s
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
            if v > 0][:top]


def clock_offset(spans, obs) -> tuple[float, float, float] | None:
    """Over span ids, the trace clock's start of a span minus its
    ``t_start``: the median (add it to a ``perf_counter`` time to place
    that time on the trace's clock), the quartile spread (third quartile
    less the first) and max − min.  A thread switch between the two
    stamps makes the odd outlier, which max − min shows."""
    starts = {sid: a for _, a, _, sid, _ in obs if sid is not None}
    offs = [starts[s.span_id] - s.t_start for s in spans
            if s.span_id in starts]
    if not offs:
        return None
    q1, _, q3 = (statistics.quantiles(offs, n=4) if len(offs) > 1
                 else offs * 3)
    return statistics.median(offs), q3 - q1, max(offs) - min(offs)


def compiles(spans) -> list[dict]:
    """Every ``compile`` event: the function, its seconds, the span it
    ran under."""
    return [{"fun_name": ev["args"].get("fun_name", ""),
             "seconds": ev["args"].get("seconds"), "span": s.name,
             "span_id": s.span_id}
            for s in spans for ev in s.events if ev["name"] == "compile"]


def summary(trace: SpanTrace, spans, window_s: float) -> dict:
    """What the result line gets beside ``breakdown``."""
    idle = engine_idle_s(trace)
    offset = clock_offset(spans, trace.obs)
    named = {s.name for s in spans}
    return {
        "engine_idle_share": 100.0 * idle / window_s if window_s else None,
        "engine_idle_s": idle,
        "idle_by_span": idle_by_span(trace),
        "write_idle_by_span": idle_by_span(trace, call="engine.write"),
        "offset_s": None if offset is None else offset[0],
        "offset_spread_s": None if offset is None else offset[1],
        "offset_range_s": None if offset is None else offset[2],
        "obs_events": len(trace.obs),
        "spans_without_event": sorted(
            named - {name for name, *_ in trace.obs}),
        "compiles": compiles(spans),
    }


@contextlib.contextmanager
def _keep(kept: dict):
    """Read the harness's trace before it is deleted, and keep the run
    its dump is written from."""
    import bench.harness as harness
    import bench.trace_reduce as trace_reduce

    reduce, dump = trace_reduce.reduce_trace, harness.write_dump

    def reduce_and_read(trace_dir, window_s=None):
        kept["trace"] = read_trace(trace_dir)
        return reduce(trace_dir, window_s)

    def dump_and_keep(path, run, t0):
        kept.update(run=run, t0=t0)
        dump(path, run, t0)

    trace_reduce.reduce_trace = reduce_and_read
    harness.write_dump = dump_and_keep
    try:
        yield
    finally:
        trace_reduce.reduce_trace, harness.write_dump = reduce, dump


def traced_cell(name: str, seed: int, seconds: float, dump: Path,
                **kw) -> dict:
    """One traced run of cell ``name`` (``bench.harness.run_cell``),
    its line with ``span_clock`` beside ``breakdown``; ``dump`` gets the
    harness's dump with each span's ``t_dev`` and events added."""
    from bench.harness import run_cell

    kept: dict = {}
    with _keep(kept):
        line = run_cell(name, seed, seconds, True, dump=dump, **kw)
    run = kept["run"]
    extra = summary(kept["trace"], run.spans, line["device"]["window_s"])
    offset = extra["offset_s"] or 0.0
    out = json.loads(Path(dump).read_text())
    for rec, s in zip(out["spans"], run.spans):
        rec["t_dev"] = s.t_start + offset
        rec["events"] = [{"name": ev["name"], "t": ev["t"] - kept["t0"],
                          **ev["args"]} for ev in s.events]
    out["span_clock"] = extra
    out["obs"] = kept["trace"].obs
    Path(dump).write_text(json.dumps(out))
    checks = line.pop("checks")
    line["span_clock"] = extra
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    from bench.harness import OUT, SetupError

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", type=Path)
    args = ap.parse_args(argv)
    dump = args.dump or OUT / f"span_clock-{args.workload}.json"
    try:
        line = traced_cell(args.workload, args.seed, args.seconds, dump,
                           t_start=T_START)
    except (SetupError, FileNotFoundError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
