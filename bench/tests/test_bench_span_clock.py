"""The program's spans on the device clock (``bench/span_clock.py``): the
engine-bound idle time and its attribution on hand-placed intervals, the
recorded traces, and a tiny traced drive on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.span_clock import (
    SpanTrace,
    clock_offset,
    engine_idle_s,
    idle_by_span,
    innermost_segments,
    read_trace,
    summary,
    traced_cell,
)
from bench.tests.test_bench_drive import restore_jax_config  # noqa: F401
from bench.tests.tiny import tiny_root, workloads

DATA = Path(__file__).resolve().parent / "data"


def hand_placed() -> SpanTrace:
    """A write (0-10 s) and a read (12-14 s) with nested spans on the
    engine's thread 1, spans of the serving thread 2 (one during the
    write, one outside any engine call), and the chip busy over 5-7 and
    11-12.5 s."""
    return SpanTrace(
        obs=sorted([
            ("engine.write", 0.0, 10.0, 1, 1),
            ("delta.repair", 1.0, 9.0, 2, 1),
            ("repair.plan", 1.0, 3.0, 3, 1),
            ("scatter", 2.5, 4.5, 9, 2),  # overlaps, on another thread
            ("closure.execute", 4.0, 8.0, 4, 1),
            ("engine.mirror", 8.0, 9.5, 5, 1),  # over-runs its parent
            ("engine.read", 12.0, 14.0, 6, 1),
            ("engine.slice", 13.0, 14.0, 7, 1),
            ("scatter", 15.0, 16.0, 8, 2),
        ], key=lambda e: (e[1], -e[2])),
        busy=[(5.0, 7.0), (11.0, 12.5)],
    )


def test_innermost_segments_tile_each_engine_call():
    segs = innermost_segments(hand_placed().obs)
    w, r = "engine.write", "engine.read"
    assert segs == [
        (0.0, 1.0, w, w), (1.0, 3.0, "repair.plan", w),
        (3.0, 4.0, "delta.repair", w), (4.0, 8.0, "closure.execute", w),
        (8.0, 9.0, "engine.mirror", w), (9.0, 10.0, w, w),
        (12.0, 13.0, r, r), (13.0, 14.0, "engine.slice", r),
    ]


def test_engine_idle_and_its_attribution_by_hand():
    trace = hand_placed()
    # write 10 s less 2 busy, read 2 s less 0.5 busy; neither scatter
    # counts (another thread, and outside every engine call)
    assert engine_idle_s(trace) == pytest.approx(9.5)
    assert idle_by_span(trace) == [
        ["engine.write", pytest.approx(2.0)],
        ["repair.plan", pytest.approx(2.0)],
        ["closure.execute", pytest.approx(2.0)],
        ["delta.repair", pytest.approx(1.0)],
        ["engine.mirror", pytest.approx(1.0)],
        ["engine.slice", pytest.approx(1.0)],
        ["engine.read", pytest.approx(0.5)],
    ]
    assert idle_by_span(trace, top=2) == idle_by_span(trace)[:2]
    assert idle_by_span(trace, call="engine.read") == [
        ["engine.slice", pytest.approx(1.0)],
        ["engine.read", pytest.approx(0.5)],
    ]
    out = summary(trace, [], window_s=20.0)
    assert out["engine_idle_share"] == pytest.approx(47.5)
    assert sum(s for _, s in out["idle_by_span"]) == pytest.approx(9.5)


def test_clock_offset_is_the_median_over_span_ids():
    spans = [SimpleNamespace(span_id=k, t_start=100.0 + k)
             for k in range(1, 6)]
    # offsets -99.5, -99.5, -99.5, -99.4 and an outlier, -99.0
    obs = [("a", 1.5, 2.0, 1, 1), ("b", 2.5, 3.0, 2, 1),
           ("c", 3.5, 4.0, 3, 1), ("d", 4.6, 5.0, 4, 1),
           ("e", 6.0, 6.5, 5, 2), ("f", 9.0, 9.5, 44, 1),
           ("g", 9.0, 9.5, None, 1)]
    median, spread, full = clock_offset(spans, obs)
    assert median == pytest.approx(-99.5)
    assert spread == pytest.approx(0.3)  # quartiles -99.5 and -99.2
    assert full == pytest.approx(0.5)
    assert clock_offset([], obs) is None


def in_profile_dir(tmp_path, name):
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(DATA / name, where)
    return tmp_path


def test_busy_intervals_of_the_recorded_trace_match_its_busy_time(tmp_path):
    from bench.trace_reduce import reduce_trace

    root = in_profile_dir(tmp_path, "rel_small.xplane.pb")
    trace = read_trace(root)
    assert trace.obs == []  # recorded before the program had the bridge
    busy = sum(b - a for a, b in trace.busy)
    assert busy == pytest.approx(reduce_trace(root).busy_s, rel=1e-9)
    assert engine_idle_s(trace) == 0.0


#: ``rel_obs.xplane.pb``: a v5e ("TPU v5 lite") trace of ``rel16k-rw`` cut
#: to n = 1,024 (250 classes, 750 instances), 40 ops/s, one write in 5,
#: recorded through ``traced_cell``; the run's window was this long.  Host
#: lines that carry no ``obs.*`` or ``bench.*`` event (compiler passes,
#: runtime threads) were removed to keep the file under 500 KB, and host
#: paths replaced by a placeholder of the same length; the device plane
#: is whole.
REL_OBS_WINDOW_S = 2.7081478710000013


def test_recorded_trace_with_spans_pins_the_engine_idle_share(tmp_path):
    from bench.trace_reduce import reduce_trace

    root = in_profile_dir(tmp_path, "rel_obs.xplane.pb")
    trace = read_trace(root)
    assert len(trace.obs) == 146
    assert sum(b - a for a, b in trace.busy) == pytest.approx(
        reduce_trace(root).busy_s, rel=1e-9)
    # the serving loop's scatter spans lie on a thread of their own
    engine_threads = {e[4] for e in trace.obs if e[0] == "engine.read"}
    assert {e[4] for e in trace.obs if e[0] == "scatter"}.isdisjoint(
        engine_threads)
    out = summary(trace, [], window_s=REL_OBS_WINDOW_S)
    assert out["engine_idle_s"] == pytest.approx(1.962569044)
    assert out["engine_idle_share"] == pytest.approx(72.46905034)
    # the in-window scatter compiles hold the chip idle under the upload
    assert out["idle_by_span"][0] == ["repair.upload",
                                      pytest.approx(1.849186715)]
    assert sum(s for _, s in out["idle_by_span"]) == pytest.approx(
        out["engine_idle_s"])


def test_tiny_traced_drive_puts_spans_on_the_device_clock(
        tmp_path, restore_jax_config):  # noqa: F811
    dump = tmp_path / "dump.json"
    line = traced_cell(workloads()[0], 2**31 + 99, 2.0, dump,
                       need_tpu=False, root=tiny_root(tmp_path),
                       log=lambda msg: None)
    assert line["correct"], line["checks"]
    assert list(line)[-2:] == ["span_clock", "checks"]
    clock = line["span_clock"]
    # every context-managed span left its event; only the spans with an
    # explicit lifecycle did not
    assert set(clock["spans_without_event"]) <= {
        "request", "queue.wait", "window"}
    assert clock["obs_events"] > 0
    assert clock["offset_spread_s"] < 1e-3
    # no device plane on the CPU: every engine second reads as idle
    assert clock["engine_idle_s"] > 0
    assert sum(s for _, s in clock["idle_by_span"]) == pytest.approx(
        clock["engine_idle_s"], rel=1e-6)
    out = json.loads(dump.read_text())
    assert out["span_clock"] == json.loads(json.dumps(clock))
    # each span's start on the trace's clock is its event's start there
    starts = {sid: a for _, a, _, sid, _ in out["obs"]}
    placed = [s for s in out["spans"] if s["id"] in starts]
    assert placed
    for s in placed:
        assert abs(s["t_dev"] - starts[s["id"]]) < 1e-3
    for s in out["spans"]:
        for ev in s["events"]:
            if ev["name"] == "compile":
                assert ev["fun_name"] and ev["seconds"] >= 0
