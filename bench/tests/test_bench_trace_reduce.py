"""The reduction from a profiler trace to busy time, idle share,
contraction share and the breakdown."""
from __future__ import annotations

import shutil
from pathlib import Path

import pytest

from bench.trace_reduce import (
    DeviceTrace,
    host_activity,
    is_contraction,
    parse_op,
    reduce_trace,
    union_length,
)

DATA = Path(__file__).resolve().parent / "data"


def test_union_of_overlapping_intervals():
    covered, merged = union_length([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert covered == 7
    assert merged == [(0, 3), (5, 9)]


def test_idle_gap_is_named_by_the_call_covering_most_of_it():
    ann = [(0, 10, "read"), (12, 30, "write")]
    assert host_activity(ann, 13, 20) == "engine write call"
    assert host_activity(ann, 9, 13) == "serve loop"  # no call covers half
    assert host_activity([], 1, 2) == "serve loop"


def test_op_text_parsing_and_contractions():
    conv = ("%convolution_compare_fusion.2 = pred[4,256,1024]{2,1,0:T(8,128)}"
            " fusion(pred[4,256,1024]{2,1,0} %fusion.30), kind=kOutput")
    loop = ("%while = (pred[5,1024,1024]{2,0,1:T(8,128)}, s32[]{:T(128)}) "
            "while((pred[5,1024,1024]{2,0,1}, s32[]) %tuple), body=%b")
    copy = "%copy.1 = s32[800]{0:T(1024)} copy(s32[800]{0:T(1024)} %a)"
    assert parse_op(conv) == ("convolution_compare_fusion.2", "fusion")
    assert parse_op(loop) == ("while", "while")
    assert parse_op(copy) == ("copy.1", "copy")
    assert is_contraction("convolution_compare_fusion.2")
    assert is_contraction("bitmm_pallas.6") and is_contraction("dot.3")
    assert not is_contraction("and_reduce_fusion")


def test_breakdown_keeps_the_ten_largest():
    dev = DeviceTrace(
        window_s=2.0, busy_s=0.5,
        op_seconds={f"op{k}": k / 100 for k in range(15)},
        idle_gaps=[("serve loop", k / 10) for k in range(12)],
    )
    out = dev.breakdown()
    assert [n for n, _ in out["device_ops"]][:2] == ["op14", "op13"]
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert dev.idle_share == 0.75


def recorded(tmp_path):
    """The committed trace, where the profiler would have written it: a
    1 s traced window of the relational rw cell cut to n = 1,024, on one
    TPU v5e."""
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(DATA / "rel_small.xplane.pb", where)
    return reduce_trace(tmp_path)


def test_recorded_trace_reduces_to_known_numbers(tmp_path):
    dev = recorded(tmp_path)
    assert dev.n_ops == 837
    assert dev.busy_s == pytest.approx(0.002678012, rel=1e-9)
    assert dev.contraction_s == pytest.approx(0.000561579, rel=1e-9)
    assert dev.window_s == pytest.approx(0.97482629, rel=1e-9)
    assert 0 < dev.idle_share < 1
    out = dev.breakdown()
    assert out["device_ops"][0][0] == "bitmm_pallas.6"  # the Pallas product
    assert not any(n.startswith("while") for n, _ in out["device_ops"])
    assert out["idle_gaps"][0] == ["serve loop", pytest.approx(0.490044253)]
    assert {n for n, _ in out["idle_gaps"]} <= {
        "engine read call", "engine write call", "serve loop"}


def test_recorded_trace_through_the_metric_readers(tmp_path):
    from bench.harness import Run, metric_reader

    run = Run(reads=[], writes=[], window_s=3.0, setup_s=1.0,
              compiles_in_window=0, device=recorded(tmp_path))
    idle = metric_reader("device_idle_share.rw")(run)
    share = metric_reader("contraction_share.rw")(run)
    assert idle == pytest.approx(100 * (1 - 0.002678012 / 0.97482629))
    assert share == pytest.approx(100 * 0.000561579 / 0.002678012)
    assert 0 < share <= 100
