"""The benchmark finds every piece by name, and ``BENCHMARK.json`` keeps
to its schema."""
from __future__ import annotations

import json
import re

import pytest

from bench.harness import (
    BENCH, ROOT, Run, SetupError, load_cell, metric_reader, peaks_of, plugin,
)
from bench.tests.tiny import mesh_workloads, workloads

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("name", workloads() + mesh_workloads())
def test_cell_files_resolve(name):
    cell = load_cell(name)
    (w,) = [w for w in SPEC["workloads"] if w["name"] == name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and cell.chips == w["chips"]
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert "setup_s" in cell.metrics and len(cell.metrics) >= 2
    assert cell.layer_metrics, "every cell reports a per-layer metric"
    for m in cell.layer_metrics.values():
        assert m["moves"] in cell.metrics


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    body = json.loads((ROOT / conf["file"]).read_text())
    assert body["name"] == conf["name"]
    assert set(conf["reduced"]) <= set(body) and len(conf["reduced"]) <= 16
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


#: what each kind of generator file found by name must define
PLUGIN_API = {
    "graphs": ("build",),
    "compare": ("compare",),
    "loops": ("schedule", "warmup", "drive"),
    "writes": ("triples", "warmup_triple"),
}


def named_plugins():
    """(kind, name) of every generator file that a configuration or a
    mix of ``BENCHMARK.json`` names."""
    out = set()
    for conf in SPEC["configs"]:
        body = json.loads((ROOT / conf["file"]).read_text())
        out |= {("graphs", body["generator"]), ("compare", body["semantics"])}
    for w in SPEC["workloads"]:
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        out.add(("loops", mix["loop"]))
        if "writes" in mix:
            out.add(("writes", mix["writes"]))
    return sorted(out)


@pytest.mark.parametrize("kind,name", named_plugins(),
                         ids=lambda x: str(x))
def test_named_generators_are_found(kind, name):
    mod = plugin(kind, name)
    assert (BENCH / kind / f"{name}.py").is_file()
    for fn in PLUGIN_API[kind]:
        assert callable(getattr(mod, fn)), (kind, name, fn)
    assert plugin(kind, name) is mod  # loaded once


def test_unknown_generator_is_refused():
    with pytest.raises(SetupError, match="no loops file"):
        plugin("loops", "no-such-loop")


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
    empty = Run(reads=[], writes=[], window_s=1.0, setup_s=1.0,
                compiles_in_window=0)
    value = metric_reader(metric["name"])(empty)
    assert value is None or isinstance(value, (int, float))


def test_names_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SetupError, match="no peaks"):
        peaks_of("TPU v9 imaginary")
