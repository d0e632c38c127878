"""A tiny in-process drive of each cell's mix through the harness, on the
CPU, with the references agreeing; the result line's schema; and the
command's refusal where there is no TPU or no program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench.harness import ROOT, run_cell
from bench.tests.tiny import tiny_root, workloads


@pytest.fixture
def restore_jax_config():
    """run_cell turns the persistent compile cache on for its process;
    give the worker its settings back."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def drive(tmp_path, name, **kw):
    return run_cell(name, 2**31 + 99, 2.0, kw.pop("trace", False),
                    need_tpu=False, root=tiny_root(tmp_path),
                    log=lambda msg: None, **kw)


@pytest.mark.parametrize("name", workloads())
def test_tiny_drive_is_correct(tmp_path, restore_jax_config, name):
    line = drive(tmp_path, name)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["window"]["writes"] > 0
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"wrong": {"value": 0, "limit": 0}}
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["count"] == 1
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_tiny_traced_drive_reads_layers(tmp_path, restore_jax_config):
    name = workloads()[0]
    line = drive(tmp_path, name, trace=True)
    assert line["correct"], line["checks"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    span_metrics = {m["name"] for m in spec["per_layer"]
                    if m["source"] != "device_trace"}
    # the CPU has no device plane: only the device-trace metrics are
    # left out, as a reader that finds nothing returns nothing
    assert span_metrics <= set(line["metrics"])


def run_command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workloads()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_refuses_a_platform_other_than_tpu():
    out = run_command(ROOT, {})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = run_command(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
