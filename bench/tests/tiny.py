"""A small copy of the benchmark for tests on the CPU: the same cells,
mixes and metrics, with each configuration cut to a few hundred nodes,
the engine pinned to the dense backend (to ``opt``, the one that
shards, where the configuration names a mesh), and a write every few
reads."""
from __future__ import annotations

import json
from pathlib import Path

from bench.harness import ROOT


def tiny_root(tmp: Path, rate: float = 40.0, write_every: int = 5) -> Path:
    """A root holding a ``BENCHMARK.json`` whose cells run at tiny size."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for conf in bench["configs"]:
        cfg = json.loads((ROOT / conf["file"]).read_text())
        cfg.update(n_classes=60, n_instances=150, n_nodes=256)
        cfg["engine"].update(
            engine="opt" if "mesh" in cfg["engine"] else "dense",
            row_capacity=64,
        )
        path = tmp / f"{conf['name']}.json"
        path.write_text(json.dumps(cfg))
        conf["file"] = str(path)
    for w in bench["workloads"]:
        src = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(src.read_text())
        mix.update(rate_per_s=rate, write_every=write_every)
        dst = tmp / "bench" / "traffic" / f"{w['traffic']}.json"
        dst.write_text(json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def _cells() -> list[dict]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]


def workloads() -> list[str]:
    """The one-chip cells: they run in-process, on the one CPU device
    the tests see."""
    return [w["name"] for w in _cells() if w["chips"] == 1]


def mesh_workloads() -> list[str]:
    """The cells on more than one chip, whose engines run on a mesh: they
    run in a process of their own, on as many host CPU devices."""
    return [w["name"] for w in _cells() if w["chips"] > 1]
