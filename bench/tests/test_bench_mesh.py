"""A cell on more than one chip runs its engine on a mesh of exactly its
chips: ``load_cell`` refuses a layout that cannot, a configuration
without a mesh builds an engine without one, and a tiny mesh cell runs
through ``run_cell`` on four host CPU devices, correct as it is and not
correct with each fault of ``bench/faults.py`` planted (in a process of
its own, since the device count is fixed when the backend starts)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench.faults import FAULTS
from bench.harness import ROOT, SetupError, build_engine, load_cell, plugin
from bench.tests.tiny import mesh_workloads, tiny_root

#: the cell copied onto a mesh, and its configuration
BASE_CELL, BASE_CONFIG = "rel16k-rw", "onto-q2-rel-16k"


def add_cell(root: Path, name: str, chips: int, **engine) -> None:
    """Add cell ``name`` to the tiny benchmark at ``root``: the base
    cell on ``chips`` chips, its configuration's ``engine`` block updated
    with ``engine``, reporting every metric the base cell reports."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    (conf,) = [c for c in bench["configs"] if c["name"] == BASE_CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["name"] == BASE_CELL]
    cfg = json.loads(Path(conf["file"]).read_text())
    cfg["engine"].update(engine)
    cfg_path = root / f"{name}.json"
    cfg_path.write_text(json.dumps(cfg))
    bench["configs"].append(dict(conf, name=name, file=str(cfg_path)))
    bench["workloads"].append(dict(cell, name=name, config=name, chips=chips))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if BASE_CELL in m.get("workloads", ()):
            m["workloads"].append(name)
    path.write_text(json.dumps(bench))


@pytest.mark.parametrize("chips,engine,refusal", [
    (4, {}, "names no mesh"),
    (4, {"engine": "opt", "mesh": [2, 1]}, "not a \\[data, model\\] shape"),
    (4, {"engine": "dense", "mesh": [2, 2]}, "needs the engine 'opt'"),
], ids=["chips_without_mesh", "mesh_not_chips", "mesh_beside_dense"])
def test_load_refuses_a_layout_off_the_cells_chips(tmp_path, chips, engine,
                                                   refusal):
    root = tiny_root(tmp_path)
    add_cell(root, "odd-layout", chips, **engine)
    with pytest.raises(SetupError, match=f"cell 'odd-layout'.*{refusal}"):
        load_cell("odd-layout", root)


def test_config_without_mesh_builds_an_unsharded_engine(tmp_path):
    cell = load_cell(BASE_CELL, tiny_root(tmp_path))
    assert "mesh" not in cell.config["engine"] and cell.chips == 1
    graph = plugin("graphs", cell.config["generator"]).build(cell.config, 5)
    engine = build_engine(cell.config, graph, jax.devices()[:1])
    assert engine.mesh is None


#: run in a fresh interpreter on four host devices: the cell, with the
#: fault named (if any) planted, and the engine the harness builds
_DRIVE = """
import json, sys
from pathlib import Path
import jax
from bench import harness

built = []
build_engine = harness.build_engine
def recording(*a):
    built.append(build_engine(*a))
    return built[-1]
harness.build_engine = recording
line = harness.run_cell(sys.argv[2], 2**31 + 99, 2.0, False, need_tpu=False,
                        root=Path(sys.argv[1]), fault=sys.argv[3] or None,
                        log=lambda msg: None)
eng = built[0]
line["mesh"] = eng.mesh and {
    "shape": dict(eng.mesh.shape),
    "ids": sorted(d.id for d in eng.mesh.devices.flat),
    "cell_ids": [d.id for d in jax.devices()[:4]],
    "sharded": sorted({k.engine for k in eng.plans._exe if k.mesh})}
print(json.dumps(line))
"""

#: a tiny copy of the base cell on a 2x2 mesh, beside the benchmark's own
#: mesh cells
MESH_CELLS = ["rel16k-rw-2x2", *mesh_workloads()]


def drive_on_four(tmp_path, name, fault=None) -> dict:
    """The result line of cell ``name`` run on four host devices, with
    the engine's mesh under ``"mesh"`` (``None`` where it has none)."""
    root = tiny_root(tmp_path)
    if name == MESH_CELLS[0]:
        add_cell(root, name, 4, engine="opt", mesh=[2, 2])
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"),
    )
    out = subprocess.run(
        [sys.executable, "-c", _DRIVE, str(root), name, fault or ""],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def assert_sound(line: dict) -> None:
    assert line["correct"], line["checks"]
    assert line["checks"] == {"wrong": {"value": 0, "limit": 0}}
    assert line["failed"] == 0 and line["window"]["writes"] > 0


@pytest.mark.parametrize("name", MESH_CELLS)
def test_mesh_cell_runs_on_four_host_devices(tmp_path, name):
    line = drive_on_four(tmp_path, name)
    assert_sound(line)
    assert line["device"]["count"] == 4
    mesh = line["mesh"]
    assert mesh["shape"] == {"data": 2, "model": 2}
    assert mesh["ids"] == sorted(mesh["cell_ids"])
    # the reads' closures ran the sharded executable
    assert mesh["sharded"] == ["opt"]


def test_one_chip_cell_takes_one_of_four_devices(tmp_path):
    line = drive_on_four(tmp_path, BASE_CELL)
    assert_sound(line)
    assert line["device"]["count"] == 1 and line["mesh"] is None


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", MESH_CELLS)
def test_fault_turns_mesh_cell_incorrect(tmp_path, name, fault):
    line = drive_on_four(tmp_path, name, fault)
    assert line["device"]["count"] == 4
    assert not line["correct"]
    assert line["window"]["wrong_parts"]["answers"] > 0
