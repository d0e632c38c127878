"""``chip_smoke.py`` off the chip: it refuses any platform but the TPU,
and its phases — the same serving, delta and reference checks it makes on
the chip — pass at a tiny size on the CPU (kernels in interpret mode)."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def meter(smoke):
    return smoke.CompileMeter()


def test_refuses_cpu_and_names_the_platform(smoke, capsys):
    with pytest.raises(SystemExit, match="platform is 'cpu'"):
        smoke.main([])
    assert capsys.readouterr().out == ""  # no result line


def test_relational_phase_every_engine_with_delta(smoke, meter, capsys):
    smoke.run_relational(
        lambda: smoke.padded_ontology(60, 150, 256),
        smoke.ENGINES, jax.devices()[0], meter, n_batches=2, batch=4,
    )
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == len(smoke.ENGINES)
    for line, engine in zip(lines, smoke.ENGINES):
        assert f"engine={engine} " in line
        assert "failed=0 shed=0" in line
        assert "repair_executables=" in line


@pytest.mark.parametrize("phase", ["single_path", "conjunctive", "count"])
def test_small_phases(smoke, meter, capsys, phase):
    getattr(smoke, f"run_{phase}")(
        lambda: smoke.padded_ontology(40, 80, 128),
        jax.devices()[0], meter, batch=4,
    )
    assert f"phase={phase}" in capsys.readouterr().out


def test_padding_below_the_graph_is_refused(smoke):
    with pytest.raises(ValueError, match="nodes"):
        smoke.padded_ontology(40, 100, 128)


def test_sharded_phase_on_four_host_devices():
    """The ``--chips 4`` phases on four CPU host devices (the device
    count is fixed at backend start, so in a fresh interpreter)."""
    code = (
        "import jax, importlib.util as u\n"
        f"spec = u.spec_from_file_location('chip_smoke', {str(_PATH)!r})\n"
        "cs = u.module_from_spec(spec); spec.loader.exec_module(cs)\n"
        "meter, devs = cs.CompileMeter(), jax.devices()[:4]\n"
        "cs.run_sharded(lambda: cs.padded_ontology(60, 150, 256),\n"
        "               'relational', devs, meter)\n"
        "cs.run_sharded(lambda: cs.padded_ontology(40, 80, 128),\n"
        "               'single_path', devs, meter)\n"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=str(_PATH.parent / "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    for semantics in ("relational", "single_path"):
        assert f"phase=sharded_{semantics} equal_answers=true" in out.stdout
    assert "engine=opt+mesh" in out.stdout and "state_devices=4" in out.stdout
