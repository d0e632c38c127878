"""Each fault the cells can have, planted under the timed path of a tiny
run on the CPU, turns ``correct`` false.  ``stale`` is also the control
that the chip runs at each cell's own size (``--control stale``)."""
from __future__ import annotations

import pytest

from bench.faults import FAULTS
from bench.tests.test_bench_drive import drive, restore_jax_config  # noqa: F401
from bench.tests.tiny import workloads


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", workloads())
def test_fault_turns_correct_false(tmp_path, restore_jax_config, name,
                                   fault):
    line = drive(tmp_path, name, fault=fault)
    assert not line["correct"]
    assert line["checks"]["wrong"]["value"] > 0
    assert line["window"]["wrong_parts"]["answers"] > 0
