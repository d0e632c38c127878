"""Multi-device correctness, run in a subprocess with 8 host-platform
devices (tests in the main process must keep seeing 1 device).

The CFPQ closure matrix — (relational | single_path) x (all-pairs |
masked) — is parametrized so a regression in any one combination on a
mesh fails as its own test instead of hiding behind the first assert of
a monolithic driver.  The in-process (and far larger) differential suite
for the masked opt engines is tests/test_distributed_masked.py, which the
dedicated multi-device CI lane runs with 8 host devices.
"""
import os
import subprocess
import sys

import pytest

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8, jax.devices()

from repro.core import closure
from repro.core.grammar import query1_grammar
from repro.core.graph import ontology_graph
from repro.core.matrices import ProductionTables, init_matrix
from repro.core.semantics import (
    base_lengths,
    masked_single_path_closure,
    masked_opt_single_path_closure,
    single_path_closure,
)
from repro.launch.mesh import make_test_mesh
from repro.shard.plans import MeshPlan

g = query1_grammar().to_cnf()
graph = ontology_graph(40, 90, seed=7)
tables = ProductionTables.from_grammar(g)
T0 = init_matrix(graph, g)
n = T0.shape[-1]
ref = np.asarray(closure.dense_closure(T0, tables))
"""

#: per-(semantics, masked) driver bodies; each asserts sharded == the
#: single-device reference on a 4x2 mesh (plus 2x1 for the masked opt
#: engines, whose row sharding is the tentpole contract)
_CLOSURE_BODIES = {
    # all-pairs Boolean: the generic GSPMD engines AND the packed-exchange
    # opt engine must reproduce the dense single-device closure
    ("relational", False): r"""
mesh = make_test_mesh(4, 2)
spec = NamedSharding(mesh, P(None, "data", "model"))
T0_sharded = jax.device_put(T0, spec)
with mesh:
    dist = jax.jit(
        lambda t: closure.dense_closure(t, tables),
        in_shardings=spec,
        out_shardings=spec,
    )(T0_sharded)
np.testing.assert_array_equal(np.asarray(dist), ref)
print("distributed closure OK")

with mesh:
    distf = jax.jit(
        lambda t: closure.frontier_closure(t, tables),
        in_shardings=spec,
        out_shardings=spec,
    )(T0_sharded)
np.testing.assert_array_equal(np.asarray(distf), ref)
print("distributed frontier closure OK")

plan = MeshPlan.from_mesh(mesh)
with mesh:
    disto = closure.opt_closure(T0, tables, plan=plan)
np.testing.assert_array_equal(np.asarray(disto), ref)
print("distributed opt closure OK")
""",
    # masked Boolean: the sharded opt engine's rows under its mask are
    # bit-identical to the single-device masked closure's
    ("relational", True): r"""
src = np.zeros(n, bool)
src[[0, 5, 17]] = True
refT, refM, ovf, _ = closure.masked_closure(
    T0, tables, jnp.asarray(src), row_capacity=n
)
assert not bool(ovf)
refT, refM = np.asarray(refT), np.asarray(refM)
np.testing.assert_array_equal(refT[:, refM, :], ref[:, refM, :])
for shape in [(2, 1), (4, 2)]:
    mesh = make_test_mesh(*shape)
    plan = MeshPlan.from_mesh(mesh)
    with mesh:
        T, M, ovf, _ = closure.masked_opt_closure(
            T0, tables, jnp.asarray(src), row_capacity=n, plan=plan
        )
    assert not bool(ovf)
    np.testing.assert_array_equal(np.asarray(M), refM)
    np.testing.assert_array_equal(np.asarray(T)[:, refM, :], refT[:, refM, :])
    print(f"distributed masked opt closure OK {shape}")
""",
    # all-pairs single-path: the Section 5 closure under GSPMD sharding
    # reproduces the single-device lengths bit-for-bit (deterministic
    # discovery order; f32 sums of small ints are exact)
    ("single_path", False): r"""
refT2, refL = single_path_closure(T0, tables)
refT2, refL = np.asarray(refT2), np.asarray(refL)
np.testing.assert_array_equal(refT2, ref)
mesh = make_test_mesh(4, 2)
spec = NamedSharding(mesh, P(None, "data", "model"))
T0_sharded = jax.device_put(T0, spec)
with mesh:
    dT, dL = jax.jit(
        lambda t: single_path_closure(t, tables),
        in_shardings=spec,
        out_shardings=(spec, spec),
    )(T0_sharded)
np.testing.assert_array_equal(np.asarray(dT), refT2)
np.testing.assert_array_equal(np.asarray(dL), refL)
print("distributed single-path closure OK")
""",
    # masked single-path: sharded opt lengths — support matches the
    # Boolean masked rows, finite entries stay frozen across mesh shapes
    ("single_path", True): r"""
src = np.zeros(n, bool)
src[[0, 5, 17]] = True
refT, refM, _, _ = closure.masked_closure(
    T0, tables, jnp.asarray(src), row_capacity=n
)
refT, refM = np.asarray(refT), np.asarray(refM)
refL, refML, ovf, _ = masked_single_path_closure(
    base_lengths(T0), tables, jnp.asarray(src), row_capacity=n
)
assert not bool(ovf)
for shape in [(2, 1), (4, 2)]:
    mesh = make_test_mesh(*shape)
    plan = MeshPlan.from_mesh(mesh)
    with mesh:
        L, M, ovf, _ = masked_opt_single_path_closure(
            base_lengths(T0), tables, jnp.asarray(src),
            row_capacity=n, plan=plan,
        )
    assert not bool(ovf)
    L, M = np.asarray(L), np.asarray(M)
    np.testing.assert_array_equal(M, refM)
    np.testing.assert_array_equal(np.isfinite(L)[:, M, :], refT[:, M, :])
    print(f"distributed masked opt single-path OK {shape}")
""",
}


@pytest.mark.slow
@pytest.mark.parametrize("semantics", ["relational", "single_path"])
@pytest.mark.parametrize("masked", [False, True], ids=["allpairs", "masked"])
def test_distributed_closure(semantics, masked):
    driver = (
        _PRELUDE
        + _CLOSURE_BODIES[(semantics, masked)]
        + "\nprint('CLOSURE CASE PASSED')\n"
    )
    _run_driver(driver, "CLOSURE CASE PASSED")


DRIVER = _PRELUDE + r"""
mesh = make_test_mesh(4, 2)

# ------------------------------------------------------------------ #
# 2. Distributed LM train step: sharded == replicated result
# ------------------------------------------------------------------ #
from repro.configs import registry
from repro.configs.reduce import reduce_config
from repro.models import transformer as tf
from repro.shard.plans import MeshPlan
from repro.train import data, optimizer as opt, trainer
import dataclasses

cfg = dataclasses.replace(
    reduce_config(registry.get_config("internlm2-20b")), dtype="float32"
)
opt_cfg = opt.OptimizerConfig()
params = tf.init_params(jax.random.PRNGKey(0), cfg)
state = opt.init_opt_state(params, opt_cfg)
batch = data.lm_batch(cfg, batch=8, seq=32, step=0)

plain = trainer.make_train_step(cfg, opt_cfg)
p_ref, _, m_ref = jax.jit(plain)(params, state, batch)

plan = MeshPlan.from_mesh(mesh)
pspecs = tf.param_specs(cfg, plan)
ospecs = opt.opt_state_specs(pspecs, opt_cfg)
bspec = {k: P("data", None) for k in batch}
ns = lambda t: jax.tree.map(
    lambda s: NamedSharding(mesh, s), t,
    is_leaf=lambda x: isinstance(x, P) or x is None,
)
step = trainer.make_train_step(cfg, opt_cfg, plan=plan)
with mesh:
    p_dist, _, m_dist = jax.jit(
        step,
        in_shardings=(ns(pspecs), ns(ospecs), ns(bspec)),
        out_shardings=(ns(pspecs), ns(ospecs), None),
    )(params, state, batch)
np.testing.assert_allclose(
    float(m_ref["loss"]), float(m_dist["loss"]), rtol=1e-5
)
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_dist)):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5
    )
print("distributed train step OK")

# ------------------------------------------------------------------ #
# 3. int8-compressed gradient all-reduce with error feedback
# ------------------------------------------------------------------ #
from repro.train.compression import make_compressed_allreduce

mesh1d = jax.make_mesh((8,), ("data",))
reduce_fn = make_compressed_allreduce(mesh1d, "data")
rng = np.random.default_rng(0)
g_stacked = {"w": jnp.asarray(rng.normal(size=(8, 64, 32)), jnp.float32)}
err = {"w": jnp.zeros((8, 64, 32), jnp.float32)}
g_hat, err = reduce_fn(g_stacked, err)
exact = np.asarray(g_stacked["w"]).mean(axis=0)
# single-shot error bounded by the int8 step size of the largest |v|
bound = np.abs(np.asarray(g_stacked["w"])).max() / 127
assert np.abs(np.asarray(g_hat["w"]) - exact).max() <= bound + 1e-6
# error feedback: repeated reduction of the SAME grads converges to exact
acc = np.zeros_like(exact)
err = {"w": jnp.zeros((8, 64, 32), jnp.float32)}
for i in range(30):
    g_hat, err = reduce_fn(g_stacked, err)
    acc += np.asarray(g_hat["w"])
np.testing.assert_allclose(acc / 30, exact, atol=bound / 10)
print("compressed allreduce OK")

# ------------------------------------------------------------------ #
# 4. Elastic checkpoint: save under one mesh, restore under another
# ------------------------------------------------------------------ #
import tempfile
from repro.train import checkpoint as ckpt

with tempfile.TemporaryDirectory() as d:
    ckpt.save(d, 1, {"params": p_dist})
    mesh2 = make_test_mesh(2, 4)  # different layout
    pspecs2 = tf.param_specs(cfg, MeshPlan.from_mesh(mesh2))
    ns2 = jax.tree.map(
        lambda s: NamedSharding(mesh2, s), pspecs2,
        is_leaf=lambda x: isinstance(x, P) or x is None,
    )
    tree, meta = ckpt.restore(
        os.path.join(d, "step_00000001"),
        {"params": params},
        {"params": ns2},
    )
    for a, b in zip(jax.tree.leaves(tree["params"]), jax.tree.leaves(p_dist)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("elastic checkpoint OK")
print("ALL DISTRIBUTED TESTS PASSED")
"""


def _run_driver(driver: str, sentinel: str) -> None:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", driver],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert sentinel in proc.stdout


@pytest.mark.slow
def test_distributed_suite():
    _run_driver(DRIVER, "ALL DISTRIBUTED TESTS PASSED")
