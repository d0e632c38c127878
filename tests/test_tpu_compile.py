"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed even where no TPU is: it compiles for a
``v5e:2x2`` topology that is described, not attached.  That refuses what
interpret mode accepts — a Mosaic construct the TPU cannot lower, a block
that breaks the tiling rules, a program over the chip's memory — so these
tests guard the kernels and closures at served widths without a chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  Compiles happen in the test's own process.  Nothing
runs; results are checked by the interpret-mode tests elsewhere.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.closure import masked_closure, masked_opt_closure
from repro.core.grammar import query2_grammar
from repro.core.matrices import ProductionTables
from repro.delta.repair import row_surgery
from repro.kernels import ops
from repro.kernels.bitmm import bitmm_or_pallas, bitmm_pallas
from repro.shard import MeshPlan, make_mesh

GiB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it while this file runs
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def q2_tables():
    return ProductionTables.from_grammar(query2_grammar().to_cnf())


def _words(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


# served widths: the masked bitpacked closure's (P, R, w) x (P, n, w)
# contraction at n = 16,384, the same at n = 16,000 (a ragged grid on
# every axis), and an all-pairs block
@pytest.mark.parametrize(
    "b,m,n", [(4, 128, 16384), (4, 256, 16000), (1, 4096, 4096)]
)
def test_bitmm_pallas_compiles_for_v5e(one_chip, b, m, n):
    ti, tw, tk = ops._pick_tiles(m, n, n // 32)
    lhs = _words(one_chip, b, m, n // 32)
    rhs = _words(one_chip, b, n, n // 32)
    compiled = jax.jit(
        lambda x, y: bitmm_pallas(x, y, ti=ti, tw=tw, tk=tk)
    ).lower(lhs, rhs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bitmm_or_pallas_compiles_for_v5e(one_chip):
    n = 4096
    x = _words(one_chip, 2, n, n // 32)
    compiled = jax.jit(
        lambda a, b, c: bitmm_or_pallas(a, b, c, ti=128, tw=128, tk=4096)
    ).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tile", [32, 128])
def test_tile_bitmm_compiles_for_v5e(one_chip, tile):
    """The public wrapper picks the compiled kernel when lowered for the
    TPU (the block-sparse engine's tile product, 64 pairs)."""
    x = _words(one_chip, 64, tile, tile // 32)
    compiled = jax.jit(ops.tile_bitmm).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_masked_closure_compiles_for_v5e(one_chip, q2_tables):
    n = 4096
    T = jax.ShapeDtypeStruct((q2_tables.n_nonterms, n, n), jnp.bool_,
                             sharding=one_chip)
    m = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    lowered = masked_closure.lower(T, q2_tables, m, row_capacity=1024)
    # lowered for the TPU, the Boolean products feed the MXU in bf16
    dots = [ln for ln in lowered.as_text().splitlines() if "dot_general" in ln]
    assert dots and all("xbf16>" in ln for ln in dots)
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * GiB


# the benchmark's two cached states and their deletes' entry buckets:
# (5, 16384, 16384) bool with ~12,000 entries, (5, 4096, 4096) f32 lengths
@pytest.mark.parametrize(
    "dtype,n,bucket", [(jnp.bool_, 16384, 16384), (jnp.float32, 4096, 4096)]
)
def test_row_surgery_compiles_for_v5e(one_chip, dtype, n, bucket):
    """The write's device row surgery compiles at served sizes, and the
    donated state is aliased to its output."""
    state = jax.ShapeDtypeStruct((5, n, n), dtype, sharding=one_chip)
    evict = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    entries = jax.ShapeDtypeStruct((3, bucket), jnp.int32, sharding=one_chip)
    compiled = row_surgery.lower(state, evict, entries).compile()
    mem = compiled.memory_analysis()
    nbytes = 5 * n * n * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes == nbytes
    assert mem.temp_size_in_bytes < 16 * GiB - 2 * nbytes


def test_masked_opt_closure_compiles_on_v5e_2x2_mesh(topo, q2_tables):
    n = 4096
    mesh = make_mesh((2, 2), devices=topo.devices)
    state = NamedSharding(mesh, P(None, "data", "model"))
    T = jax.ShapeDtypeStruct((q2_tables.n_nonterms, n, n), jnp.bool_,
                             sharding=state)
    m = jax.ShapeDtypeStruct((n,), jnp.bool_,
                             sharding=NamedSharding(mesh, P()))
    with mesh:
        compiled = masked_opt_closure.lower(
            T, q2_tables, m, row_capacity=1024,
            plan=MeshPlan.from_mesh(mesh),
        ).compile()
    assert "all-gather" in compiled.as_text()  # packed operand exchange
