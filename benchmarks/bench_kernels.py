"""Kernel-level microbench: bitpacked Boolean matmul vs the dense
f32-saturation oracle (CPU wall time for the jnp paths; the Pallas TPU
program itself is validated in interpret mode and characterized analytically
in EXPERIMENTS.md §Roofline since this container has no TPU)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.matrices import pack_bits
from repro.kernels import ref


def _time(fn, reps=3):
    fn()  # warm/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def main(rows: list[str] | None = None) -> list[str]:
    rows = rows if rows is not None else []
    rows.append("kernel,n,density,us_per_call,derived_GB_touched")
    rng = np.random.default_rng(0)
    for n in (512, 1024, 2048):
        for density in (0.01, 0.1):
            dense = jnp.asarray(rng.random((1, n, n)) < density)
            packed = pack_bits(dense)
            t_ref = _time(lambda: ref.bitmm_ref(packed, packed))
            packed_bytes = 3 * packed.size * 4 / 1e9
            rows.append(
                f"bitmm_ref,{n},{density},{t_ref*1e6:.0f},{packed_bytes:.4f}"
            )
            f = jnp.asarray(dense, jnp.float32)
            t_dense = _time(
                lambda: (jnp.einsum("bik,bkj->bij", f, f) > 0)
            )
            rows.append(
                f"dense_f32,{n},{density},{t_dense*1e6:.0f},"
                f"{3*f.size*4/1e9:.4f}"
            )
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("\n".join(main()))
