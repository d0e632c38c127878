"""Jitted public wrappers around the Pallas kernels.

``bitmm`` picks legal tile sizes for the input shape.  Which program runs
is decided when the caller is lowered, for the platform it is lowered for
(``jax.lax.platform_dependent``), never at import: the TPU gets the
compiled Mosaic kernel; any other backend runs the same kernel body in
Pallas interpret mode (one Python step per grid element, which validates
the exact TPU program), or the jnp oracle where interpret mode is too slow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitmm import bitmm_pallas
from . import ref as _ref

#: Above this many packed words per matrix the interpret-mode kernel is too
#: slow to be useful off the TPU; use the jnp oracle instead (the TPU
#: program is still exercised by the kernel test sweep).
_INTERPRET_ELEMS_BUDGET = 1 << 22

#: Interpret mode runs one Python step per grid element — for the
#: block-sparse engine that is one step per *pair*, unpayable inside a
#: fixpoint loop.  Off the TPU, batches above this size use the jnp
#: oracle; the Pallas tile program is still exercised by small batches and
#: the kernel test sweep.
_TILE_INTERPRET_PAIRS_BUDGET = 16


def _pick_tiles(m: int, k: int, w: int) -> tuple[int, int, int]:
    """(ti, tw, tk) meeting Mosaic's block rules — each of a block's last
    two dims is a multiple of (8, 128) or the whole dim — with VMEM blocks
    bounded whatever the size: a dim above its tile gets a ragged grid
    (bitmm.py handles the edge blocks) rather than a whole-dim block."""
    ti = min(m, 128)
    tw = min(w, 128)
    tk = min(k, 4096)
    return ti, tw, tk


def _kernel_or_fallback(lhs, rhs, use_oracle: bool):
    ti, tw, tk = _pick_tiles(lhs.shape[1], rhs.shape[1], rhs.shape[2])
    kernel = functools.partial(bitmm_pallas, ti=ti, tw=tw, tk=tk)
    if use_oracle:
        fallback = _ref.bitmm_ref
    else:
        fallback = functools.partial(kernel, interpret=True)
    return jax.lax.platform_dependent(lhs, rhs, tpu=kernel, default=fallback)


def bitmm(lhs_packed: jnp.ndarray, rhs_packed: jnp.ndarray) -> jnp.ndarray:
    """Bitpacked Boolean matmul: (B, m, k//32) x (B, k, w) -> (B, m, w).

    ``m`` may differ from ``k`` (the masked closure contracts a compacted
    block of active rows against the full packed state)."""
    B, m, _ = lhs_packed.shape
    k, w = rhs_packed.shape[-2:]
    return _kernel_or_fallback(
        lhs_packed, rhs_packed, B * max(m, k) * w > _INTERPRET_ELEMS_BUDGET
    )


def tile_bitmm(lhs_tiles: jnp.ndarray, rhs_tiles: jnp.ndarray) -> jnp.ndarray:
    """Square-tile bitpacked Boolean matmul for the block-sparse engine:
    (p, B, B//32) x (p, B, B//32) -> (p, B, B//32), one independent B×B
    product per occupied block pair (the pair axis rides the Pallas grid's
    batch dimension)."""
    return _kernel_or_fallback(
        lhs_tiles, rhs_tiles, lhs_tiles.shape[0] > _TILE_INTERPRET_PAIRS_BUDGET
    )
