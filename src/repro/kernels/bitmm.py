"""Pallas TPU kernel: bitpacked Boolean matrix multiplication.

Semiring: C[b, i, jw] = OR_{k in [n] : bit_k(lhs[b, i])} rhs[b, k, jw]
with every matrix stored as uint32 words packing 32 columns.

TPU mapping (DESIGN.md §3): this is the adaptation of the paper's CSR/
CUSPARSE sparse path.  TPUs have no sparse GEMM, so sparsity is exploited as
*density of representation*: 1 bit per Boolean entry means 32x less HBM
traffic than f32 and 8x less than u8, which is what matters in the
memory-bound closure regime.  The kernel runs on the VPU (bitwise AND/OR on
(8,128) vregs); the compute-bound regime is instead served by the MXU
saturation path in core/closure.py.

Tiling: grid (B, ceil(m/TI), ceil(w/TW), ceil(k/TK)); each step loads
  lhs block (TI, TK/32)   — contraction bits for TI rows,
  rhs block (TK, TW)      — TK packed rows,
and accumulates an OR into the resident out block (TI, TW).  The k axis is
the innermost grid dim so the output block stays in VMEM across the whole
contraction (standard Pallas accumulation pattern).  Tiles need not divide
the array: edge blocks of a ragged grid read undefined words past the end,
the kernel zeroes contraction words past ``k``, and writes past ``m``/``w``
are dropped — so the VMEM block stays bounded whatever the matrix size.

The body walks the block one contraction *word* at a time (a rolled loop)
and the word's 32 bits statically.  Mosaic lowers neither a partial
unroll of that loop nor a dynamic lane index into a loaded value, so the
word is picked out by a one-hot lane select and a lane reduction, and the
rhs rows come from an aligned dynamic sublane slice of the ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _or_contract(lhs_ref, rhs_ref, acc, *, tk: int, k_words: int):
    """``acc | (lhs block x rhs block)`` on int32 words: OR into ``acc``
    (TI, TW) every rhs row whose contraction bit is set in the lhs block."""
    n_words = tk // 32
    lane = jax.lax.broadcasted_iota(jnp.int32, (acc.shape[0], n_words), 1)
    # the last contraction block of a ragged grid runs past the array: its
    # out-of-range words are undefined and are zeroed here
    first = pl.program_id(3) * n_words
    ragged = k_words % n_words != 0

    def word_body(w, acc):
        lhs = jax.lax.bitcast_convert_type(lhs_ref[0], jnp.int32)
        word = jnp.sum(jnp.where(lane == w, lhs, 0), axis=1, keepdims=True)
        if ragged:
            word = jnp.where(first + w < k_words, word, 0)
        word = jnp.broadcast_to(word, acc.shape)
        # the rhs rows of this word's 32 contraction columns
        rows = jax.lax.bitcast_convert_type(
            rhs_ref[0, pl.ds(pl.multiple_of(w * 32, 32), 32), :], jnp.int32
        )
        for b in range(32):
            mask = (word << (31 - b)) >> 31  # all-ones where bit b is set
            acc = acc | (mask & rows[b : b + 1, :])
        return acc

    return jax.lax.fori_loop(0, n_words, word_body, acc)


def _bitmm_kernel(lhs_ref, rhs_ref, out_ref, *, tk: int, k_words: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jax.lax.bitcast_convert_type(out_ref[0], jnp.int32)
    acc = _or_contract(lhs_ref, rhs_ref, acc, tk=tk, k_words=k_words)
    out_ref[0] = jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _bitmm_or_kernel(lhs_ref, rhs_ref, acc_ref, out_ref, *, tk: int,
                     k_words: int):
    """Fused C = acc | (lhs x rhs): the closure-step epilogue folded into
    the contraction — the accumulator is read once and or-written in VMEM
    instead of a separate HBM round trip for the union."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    acc = jax.lax.bitcast_convert_type(out_ref[0], jnp.int32)
    acc = _or_contract(lhs_ref, rhs_ref, acc, tk=tk, k_words=k_words)
    out_ref[0] = jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _pallas_bitmm(kernel, operands, *, ti, tw, tk, interpret):
    """Run ``kernel`` over the ragged (B, m/ti, w/tw, k/tk) grid; operands
    are lhs (B, m, k // 32), rhs (B, k, w) and optionally acc (B, m, w)."""
    lhs, rhs = operands[:2]
    B, m, wk = lhs.shape
    _, k, w = rhs.shape
    assert rhs.shape[0] == B and wk * 32 == k, (lhs.shape, rhs.shape)
    assert tk % 32 == 0, tk
    out_block = pl.BlockSpec((1, ti, tw), lambda b, i, j, kk: (b, i, j))
    in_specs = [
        pl.BlockSpec((1, ti, tk // 32), lambda b, i, j, kk: (b, i, kk)),
        pl.BlockSpec((1, tk, tw), lambda b, i, j, kk: (b, kk, j)),
    ] + [out_block] * (len(operands) - 2)
    return pl.pallas_call(
        functools.partial(kernel, tk=tk, k_words=wk),
        grid=(B, pl.cdiv(m, ti), pl.cdiv(w, tw), pl.cdiv(k, tk)),
        in_specs=in_specs,
        out_specs=out_block,
        out_shape=jax.ShapeDtypeStruct((B, m, w), jnp.uint32),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("ti", "tw", "tk", "interpret")
)
def bitmm_or_pallas(
    lhs_packed: jnp.ndarray,
    rhs_packed: jnp.ndarray,
    acc_packed: jnp.ndarray,
    *,
    ti: int = 128,
    tw: int = 128,
    tk: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """C = acc | (lhs x rhs) over the AND/OR semiring on packed words."""
    assert acc_packed.shape == lhs_packed.shape[:2] + rhs_packed.shape[2:]
    return _pallas_bitmm(
        _bitmm_or_kernel,
        (lhs_packed, rhs_packed, acc_packed),
        ti=ti, tw=tw, tk=tk, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("ti", "tw", "tk", "interpret")
)
def bitmm_pallas(
    lhs_packed: jnp.ndarray,
    rhs_packed: jnp.ndarray,
    *,
    ti: int = 128,
    tw: int = 128,
    tk: int = 4096,
    interpret: bool = False,
) -> jnp.ndarray:
    """C = lhs x rhs over the AND/OR semiring on packed words.

    Shapes: lhs (B, m, k // 32), rhs (B, k, w), out (B, m, w) — rectangular
    row counts are allowed (the query engine contracts a compacted block of
    m = row_capacity active rows against the full packed state).  Tiles
    need not divide ``m``, ``k`` or ``w`` (see the module docstring); on
    the TPU they must still meet Mosaic's block rules, which ops.py's
    ``_pick_tiles`` does.
    """
    return _pallas_bitmm(
        _bitmm_kernel,
        (lhs_packed, rhs_packed),
        ti=ti, tw=tw, tk=tk, interpret=interpret,
    )
