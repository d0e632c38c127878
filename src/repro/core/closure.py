"""Transitive-closure fixpoint engines (Algorithm 1 of the paper).

The paper's loop is ``while T changes: T <- T ∪ (T x T)`` where ``x`` is the
subsets-of-N matrix product.  Valiant's decomposition turns one ``T x T`` into
|N|^2 Boolean matmuls; only the |P| products that correspond to actual
productions ``A -> B C`` can contribute, so each engine evaluates

    new[A] |= OR_{(A->BC) in P}  T[B] ·∧∨ T[C]

as ONE batched matmul over the production axis (gather by B/C, scatter-OR by
A).  TPU adaptation notes are in DESIGN.md §3.

Engines
-------
  dense_closure      0/1 bf16 MXU matmul + ``> 0`` saturation (exact) — the
                     paper-faithful baseline (maps the paper's dGPU/CUBLAS
                     implementation onto the MXU).
  frontier_closure   beyond-paper: incremental evaluation that multiplies only
                     the delta discovered in the previous iteration.
  bitpacked_closure  uint32 AND/OR words (compiled Pallas kernel on TPU;
                     elsewhere that kernel in interpret mode, or its jnp
                     oracle at large sizes) — the TPU-native adaptation of
                     the paper's sparse (CSR/CUSPARSE) implementations: 32x
                     smaller HBM traffic for the memory-bound regime.

Invariants (relied on by engine/, delta/ and serve/; tested in
tests/test_engine.py and tests/test_delta.py)
---------------------------------------------
* **Masked-row exactness.**  At the fixpoint of any masked closure, rows
  of ``T`` selected by the returned mask ``M`` are *equal* to the
  corresponding rows of the all-pairs closure — not an approximation
  (soundness: every product is a real derivation; completeness: induction
  on derivation height, see ENGINE.md §masking math).
* **Monotone warm restarts.**  The fixpoint only ever adds entries, so an
  ``overflowed=True`` return can be re-entered at a larger row-capacity
  bucket from the returned ``(T, M)`` without losing or invalidating any
  work; capacities are static shapes, never data.
* **Frozen-row bit-identity.**  The ``*_repair_closure`` variants contract
  *against* rows marked frozen but never recompute them: frozen rows of
  the output are bit-identical to the input (the delta subsystem's repair
  contract, asserted exactly in tests/test_delta.py).
* **Own iteration count.**  Every masked closure returns its
  ``while_loop`` counter as a fourth output, ``(T, M, overflowed,
  iters)``: the fixpoint iterations this call ran, one per iteration
  event of its instrumented build.  The engine reads it only for a live
  ``closure.execute`` span, so an untraced call reads nothing more back.
"""
from __future__ import annotations

import functools
import operator
from functools import partial

import jax
import jax.numpy as jnp

from .matrices import ProductionTables, pack_bits, unpack_bits

def _saturating_dot(lhs, rhs, dims, dtype):
    return jax.lax.dot_general(
        lhs.astype(dtype),
        rhs.astype(dtype),
        dimension_numbers=dims,
        preferred_element_type=jnp.float32,
    ) > 0


def _mxu_bool_dot(lhs, rhs, dims) -> jnp.ndarray:
    """``dot(A, B) > 0`` for 0/1 operands — exact with f32 accumulation
    (any positive count stays positive).  The operand dtype is picked for
    the platform the caller is lowered for: bf16 feeds the TPU's MXU; other
    backends use f32, since bf16 matmul is emulated (and slow) on CPU."""
    return jax.lax.platform_dependent(
        lhs,
        rhs,
        tpu=partial(_saturating_dot, dims=dims, dtype=jnp.bfloat16),
        default=partial(_saturating_dot, dims=dims, dtype=jnp.float32),
    )


def _bool_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """Batched Boolean matmul via MXU saturation."""
    return _mxu_bool_dot(lhs, rhs, (((2,), (1,)), ((0,), (0,))))


def _scatter_or_bool(new_per_prod: jnp.ndarray, tables: ProductionTables):
    """OR per-production results into their LHS slot (bool: max == OR)."""
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    zeros = jnp.zeros(
        (tables.n_nonterms, *new_per_prod.shape[1:]), dtype=new_per_prod.dtype
    )
    return zeros.at[a_idx].max(new_per_prod)


def _scatter_or_packed(
    prod: jnp.ndarray, tables: ProductionTables
) -> jnp.ndarray:
    """Packed analog of _scatter_or_bool: trace-time OR tree per LHS
    nonterminal (P and N are grammar-sized), (P, …, w) -> (N, …, w)."""
    groups = tables.groups()
    rows = []
    for a in range(tables.n_nonterms):
        ps = groups.get(a)
        if ps:
            rows.append(functools.reduce(operator.or_, [prod[p] for p in ps]))
        else:
            rows.append(jnp.zeros(prod.shape[1:], prod.dtype))
    return jnp.stack(rows)


def _iter_limit(T: jnp.ndarray, max_iters: int | None) -> int:
    # Thm. 3 bounds iterations by |V|^2 |N| = n^2 N.  The loops all carry a
    # `changed` flag, so this limit is only a divergence guard — but a
    # tighter guess (the old n*N) can truncate *before* the fixpoint on
    # deep-derivation inputs: one iteration may add as little as one entry,
    # and there are n^2 N of them.
    n = T.shape[-1]
    return max_iters if max_iters is not None else n * n * T.shape[0]


def dense_step(T: jnp.ndarray, tables: ProductionTables) -> jnp.ndarray:
    """One fixpoint iteration T | (T x T) — the roofline unit of Algorithm 1
    (the while_loop hides per-iteration cost from cost_analysis)."""
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    prod = _bool_matmul(T[b_idx], T[c_idx])
    return T | _scatter_or_bool(prod, tables)


@partial(jax.jit, static_argnames=("tables", "max_iters"))
def dense_closure(
    T: jnp.ndarray, tables: ProductionTables, max_iters: int | None = None
) -> jnp.ndarray:
    """T^cf by the MXU path.  ``T`` is (N, n, n) bool."""
    if tables.n_prods == 0:
        return T
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _iter_limit(T, max_iters)

    def cond(state):
        _, changed, it = state
        return changed & (it < limit)

    def body(state):
        T, _, it = state
        prod = _bool_matmul(T[b_idx], T[c_idx])  # (P, n, n)
        new = _scatter_or_bool(prod, tables)
        grew = jnp.any(new & ~T)
        return T | new, grew, it + 1

    T, _, _ = jax.lax.while_loop(cond, body, (T, jnp.bool_(True), 0))
    return T


@partial(jax.jit, static_argnames=("tables", "max_iters"))
def frontier_closure(
    T: jnp.ndarray, tables: ProductionTables, max_iters: int | None = None
) -> jnp.ndarray:
    """Beyond-paper incremental closure.

    Invariant: entering an iteration, ``D`` holds exactly the entries added in
    the previous iteration.  Products of old·old entries were already folded
    in, so only ``T·D ∪ D·T`` can produce anything new.  Identical fixpoint,
    and the matmul operands are far sparser in late iterations.
    """
    if tables.n_prods == 0:
        return T
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _iter_limit(T, max_iters)

    def cond(state):
        _, D, it = state
        return jnp.any(D) & (it < limit)

    def body(state):
        T, D, it = state
        left = _bool_matmul(T[b_idx], D[c_idx])
        right = _bool_matmul(D[b_idx], T[c_idx])
        new = _scatter_or_bool(left | right, tables)
        D_next = new & ~T
        return T | new, D_next, it + 1

    T, _, _ = jax.lax.while_loop(cond, body, (T, T, 0))
    return T


# ---------------------------------------------------------------------- #
# Distributed-optimized engine (beyond-paper; see EXPERIMENTS.md §Perf).
#
# The baseline's distributed matmul lets XLA all-gather the bf16-lifted
# operands per production: ~12 GB/device/iteration of ICI traffic at n=64k.
# This engine:
#   1. hoists the operand exchange out of the production loop — T is
#      re-sharded ONCE per iteration into a row copy (k replicated within a
#      mesh row) and a col copy, so every production contracts locally;
#   2. moves BITS on the wire — the exchanged copies are the uint32-packed
#      matrix (1 bit/entry = 16x less ICI traffic than bf16), unpacked to
#      int8 on arrival (cheap VPU work);
#   3. contracts on the int8 MXU (s8 x s8 -> s32 at 2x the bf16 peak;
#      saturation > 0 is still exact since row counts < 2^31).
# State stays packed across iterations (8x smaller HBM footprint + the
# fixpoint check compares words).
# ---------------------------------------------------------------------- #


def _unpack_s8(Tp: jnp.ndarray, n: int) -> jnp.ndarray:
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (Tp[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(*Tp.shape[:-1], Tp.shape[-1] * 32)
    return out[..., :n].astype(jnp.int8)


@partial(jax.jit, static_argnames=("tables", "max_iters", "plan"))
def opt_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    max_iters: int | None = None,
    plan=None,
) -> jnp.ndarray:
    """T^cf with one-sided packed operand exchange + int8 MXU contraction."""
    if tables.n_prods == 0:
        return T

    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    n = T.shape[-1]
    limit = _iter_limit(T, max_iters)
    Tp = pack_bits(T)  # (N, n, w) uint32 — the persistent state

    if plan is not None:
        row_spec, col_spec, state_spec = plan.closure_specs()
    else:
        row_spec = col_spec = state_spec = None

    def wsc(x, spec):
        return x if spec is None else jax.lax.with_sharding_constraint(x, spec)

    def body(state):
        Tp, _, it = state
        # ONE packed exchange per iteration (bits on the wire): a row copy
        # (rows sharded, all words) and a col copy (all rows, words sharded);
        # both gathers move ~|T_packed|/mesh_dim bytes per device.
        row_copy = wsc(Tp, row_spec)
        col_copy = wsc(Tp, col_spec)
        lhs = _unpack_s8(row_copy, n)  # (N, rows_loc, n) int8, local
        rhs = _unpack_s8(col_copy, n)  # (N, n, cols_loc) int8, local
        prod = jax.lax.dot_general(
            lhs[b_idx],
            rhs[c_idx],
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        ) > 0
        new = _scatter_or_bool(prod, tables)
        new_p = wsc(pack_bits(new), state_spec)
        Tp_next = Tp | new_p
        grew = jnp.any(Tp_next != Tp)
        return Tp_next, grew, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < limit)

    Tp, _, _ = jax.lax.while_loop(cond, body, (Tp, jnp.bool_(True), 0))
    return unpack_bits(Tp, n)


def opt_step(T_packed: jnp.ndarray, tables: ProductionTables, n: int, plan=None):
    """One opt_closure iteration on packed state (roofline unit)."""
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)

    def wsc(x, spec):
        return x if spec is None or plan is None else (
            jax.lax.with_sharding_constraint(x, spec)
        )

    row_spec = col_spec = None
    if plan is not None:
        row_spec, col_spec, _ = plan.closure_specs()
    # barrier: materialize the PACKED replicas before unpacking, so the
    # all-gathers move 1-bit words (XLA otherwise reorders the unpack ahead
    # of the resharding and gathers int8 - 8x the wire bytes)
    row_copy = wsc(T_packed, row_spec)
    col_copy = wsc(T_packed, col_spec)
    if plan is not None:
        row_copy, col_copy = jax.lax.optimization_barrier((row_copy, col_copy))
    lhs = _unpack_s8(row_copy, n)
    rhs = _unpack_s8(col_copy, n)
    prod = jax.lax.dot_general(
        lhs[b_idx],
        rhs[c_idx],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    ) > 0
    new = _scatter_or_bool(prod, tables)
    return T_packed | pack_bits(new)


# ---------------------------------------------------------------------- #
# Source-restricted (masked) closure engines — the query-engine tentpole.
#
# A single-/multi-source CFPQ ("which j are reachable from these sources
# under nonterminal A?") does not need the all-pairs T^cf: row i of T^cf
# depends only on rows k reachable from i (T^cf[A,i,j] splits as
# T^cf[B,i,k] ∧ T^cf[C,k,j], and any such k is reachable from i through
# base edges).  These engines therefore maintain a row mask M, seeded with
# the requested sources, and
#
#   1. gather the ≤ R active rows into a compacted (R, n) sub-problem, so
#      one iteration costs |P|·R²·n (dense) / |P|·R·n·w words (bitpacked)
#      instead of the all-pairs |P|·n³ — asymptotically less work while the
#      reachable set stays small;
#   2. expand M with every column reached from an active row (those are the
#      rows the next iteration may contract against);
#   3. run the usual grow-until-fixpoint loop over BOTH T and M.
#
# R (``row_capacity``) is a static shape so the loop stays jittable; if the
# active set outgrows it the engine stops with ``overflowed=True`` and the
# caller re-enters with a larger capacity, warm-starting from the returned
# (T, M) — the fixpoint is monotone, so no work is lost.  At the fixpoint,
# rows of T selected by M equal the corresponding rows of the all-pairs
# closure (proof: soundness is monotonicity; completeness is induction on
# derivation height — the B-operand row is a source row, and its k column
# joins M before the C-operand row is needed).
# ---------------------------------------------------------------------- #


def _active_rows(M: jnp.ndarray, R: int):
    """First R set rows of the mask: (idx (R,) int32, valid (R,) bool)."""
    count = jnp.sum(M, dtype=jnp.int32)
    idx = jnp.nonzero(M, size=R, fill_value=0)[0].astype(jnp.int32)
    valid = jnp.arange(R, dtype=jnp.int32) < jnp.minimum(count, R)
    return idx, valid


def _iter_event(hook, it, M_next, changed, overflow) -> None:
    """Iteration-boundary observability hook (repro.obs).

    ``hook`` is a *static* argument of the masked closures: ``None``
    (the default, and every uninstrumented plan) compiles to nothing at
    all — same HLO as before the hook existed.  When set, a host
    callback fires once per fixpoint iteration with
    ``(iteration, active_rows, changed_units, overflow)``; ``changed``
    is whatever per-engine array records this iteration's growth (bool
    entries on dense paths, changed words on packed paths), reduced here
    so the transfer is four scalars.  Callback ordering follows program
    order within the loop; callers flush with ``jax.effects_barrier()``
    (see repro.obs.trace.iteration_scope).
    """
    if hook is None:
        return
    jax.debug.callback(
        hook,
        it + 1,
        jnp.sum(M_next, dtype=jnp.int32),
        jnp.sum(changed, dtype=jnp.int32),
        overflow,
    )


def _masked_limit(T: jnp.ndarray, max_iters: int | None) -> int:
    # the mask can grow for at most n extra iterations beyond the T bound
    return _iter_limit(T, max_iters) + T.shape[-1]


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Source-restricted closure on the dense MXU path.

    ``src_mask`` is an (n,) bool row seed.  Returns ``(T, M, overflowed, iters)``;
    rows of ``T`` where ``M`` is set equal the all-pairs closure rows iff
    ``overflowed`` is False (otherwise re-enter with the returned state and
    a larger ``row_capacity``).
    """
    n = T.shape[-1]
    if tables.n_prods == 0:
        # T^cf == T0: every row is already exact.
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        T, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = T[:, idx, :] & valid[None, :, None]  # (N, R, n) active rows
        # compact the contraction axis too: only rows in M can contribute
        lhs = rows[b_idx][:, :, idx] & valid[None, None, :]  # (P, R, R)
        prod = _bool_matmul(lhs, rows[c_idx])  # (P, R, n)
        new_r = _scatter_or_bool(prod, tables) & valid[None, :, None]
        # fill lanes are zeroed, so each target row has one real contributor
        new = jnp.zeros_like(T).at[:, idx, :].max(new_r)
        M_next = M | jnp.any(rows, axis=(0, 1))  # columns reached -> new rows
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        changed = new & ~T
        grew = jnp.any(changed) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, changed, overflow)
        return T | new, M_next, grew, overflow, it + 1

    state = (T, src_mask, jnp.bool_(True), jnp.bool_(False), 0)
    T, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return T, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_frontier_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Masked closure with the frontier (delta) trick: only products through
    entries discovered in the previous iteration are formed, and rows newly
    admitted to the mask enter the delta with their base edges."""
    n = T.shape[-1]
    if tables.n_prods == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)

    def cond(state):
        _, D, _, overflow, it = state
        return jnp.any(D) & ~overflow & (it < limit)

    def body(state):
        T, D, M, _, it = state
        idx, valid = _active_rows(M, R)
        rows_t = T[:, idx, :] & valid[None, :, None]
        rows_d = D[:, idx, :] & valid[None, :, None]
        lhs_t = rows_t[b_idx][:, :, idx] & valid[None, None, :]
        lhs_d = rows_d[b_idx][:, :, idx] & valid[None, None, :]
        prod = _bool_matmul(lhs_t, rows_d[c_idx]) | _bool_matmul(
            lhs_d, rows_t[c_idx]
        )
        new_r = _scatter_or_bool(prod, tables) & valid[None, :, None]
        new = jnp.zeros_like(T).at[:, idx, :].max(new_r)
        M_next = M | jnp.any(rows_t, axis=(0, 1))
        newly = M_next & ~M  # rows activated now: their base edges are fresh
        D_next = (new & ~T) | (T & newly[None, :, None])
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        _iter_event(iter_hook, it, M_next, new & ~T, overflow)
        return T | new, D_next, M_next, overflow, it + 1

    D0 = T & src_mask[None, :, None]
    state = (T, D0, src_mask, jnp.bool_(False), 0)
    T, _, M, overflow, iters = jax.lax.while_loop(cond, body, state)
    return T, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=(
        "tables", "row_capacity", "max_iters", "use_kernel", "iter_hook"
    ),
)
def masked_bitpacked_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    use_kernel: bool = True,
    iter_hook=None,
):
    """Source-restricted closure on packed words via the rectangular bitmm
    path: lhs is the (P, R, w) gather of active rows, rhs the full (P, n, w)
    packed state (contraction against base-only rows is sound — their
    entries are a subset of the true closure — and speeds convergence)."""
    n = T.shape[-1]
    if tables.n_prods == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)
    mm = kops.bitmm if use_kernel else kref.bitmm_ref
    Tp0 = pack_bits(T)  # (N, n, w)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        Tp, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = jnp.where(valid[None, :, None], Tp[:, idx, :], 0)  # (N, R, w)
        prod = mm(rows[b_idx], Tp[c_idx])  # (P, R, w)
        new_r = jnp.where(
            valid[None, :, None], _scatter_or_packed(prod, tables), 0
        )
        new = jnp.zeros_like(Tp).at[:, idx, :].max(new_r)
        reach_w = jax.lax.reduce(
            rows, jnp.uint32(0), jax.lax.bitwise_or, (0, 1)
        )  # (w,) packed columns reached from active rows
        M_next = M | unpack_bits(reach_w, n)
        Tp_next = Tp | new
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        changed_w = Tp_next != Tp  # changed words (packed growth unit)
        grew = jnp.any(changed_w) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, changed_w, overflow)
        return Tp_next, M_next, grew, overflow, it + 1

    state = (Tp0, src_mask, jnp.bool_(True), jnp.bool_(False), 0)
    Tp, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return unpack_bits(Tp, n), M, overflow, iters


@partial(
    jax.jit, static_argnames=("tables", "row_capacity", "max_iters", "plan")
)
def masked_opt_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    plan=None,
):
    """Source-restricted closure on the distributed packed-exchange path.

    The sharded sibling of :func:`masked_bitpacked_closure`, built like
    :func:`opt_closure`: the state stays uint32-packed across iterations,
    and with a :class:`~repro.shard.plans.MeshPlan` the compacted R-row
    active block is partitioned over the mesh row axis while packed words
    shard over ``model`` (``MeshPlan.closure_specs``).  Each iteration
    exchanges ONE pair of packed copies — the (N, R, w) row copy (the
    collective is restricted to the active row shards, R·w words instead
    of the all-pairs n·w) and the (N, n, w) column copy — then contracts
    locally on the int8 MXU.  ``plan=None`` runs the identical math on a
    single device.

    Semantics match the other masked engines exactly: returns
    ``(T, M, overflowed, iters)``; bucket-growth warm restarts are monotone and
    rows already at their fixpoint come back bit-identical regardless of
    the mesh shape (tested in tests/test_distributed_masked.py).

    No ``iter_hook``: under SPMD a ``jax.debug.callback`` fires on every
    participating device, so per-iteration events would arrive mesh-size
    times over.  Observability for this engine is call-level only
    (warm-restart/fallback events from the engine driver).
    """
    n = T.shape[-1]
    if tables.n_prods == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)
    Tp0 = pack_bits(T)  # (N, n, w) uint32 — persistent state

    if plan is not None:
        row_spec, col_spec, state_spec = plan.closure_specs()
    else:
        row_spec = col_spec = state_spec = None

    def wsc(x, spec):
        return x if spec is None else jax.lax.with_sharding_constraint(x, spec)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        Tp, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = jnp.where(valid[None, :, None], Tp[:, idx, :], 0)  # (N, R, w)
        # packed exchange restricted to the active shard: a row copy of the
        # COMPACTED block (rows sharded, all words) and a col copy of the
        # full state (all rows, words sharded); bits on the wire.
        row_copy = wsc(rows, row_spec)
        col_copy = wsc(Tp, col_spec)
        if plan is not None:
            row_copy, col_copy = jax.lax.optimization_barrier(
                (row_copy, col_copy)
            )
        lhs = _unpack_s8(row_copy, n)  # (N, R, n) int8, rows local
        rhs = _unpack_s8(col_copy, n)  # (N, n, n) int8, cols local
        prod = jax.lax.dot_general(
            lhs[b_idx],
            rhs[c_idx],
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        ) > 0  # (P, R, n)
        new_r = _scatter_or_bool(prod, tables) & valid[None, :, None]
        # fill lanes carry zero words, so each target row has exactly one
        # real contributor and the scatter-max is a plain scatter
        new_p = wsc(pack_bits(new_r), row_spec)  # (N, R, w)
        new = jnp.zeros_like(Tp).at[:, idx, :].max(new_p)
        Tp_next = wsc(Tp | new, state_spec)
        # columns reached from active rows -> new mask rows; reduced over
        # the unpacked int8 copy (a plain any-reduction — the SPMD
        # partitioner cannot shard the packed bitwise-or reduction)
        M_next = M | jnp.any(lhs, axis=(0, 1))
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        grew = jnp.any(Tp_next != Tp) | jnp.any(M_next & ~M)
        return Tp_next, M_next, grew, overflow, it + 1

    state = (Tp0, src_mask, jnp.bool_(True), jnp.bool_(False), 0)
    Tp, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return unpack_bits(Tp, n), M, overflow, iters


# ---------------------------------------------------------------------- #
# Reverse-reachability sweep (delta-repair support; see DELTA.md).
#
# Row i of any closure depends only on rows reachable from i through base
# edges (the masked-closure argument above).  Dually: an edge edit at row u
# can only change closure rows i that REACH u.  ``reverse_reachable_mask``
# computes that ancestor set as a Boolean matvec fixpoint on the label-blind
# base adjacency — O(n^2) per step for diameter steps, vs the |P| n^2 R per
# step of a closure iteration, so the repair planner can afford to run it on
# every delta.  delta/repair.py has the equivalent O(V+E) host BFS; this is
# the device path for graphs whose edge lists are too big to walk in Python.
# ---------------------------------------------------------------------- #


@partial(jax.jit, static_argnames=("max_iters",))
def reverse_reachable_mask(
    adj: jnp.ndarray, seeds: jnp.ndarray, max_iters: int | None = None
) -> jnp.ndarray:
    """Rows that can reach a seed row over ``adj`` (seeds included).

    ``adj`` is the (n, n) bool label-blind adjacency (adj[i, j] iff some
    edge i -> j); ``seeds`` an (n,) bool mask.  Fixpoint of
    ``m <- m | adj @ m`` — one step adds the direct predecessors of the
    current set, so it converges in at most graph-diameter iterations.
    """
    n = adj.shape[-1]
    limit = max_iters if max_iters is not None else n

    def cond(state):
        _, grew, it = state
        return grew & (it < limit)

    def body(state):
        m, _, it = state
        hit = _mxu_bool_dot(adj, m[:, None], (((1,), (0,)), ((), ())))[:, 0]
        m_next = m | hit
        return m_next, jnp.any(m_next & ~m), it + 1

    m, _, _ = jax.lax.while_loop(cond, body, (seeds, jnp.bool_(True), 0))
    return m


# ---------------------------------------------------------------------- #
# Repair closures (delta subsystem; see DELTA.md).
#
# A delta repair warm-starts from a cached state where MOST rows are known
# exact already ("frozen") and only a small set needs recomputing.  The
# query-path masked engines would re-admit every reached row to the active
# set — including the frozen ones — and recompute them all.  The repair
# variants instead treat frozen rows as already-converged constants:
#
#   * the compacted active block (R slots — only rows being rebuilt)
#     contracts against a compacted CONTEXT block (C slots — active plus
#     frozen rows), so frozen rows contribute their exact entries without
#     being recomputed: |P|·R·C·n dense per iteration vs the query path's
#     |P|·C'²·n with C' the whole re-seeded set (the packed variant keeps
#     the full-width rhs — |P|·R·n·w words — since w = n/32 makes the
#     contraction axis cheap and re-packing a gathered context is not);
#   * mask expansion skips frozen rows (M_next = M ∪ (reached \ frozen)),
#     so the row capacity is sized by the blast radius of the edit, not by
#     the size of the cached state.
#
# Contract: at the fixpoint, rows under the returned M are exact, and
# frozen rows are never written (bit-identical to their cached values).
# Completeness is the usual induction on derivation height, with frozen
# rows as base cases: an operand row is either frozen (its entries are
# already final in T) or joins M and converges by induction.
# ---------------------------------------------------------------------- #


@partial(
    jax.jit,
    static_argnames=(
        "tables", "row_capacity", "ctx_capacity", "max_iters", "iter_hook"
    ),
)
def masked_repair_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    frozen_mask: jnp.ndarray,
    row_capacity: int = 128,
    ctx_capacity: int | None = None,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Dense-path repair fixpoint.  ``src_mask`` seeds the rows to rebuild;
    rows under ``frozen_mask`` are trusted exact and never recomputed, but
    join the compacted contraction context (≤ ``ctx_capacity`` rows).
    Returns ``(T, M, overflowed, iters)`` with ``M`` the rebuilt rows; overflow
    fires when either the active set outgrows ``row_capacity`` or the
    context outgrows ``ctx_capacity``."""
    n = T.shape[-1]
    if tables.n_prods == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    C = min(ctx_capacity if ctx_capacity is not None else n, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        T, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        cidx, cvalid = _active_rows(M | frozen_mask, C)
        rows = T[:, idx, :] & valid[None, :, None]  # (N, R, n) active rows
        ctx = T[:, cidx, :] & cvalid[None, :, None]  # (N, C, n) context
        # contraction axis compacted to the context: frozen rows supply
        # their exact entries without occupying ACTIVE (output) capacity
        lhs = rows[b_idx][:, :, cidx] & cvalid[None, None, :]  # (P, R, C)
        prod = _bool_matmul(lhs, ctx[c_idx])  # (P, R, n)
        new_r = _scatter_or_bool(prod, tables) & valid[None, :, None]
        new = jnp.zeros_like(T).at[:, idx, :].max(new_r)
        reach = jnp.any(rows, axis=(0, 1))
        M_next = M | (reach & ~frozen_mask)
        overflow = (jnp.sum(M_next, dtype=jnp.int32) > R) | (
            jnp.sum(M_next | frozen_mask, dtype=jnp.int32) > C
        )
        changed = new & ~T
        grew = jnp.any(changed) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, changed, overflow)
        return T | new, M_next, grew, overflow, it + 1

    state = (T, src_mask & ~frozen_mask, jnp.bool_(True), jnp.bool_(False), 0)
    T, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return T, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=(
        "tables", "row_capacity", "max_iters", "use_kernel", "iter_hook"
    ),
)
def masked_bitpacked_repair_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    frozen_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    use_kernel: bool = True,
    iter_hook=None,
):
    """Packed-word analog of :func:`masked_repair_closure` (the bitpacked
    query engine already contracts against the full packed state; repair
    additionally excludes frozen rows from mask expansion)."""
    n = T.shape[-1]
    if tables.n_prods == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(T, max_iters)
    mm = kops.bitmm if use_kernel else kref.bitmm_ref
    Tp0 = pack_bits(T)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        Tp, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = jnp.where(valid[None, :, None], Tp[:, idx, :], 0)  # (N, R, w)
        prod = mm(rows[b_idx], Tp[c_idx])  # (P, R, w)
        new_r = jnp.where(
            valid[None, :, None], _scatter_or_packed(prod, tables), 0
        )
        new = jnp.zeros_like(Tp).at[:, idx, :].max(new_r)
        reach_w = jax.lax.reduce(
            rows, jnp.uint32(0), jax.lax.bitwise_or, (0, 1)
        )
        M_next = M | (unpack_bits(reach_w, n) & ~frozen_mask)
        Tp_next = Tp | new
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        changed_w = Tp_next != Tp
        grew = jnp.any(changed_w) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, changed_w, overflow)
        return Tp_next, M_next, grew, overflow, it + 1

    state = (
        Tp0,
        src_mask & ~frozen_mask,
        jnp.bool_(True),
        jnp.bool_(False),
        0,
    )
    Tp, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return unpack_bits(Tp, n), M, overflow, iters


# ---------------------------------------------------------------------- #
# Bitpacked engine.
# ---------------------------------------------------------------------- #


@partial(jax.jit, static_argnames=("tables", "max_iters", "use_kernel"))
def bitpacked_closure(
    T: jnp.ndarray,
    tables: ProductionTables,
    max_iters: int | None = None,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """T^cf on uint32-packed columns; state never leaves the packed layout.

    ``Tp[A]`` packs the columns of T[A].  For a production A -> B C the lhs
    operand T[B] needs its *contraction* axis (its columns) packed and the rhs
    T[C] its *output* axis (also its columns) packed — both are exactly the
    stored layout, so the whole fixpoint runs on packed words.
    """
    if tables.n_prods == 0:
        return T
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    n = T.shape[-1]
    limit = _iter_limit(T, max_iters)
    Tp = pack_bits(T)  # (N, n, w) uint32
    mm = kops.bitmm if use_kernel else kref.bitmm_ref

    def body(state):
        Tp, _, it = state
        prod = mm(Tp[b_idx], Tp[c_idx])  # (P, n, w) uint32
        Tp_next = Tp | _scatter_or_packed(prod, tables)
        grew = jnp.any(Tp_next != Tp)
        return Tp_next, grew, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < limit)

    Tp, _, _ = jax.lax.while_loop(cond, body, (Tp, jnp.bool_(True), 0))
    return unpack_bits(Tp, n)
