"""Query semantics on top of the closure (paper Sections 4-5).

Relational semantics: R_A = {(i, j) | A in T^cf[i, j]}  (Theorem 2).

Single-path semantics (Section 5): annotate every nonterminal entry with ONE
witness path length, frozen at first discovery — if A enters a[i,j] at
iteration p via A -> B C through node k, then l_A = l_B + l_C with the
lengths recorded for those operands, and l_A is never overwritten later.
A witness path of exactly that length is then reconstructed by recursive
splitting (``extract_path``).

Implementation note: the length annotation is a min-plus-style matrix product
*gated by novelty*.  We compute candidate lengths with a chunked min-plus
contraction (the (n, n, n) broadcast is tiled over k to bound memory) and
write them only where the Boolean closure just discovered a new entry, which
reproduces the paper's freeze-on-first-discovery rule exactly.

Invariants (relied on by engine/service.py and delta/repair.py; tested in
tests/test_single_path.py)
--------------------------
* **isfinite(L) == Boolean closure.**  On rows covered by the state's
  mask, ``jnp.isfinite(L)`` IS the Boolean closure ``T`` — the engine
  caches the single f32 tensor, never a ``(T, L)`` pair, and every
  consumer may recover membership from finiteness alone.
* **Freeze-on-first-discovery.**  A finite entry of ``L`` is never
  overwritten — not by further fixpoint iterations, not by warm restarts
  or capacity-bucket growth, not by delta repair (frozen rows come back
  bit-identical).  Witness extraction splits an entry by *exact length
  equality* (l_A == l_B + l_C), so this is a correctness requirement, not
  an optimization.
* **Backend-relative lengths.**  Recorded lengths may differ across
  backends (discovery order differs) but each is the length of some real
  witness path; ``extract_path`` reconstructs one of exactly that length.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .grammar import CNFGrammar
from .graph import Graph
from .matrices import ProductionTables, init_matrix, padded_size

INF = np.float32(np.inf)  # numpy: importing starts no backend


def _minplus(lhs: jnp.ndarray, rhs: jnp.ndarray, chunk: int = 64):
    """Batched min-plus matmul: out[p,i,j] = min_k lhs[p,i,k] + rhs[p,k,j].

    Tiled over the contraction axis k with a fori_loop so peak memory is
    (P, rows, chunk, cols).  Operands may be rectangular — the masked
    single-path closures contract compacted (R, C) row blocks against
    (C, n) context blocks."""
    P, rows, K = lhs.shape
    cols = rhs.shape[-1]
    chunk = min(chunk, K)
    n_chunks = -(-K // chunk)
    pad = n_chunks * chunk - K
    if pad:
        lhs = jnp.pad(lhs, ((0, 0), (0, 0), (0, pad)), constant_values=jnp.inf)
        rhs = jnp.pad(rhs, ((0, 0), (0, pad), (0, 0)), constant_values=jnp.inf)

    def body(c, acc):
        lk = jax.lax.dynamic_slice_in_dim(lhs, c * chunk, chunk, axis=2)
        rk = jax.lax.dynamic_slice_in_dim(rhs, c * chunk, chunk, axis=1)
        cand = jnp.min(lk[:, :, :, None] + rk[:, None, :, :], axis=2)
        return jnp.minimum(acc, cand)

    init = jnp.full((P, rows, cols), jnp.inf, jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def base_lengths(T: jnp.ndarray) -> jnp.ndarray:
    """Length annotation of a *base* matrix (``init_matrix`` output): every
    present entry is a real length-1 edge.  ``isfinite == T`` holds, but do
    NOT apply this to a derived/cached closure — its non-base entries are
    not edges, and extraction would fail on them."""
    return jnp.where(T, 1.0, jnp.inf).astype(jnp.float32)


@partial(jax.jit, static_argnames=("tables", "max_iters"))
def single_path_closure(
    T: jnp.ndarray, tables: ProductionTables, max_iters: int | None = None
):
    """Returns (T^cf bool (N,n,n), lengths f32 (N,n,n) with inf = absent)."""
    if tables.n_prods == 0:
        L = jnp.where(T, 1.0, jnp.inf).astype(jnp.float32)
        return T, L
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    # Thm. 3's |V|^2 |N| divergence guard — n*N is NOT enough (one entry
    # can land per iteration); see closure._iter_limit.
    limit = (
        max_iters
        if max_iters is not None
        else T.shape[-1] * T.shape[-1] * T.shape[0]
    )
    L0 = base_lengths(T)

    def cond(state):
        _, _, changed, it = state
        return changed & (it < limit)

    def body(state):
        T, L, _, it = state
        cand = _minplus(L[b_idx], L[c_idx])  # (P, n, n)
        cand_a = (
            jnp.full((tables.n_nonterms, *cand.shape[1:]), jnp.inf)
            .at[a_idx]
            .min(cand)
        )
        new_mask = jnp.isfinite(cand_a) & ~T
        L_next = jnp.where(new_mask, cand_a, L)  # freeze-on-first-discovery
        T_next = T | new_mask
        return T_next, L_next, jnp.any(new_mask), it + 1

    T, L, _, _ = jax.lax.while_loop(cond, body, (T, L0, jnp.bool_(True), 0))
    return T, L


# ---------------------------------------------------------------------- #
# Source-restricted (masked) single-path closures — the engine workload.
#
# The state is the length matrix L alone: by construction isfinite(L) is
# exactly the Boolean closure at every step (base entries start at 1,
# every newly discovered entry receives a finite candidate), so the engine
# caches ONE (N, n, n) f32 tensor per grammar instead of a (T, L) pair.
# The row-mask machinery is the Boolean masked closure's (closure.py):
# active rows are compacted to a static R-slot block, the min-plus
# contraction runs over the compacted (≤ R or ≤ C) row set, and columns
# reached from active rows join the mask until a joint fixpoint.  One
# iteration therefore costs |P|·R²·n min-plus work instead of the
# all-pairs |P|·n³ — the same row-compaction asymptotics as the Boolean
# engines, applied to the far more expensive min-plus contraction.
#
# Freeze-on-first-discovery is preserved verbatim: candidates are written
# only where isfinite(L) just flipped, and finite entries are NEVER
# overwritten — extraction depends on recorded sums staying exact, and
# warm restarts / delta repair depend on frozen rows staying bit-identical.
# Lengths may legitimately differ from the all-pairs closure's (discovery
# order differs), but every recorded length is a valid witness length.
# ---------------------------------------------------------------------- #


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_single_path_closure(
    L: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Source-restricted single-path closure (dense min-plus path).

    ``L`` is the (N, n, n) f32 length state (``base_lengths`` of the base
    matrix, or a cached state for a warm restart); ``src_mask`` the (n,)
    bool row seed.  Returns ``(L, M, overflowed, iters)``; rows of ``L`` under
    ``M`` have ``isfinite(L)`` equal to the all-pairs Boolean closure rows
    iff ``overflowed`` is False (otherwise re-enter with the returned
    state and a larger ``row_capacity`` — the fixpoint is monotone and
    finite entries are frozen, so no work is lost)."""
    from .closure import _active_rows, _iter_event, _masked_limit

    n = L.shape[-1]
    if tables.n_prods == 0:
        return L, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(L, max_iters)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        L, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = jnp.where(valid[None, :, None], L[:, idx, :], INF)  # (N, R, n)
        # compact the contraction axis too: only rows in M can contribute
        lhs = jnp.where(
            valid[None, None, :], rows[b_idx][:, :, idx], INF
        )  # (P, R, R)
        cand = _minplus(lhs, rows[c_idx])  # (P, R, n)
        cand_a = (
            jnp.full((tables.n_nonterms, R, n), jnp.inf).at[a_idx].min(cand)
        )
        newly = jnp.isfinite(cand_a) & ~jnp.isfinite(rows)
        # freeze-on-first-discovery: finite entries are never overwritten;
        # fill lanes carry inf so the scatter-min is duplicate-safe
        L_next = L.at[:, idx, :].min(jnp.where(newly, cand_a, jnp.inf))
        M_next = M | jnp.any(jnp.isfinite(rows), axis=(0, 1))
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        grew = jnp.any(newly) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, newly, overflow)
        return L_next, M_next, grew, overflow, it + 1

    state = (L, src_mask, jnp.bool_(True), jnp.bool_(False), 0)
    L, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return L, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_frontier_single_path_closure(
    L: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Masked single-path closure with the frontier (delta) trick: only
    min-plus products through entries discovered in the previous iteration
    are formed, and rows newly admitted to the mask enter the delta with
    all their entries.  A new entry's length is then the min over
    delta-involving splits — a subset of all splits, so it may exceed the
    dense variant's choice, but both operands are frozen finite entries and
    the recorded sum stays extraction-exact."""
    from .closure import _active_rows, _iter_event, _masked_limit

    n = L.shape[-1]
    if tables.n_prods == 0:
        return L, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(L, max_iters)

    def cond(state):
        _, D, _, overflow, it = state
        return jnp.any(D) & ~overflow & (it < limit)

    def body(state):
        L, D, M, _, it = state
        idx, valid = _active_rows(M, R)
        vrow = valid[None, :, None]
        rows = jnp.where(vrow, L[:, idx, :], INF)  # (N, R, n)
        rows_d = jnp.where(D[:, idx, :] & vrow, rows, INF)  # delta entries
        vk = valid[None, None, :]
        lhs = jnp.where(vk, rows[b_idx][:, :, idx], INF)  # (P, R, R)
        lhs_d = jnp.where(vk, rows_d[b_idx][:, :, idx], INF)
        cand = jnp.minimum(
            _minplus(lhs, rows_d[c_idx]), _minplus(lhs_d, rows[c_idx])
        )
        cand_a = (
            jnp.full((tables.n_nonterms, R, n), jnp.inf).at[a_idx].min(cand)
        )
        newly = jnp.isfinite(cand_a) & ~jnp.isfinite(rows)
        L_next = L.at[:, idx, :].min(jnp.where(newly, cand_a, jnp.inf))
        M_next = M | jnp.any(jnp.isfinite(rows), axis=(0, 1))
        fresh = M_next & ~M  # rows activated now: all their entries are new
        D_next = jnp.zeros_like(D).at[:, idx, :].max(newly) | (
            jnp.isfinite(L_next) & fresh[None, :, None]
        )
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        _iter_event(iter_hook, it, M_next, newly, overflow)
        return L_next, D_next, M_next, overflow, it + 1

    D0 = jnp.isfinite(L) & src_mask[None, :, None]
    state = (L, D0, src_mask, jnp.bool_(False), 0)
    L, _, M, overflow, iters = jax.lax.while_loop(cond, body, state)
    return L, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "plan"),
)
def masked_opt_single_path_closure(
    L: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    plan=None,
):
    """Source-restricted single-path closure for the distributed ``opt``
    engine: :func:`masked_single_path_closure` with the compacted R-row
    block partitioned over the mesh row axis.

    Lengths are f32 — there is no packed word layout to exchange — so the
    "opt" treatment here is the operand-exchange hoist alone: per
    iteration the compacted (N, R, n) active block is all-gathered ONCE
    (an explicit replication constraint — R·n f32 words on the wire, the
    f32 analog of the packed row exchange; XLA would otherwise reach the
    same exchange through an involuntary full rematerialization), and the
    two contraction operands slice locally from it: a row copy (R sharded
    over the mesh row axis via
    :meth:`~repro.shard.plans.MeshPlan.closure_specs`, columns replicated
    within a mesh row — the lhs gather by ``idx`` stays local) and a
    column copy (R replicated, columns sharded over ``model``).  The
    min-plus contraction and the scatter back into L then run fully
    locally, with the state L sharded over ``(row, model)``.
    ``plan=None`` is the identical single-device math.

    Freeze-on-first-discovery is preserved verbatim (candidates only land
    where ``isfinite(L)`` just flipped), so frozen rows stay bit-identical
    across warm restarts and mesh shapes; returns ``(L, M, overflowed, iters)``.
    """
    from .closure import _active_rows, _masked_limit

    n = L.shape[-1]
    if tables.n_prods == 0:
        return L, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(L, max_iters)

    if plan is not None:
        from jax.sharding import PartitionSpec

        row_spec, col_spec, state_spec = plan.closure_specs()
        repl_spec = PartitionSpec(None, None, None)
    else:
        row_spec = col_spec = state_spec = repl_spec = None

    def wsc(x, spec):
        return x if spec is None else jax.lax.with_sharding_constraint(x, spec)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        L, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        # ONE explicit exchange of the compacted block per iteration: the
        # row copy needs all columns of its row shard and the col copy
        # all rows of its column shard, so their union is the replicated
        # block — annotate that all-gather explicitly (the partitioner
        # would otherwise reach it via involuntary full rematerialization
        # on the conflicting row/col constraints), then slice locally.
        rows = wsc(
            jnp.where(valid[None, :, None], L[:, idx, :], INF), repl_spec
        )  # (N, R, n)
        row_copy = wsc(rows, row_spec)
        col_copy = wsc(rows, col_spec)
        if plan is not None:
            row_copy, col_copy = jax.lax.optimization_barrier(
                (row_copy, col_copy)
            )
        # compact the contraction axis too: only rows in M can contribute;
        # the idx column gather reads the row copy's replicated axis
        lhs = jnp.where(
            valid[None, None, :], row_copy[b_idx][:, :, idx], INF
        )  # (P, R, R) — output rows sharded, contraction local
        cand = _minplus(lhs, col_copy[c_idx])  # (P, R, n) (row, model)-sharded
        cand_a = (
            jnp.full((tables.n_nonterms, R, n), jnp.inf).at[a_idx].min(cand)
        )
        newly = jnp.isfinite(cand_a) & ~jnp.isfinite(rows)
        # freeze-on-first-discovery: finite entries are never overwritten;
        # fill lanes carry inf so the scatter-min is duplicate-safe
        L_next = wsc(
            L.at[:, idx, :].min(jnp.where(newly, cand_a, jnp.inf)), state_spec
        )
        M_next = M | jnp.any(jnp.isfinite(rows), axis=(0, 1))
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        grew = jnp.any(newly) | jnp.any(M_next & ~M)
        return L_next, M_next, grew, overflow, it + 1

    state = (L, src_mask, jnp.bool_(True), jnp.bool_(False), 0)
    L, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return L, M, overflow, iters


@partial(
    jax.jit,
    static_argnames=(
        "tables", "row_capacity", "ctx_capacity", "max_iters", "iter_hook"
    ),
)
def masked_single_path_repair_closure(
    L: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    frozen_mask: jnp.ndarray,
    row_capacity: int = 128,
    ctx_capacity: int | None = None,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Repair fixpoint for cached length states (delta subsystem; DELTA.md).

    Mirrors :func:`~repro.core.closure.masked_repair_closure`: ``src_mask``
    seeds the rows to rebuild, rows under ``frozen_mask`` are trusted exact
    and never recomputed but join the compacted contraction context
    (≤ ``ctx_capacity`` rows), supplying their frozen lengths as constants.
    Served by every backend — lengths are f32, so there is no packed
    variant to specialize.  Returns ``(L, M, overflowed, iters)``; frozen rows
    come back bit-identical (the scatter only targets active slots)."""
    from .closure import _active_rows, _iter_event, _masked_limit

    n = L.shape[-1]
    if tables.n_prods == 0:
        return L, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    C = min(ctx_capacity if ctx_capacity is not None else n, n)
    a_idx = jnp.asarray(tables.a_idx, jnp.int32)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(L, max_iters)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        L, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        cidx, cvalid = _active_rows(M | frozen_mask, C)
        rows = jnp.where(valid[None, :, None], L[:, idx, :], INF)  # (N, R, n)
        ctx = jnp.where(cvalid[None, :, None], L[:, cidx, :], INF)  # (N, C, n)
        lhs = jnp.where(
            cvalid[None, None, :], rows[b_idx][:, :, cidx], INF
        )  # (P, R, C)
        cand = _minplus(lhs, ctx[c_idx])  # (P, R, n)
        cand_a = (
            jnp.full((tables.n_nonterms, R, n), jnp.inf).at[a_idx].min(cand)
        )
        newly = jnp.isfinite(cand_a) & ~jnp.isfinite(rows)
        L_next = L.at[:, idx, :].min(jnp.where(newly, cand_a, jnp.inf))
        reach = jnp.any(jnp.isfinite(rows), axis=(0, 1))
        M_next = M | (reach & ~frozen_mask)
        overflow = (jnp.sum(M_next, dtype=jnp.int32) > R) | (
            jnp.sum(M_next | frozen_mask, dtype=jnp.int32) > C
        )
        grew = jnp.any(newly) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, newly, overflow)
        return L_next, M_next, grew, overflow, it + 1

    state = (L, src_mask & ~frozen_mask, jnp.bool_(True), jnp.bool_(False), 0)
    L, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return L, M, overflow, iters


# ---------------------------------------------------------------------- #
# Source-restricted (masked) conjunctive closures — the engine workload
# for ``semantics="conjunctive"`` (ENGINE.md#conjunctive).
#
# Per iteration:  new[A] = OR_prods-of-A ( AND_conjuncts ( T[b] x T[c] ) )
# over the compacted active-row block — the conjunctive generalization of
# closure.masked_closure with the identical state/mask/overflow contract.
# The masked-row exactness argument carries over: soundness because AND of
# monotone products is monotone, completeness by the same induction as the
# Boolean engine (every contraction column k of an active row joins M via
# M_next before the k-row's entries are needed exact).  The frontier
# (delta-only) trick is UNSOUND under AND — a conjunct's delta product
# misses pairs whose other conjuncts completed in earlier iterations — so
# there is no frontier variant; the engine aliases frontier to dense
# (plan.conj_engine_name).  Warm restarts on overflow are monotone for the
# same reason the relational ones are: the cached T is a subset of the
# fixpoint, and re-entering with a larger capacity only grows it.
# ---------------------------------------------------------------------- #


def _conj_combine(prod, tables):
    """Fold per-conjunct products into per-nonterminal planes: AND over
    each production's conjuncts, then OR over productions per LHS.

    ``prod`` has one leading plane per flattened conjunct (see
    :class:`~repro.core.conjunctive.ConjunctiveTables`).  Works on bool
    planes (dense path) and packed uint32 words (bitpacked path) alike —
    ``&``/``|`` are logical on the former and bitwise on the latter, the
    same fold bit-by-bit.  The reduce trees are built at trace time from
    the static tables (conjunct counts are grammar-sized)."""
    conj_groups = tables.conj_groups()
    lhs_groups = tables.lhs_groups()
    zero = jnp.zeros(prod.shape[1:], prod.dtype)
    planes = []
    for a in range(tables.n_nonterms):
        terms = []
        for p in lhs_groups.get(a, ()):
            ks = conj_groups[p]
            t = prod[ks[0]]
            for k in ks[1:]:
                t = t & prod[k]
            terms.append(t)
        if not terms:
            planes.append(zero)
            continue
        plane = terms[0]
        for t in terms[1:]:
            plane = plane | t
        planes.append(plane)
    return jnp.stack(planes)


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_conjunctive_closure(
    T: jnp.ndarray,
    tables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Source-restricted conjunctive closure on the dense MXU path.

    ``T`` is the (N, n, n) bool state (``conjunctive.init_matrix`` output
    or a cached state for a warm restart), ``tables`` a
    :class:`~repro.core.conjunctive.ConjunctiveTables`, ``src_mask`` the
    (n,) bool row seed.  Returns ``(T, M, overflowed, iters)``; rows of ``T``
    under ``M`` equal the all-pairs :func:`~repro.core.conjunctive.
    conjunctive_closure` rows iff ``overflowed`` is False (otherwise
    re-enter with the returned state and a larger ``row_capacity``)."""
    from .closure import _active_rows, _bool_matmul, _iter_event, _masked_limit

    n = T.shape[-1]
    if tables.n_conjuncts == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.conj_b, jnp.int32)
    c_idx = jnp.asarray(tables.conj_c, jnp.int32)
    limit = _masked_limit(T, max_iters)

    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        T, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = T[:, idx, :] & valid[None, :, None]  # (N, R, n) active rows
        # compact the contraction axis too: only rows in M can contribute
        lhs = rows[b_idx][:, :, idx] & valid[None, None, :]  # (K, R, R)
        prod = _bool_matmul(lhs, rows[c_idx])  # (K, R, n) per conjunct
        new_r = _conj_combine(prod, tables) & valid[None, :, None]
        new = jnp.zeros_like(T).at[:, idx, :].max(new_r)
        M_next = M | jnp.any(rows, axis=(0, 1))  # columns reached -> rows
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
    static_argnames=(
        "tables", "row_capacity", "max_iters", "use_kernel", "iter_hook"
    ),
)
def masked_bitpacked_conjunctive_closure(
    T: jnp.ndarray,
    tables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    use_kernel: bool = True,
    iter_hook=None,
):
    """Source-restricted conjunctive closure on packed words: each
    conjunct contracts the (K, R, w) gather of active rows against the
    full (K, n, w) packed state via the rectangular bitmm path, then the
    AND/OR fold runs bitwise on the packed products.  Contracting against
    base-only rows stays sound under AND — every per-conjunct product
    over a subset state is a subset of the true product, and an AND of
    subsets is a subset of the true AND — and at the joint fixpoint the
    masked rows match the dense variant bit-for-bit (any usable split
    column of an active row has joined M and converged)."""
    from .closure import _active_rows, _iter_event, _masked_limit
    from .matrices import pack_bits, unpack_bits

    n = T.shape[-1]
    if tables.n_conjuncts == 0:
        return T, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.conj_b, jnp.int32)
    c_idx = jnp.asarray(tables.conj_c, jnp.int32)
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
        prod = mm(rows[b_idx], Tp[c_idx])  # (K, R, w) per conjunct
        new_r = jnp.where(
            valid[None, :, None], _conj_combine(prod, tables), 0
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


# ---------------------------------------------------------------------- #
# Counting semantics: path-count matrices in a saturating semiring
# (ENGINE.md#counting--all-paths).
#
# C[A, i, j] counts the *derivation trees* of (A, i ->* j) — on an
# unambiguous grammar exactly the number of distinct paths i ->* j whose
# label string derives from A.  The count planes live in uint32 with the
# all-ones word as a sticky saturation sentinel: graphs with cycles have
# infinitely many paths, and the saturating arithmetic below makes the
# fixpoint land exactly on the sentinel instead of diverging (or silently
# wrapping).  Every combine is add-then-clamp / multiply-then-clamp, so
# SAT absorbs: once an entry saturates no later iteration, warm restart,
# or repair can bring it back down.
#
# The fixpoint is the Jacobi iteration of the polynomial system
#     C[A] = C0[A] + Σ_{A→BC} C[B] · C[C]
# (a tree is a base edge or a root production over two subtrees), iterated
# from below: every intermediate state under-counts, iterates increase
# monotonically, and height-h trees are counted after h iterations — so
# the masked machinery's bucket-growth warm restarts and the engine's
# monotone-state contract carry over verbatim.  Unlike the idempotent
# Boolean/min-plus algebras the combine is NOT absorptive (C | new would
# double-count), hence the recompute-from-base shape: the base tensor
# rides along as an explicit operand.
#
# Divergent entries cannot be left to the arithmetic alone: a single-label
# self-loop grows its count by +1 per iteration, so "iterate until the
# clamp kicks in" would take 2^32 iterations (and any iteration guard
# would truncate it into a silently wrong finite count).  Instead the
# closures run three phases:
#   A. the ordinary *Boolean* fixpoint on the support (derivability);
#   B. a *divergence* greatest-fixpoint: an entry has infinitely many
#      derivations iff some derivation of it passes through a dependency
#      cycle (pumping: a config (B,k,l) properly containing itself).
#      D = the largest X ⊆ support with  X[A,i,j] ⇒ ∃ A→BC, k with
#      (X[B,i,k] ∧ T[C,k,j]) ∨ (T[B,i,k] ∧ X[C,k,j]) — computed by
#      peeling entries with no X-touching split until stable;
#   C. the saturating Jacobi above, seeded with D stamped to SAT — the
#      finite entries converge at their (finite) derivation heights, and
#      SAT absorbs through every product that touches it.
# Phase B is sound under partial states too: a cycle found inside an
# under-approximated support is a cycle of the true support, so warm
# restarts never see a premature sentinel.
# ---------------------------------------------------------------------- #

#: saturation sentinel: a count of 0xFFFFFFFF means ">= 2^32 - 1 paths".
SAT_COUNT = np.uint32(0xFFFFFFFF)


def _sat_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Saturating uint32 add: clamps to the sentinel instead of wrapping.
    Unsigned overflow wrapped iff the wrapped sum is below an operand."""
    s = a + b
    return jnp.where(s < a, SAT_COUNT, s)


def _sat_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Saturating uint32 multiply: a*b overflows iff b > 0 and
    a > SAT // b.  SAT is absorbing for any b >= 2, and SAT * 1 = SAT,
    so stickiness needs no special casing."""
    hi = jnp.floor_divide(SAT_COUNT, jnp.maximum(b, jnp.uint32(1)))
    return jnp.where((b > jnp.uint32(0)) & (a > hi), SAT_COUNT, a * b)


def _count_mm(lhs: jnp.ndarray, rhs: jnp.ndarray, chunk: int = 64):
    """Batched saturating count matmul:
    out[p,i,j] = sat-Σ_k  sat(lhs[p,i,k] * rhs[p,k,j]).

    Mirrors :func:`_minplus`: tiled over the contraction axis k with a
    fori_loop so peak memory is (P, rows, chunk, cols), rectangular
    operands welcome.  The per-chunk reduction is a trace-time pairwise
    tree of saturating adds — a wrapping ``jnp.sum`` could alias a huge
    true count back into the small range, which the battery's golden
    saturation case would catch."""
    P, rows, K = lhs.shape
    cols = rhs.shape[-1]
    chunk = min(chunk, K)
    n_chunks = -(-K // chunk)
    pad = n_chunks * chunk - K
    if pad:
        lhs = jnp.pad(lhs, ((0, 0), (0, 0), (0, pad)))
        rhs = jnp.pad(rhs, ((0, 0), (0, pad), (0, 0)))

    def body(c, acc):
        lk = jax.lax.dynamic_slice_in_dim(lhs, c * chunk, chunk, axis=2)
        rk = jax.lax.dynamic_slice_in_dim(rhs, c * chunk, chunk, axis=1)
        part = _sat_mul(lk[:, :, :, None], rk[:, None, :, :])
        width = part.shape[2]
        while width > 1:  # static: unrolled at trace time
            half = width // 2
            merged = _sat_add(
                part[:, :, :half, :], part[:, :, half : 2 * half, :]
            )
            if width % 2:
                merged = jnp.concatenate(
                    [merged, part[:, :, 2 * half :, :]], axis=2
                )
            part = merged
            width = part.shape[2]
        return _sat_add(acc, part[:, :, 0, :])

    init = jnp.zeros((P, rows, cols), jnp.uint32)
    return jax.lax.fori_loop(0, n_chunks, body, init)


def _scatter_sat_add(prod: jnp.ndarray, tables: ProductionTables):
    """Per-LHS saturating sum of production products — the counting analog
    of closure.py's scatter-OR trees, built at trace time from the static
    tables (``.at[a_idx].add`` would wrap, not clamp)."""
    groups = tables.groups()
    zero = jnp.zeros(prod.shape[1:], jnp.uint32)
    planes = []
    for a in range(tables.n_nonterms):
        ps = groups.get(a, ())
        if not ps:
            planes.append(zero)
            continue
        t = prod[ps[0]]
        for p in ps[1:]:
            t = _sat_add(t, prod[p])
        planes.append(t)
    return jnp.stack(planes)


def count_base(
    graph: Graph, g: CNFGrammar, pad_to: int | None = None
) -> jnp.ndarray:
    """Base count matrix: C0[A,i,j] = #{edges (i,x,j) with A -> x}.

    NOT ``init_matrix(...).astype(uint32)`` — two parallel edges with
    different labels that both derive from A are two distinct length-1
    paths, which the Boolean base collapses to one bit."""
    n = pad_to if pad_to is not None else padded_size(graph.n_nodes)
    if n < graph.n_nodes:
        raise ValueError("pad_to smaller than the graph")
    C = np.zeros((g.n_nonterms, n, n), dtype=np.uint32)
    for i, x, j in graph.edges:
        for a in g.term_prods.get(x, ()):
            C[a, i, j] += 1
    return jnp.asarray(C)


def count_base_rows(
    graph: Graph, g: CNFGrammar, rows, pad_to: int | None = None
) -> np.ndarray:
    """The ``rows`` slices of :func:`count_base`, shape
    ``(|N|, len(rows), n)`` — O(|rows|·n) memory, for delta recounts."""
    n = pad_to if pad_to is not None else padded_size(graph.n_nodes)
    pos = {int(r): k for k, r in enumerate(rows)}
    out = np.zeros((g.n_nonterms, len(pos), n), dtype=np.uint32)
    for i, x, j in graph.edges:
        k = pos.get(i)
        if k is not None:
            for a in g.term_prods.get(x, ()):
                out[a, k, j] += 1
    return out


def _scatter_or(prod: jnp.ndarray, tables: ProductionTables):
    """Per-LHS OR of production products, trace-time fold (the Boolean
    analog of :func:`_scatter_sat_add`, for the divergence phase)."""
    groups = tables.groups()
    zero = jnp.zeros(prod.shape[1:], jnp.bool_)
    planes = []
    for a in range(tables.n_nonterms):
        ps = groups.get(a, ())
        if not ps:
            planes.append(zero)
            continue
        t = prod[ps[0]]
        for p in ps[1:]:
            t = t | prod[p]
        planes.append(t)
    return jnp.stack(planes)


@partial(jax.jit, static_argnames=("tables", "max_iters"))
def count_closure(
    C0: jnp.ndarray, tables: ProductionTables, max_iters: int | None = None
) -> jnp.ndarray:
    """All-pairs counting closure: the least fixpoint of
    ``C = C0 + Σ_{A→BC} C[B]·C[C]`` in the saturating semiring.

    ``C0`` is the :func:`count_base` tensor.  Runs the three phases of
    the section comment: Boolean support, divergence gfp, saturating
    Jacobi.  Finite entries converge at their derivation heights;
    entries with unboundedly many paths land exactly on the
    :data:`SAT_COUNT` sentinel."""
    if tables.n_prods == 0:
        return C0
    from .closure import _bool_matmul, dense_closure

    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = (
        max_iters
        if max_iters is not None
        else C0.shape[-1] * C0.shape[-1] * C0.shape[0]
    )

    T = dense_closure(C0 > 0, tables, max_iters=max_iters)  # phase A

    def g_cond(state):
        _, changed, it = state
        return changed & (it < limit)

    def g_body(state):
        X, _, it = state
        contrib = _bool_matmul(X[b_idx], T[c_idx]) | _bool_matmul(
            T[b_idx], X[c_idx]
        )
        X_next = X & _scatter_or(contrib, tables)
        return X_next, jnp.any(X_next != X), it + 1

    X, _, _ = jax.lax.while_loop(g_cond, g_body, (T, jnp.bool_(True), 0))

    C_seed = jnp.where(X, SAT_COUNT, C0)  # phase C: divergent entries pinned

    def cond(state):
        _, changed, it = state
        return changed & (it < limit)

    def body(state):
        C, _, it = state
        prod = _count_mm(C[b_idx], C[c_idx])  # (P, n, n)
        C_next = _sat_add(C_seed, _scatter_sat_add(prod, tables))
        # monotone guard for mixed/warm inputs (a cold run never dips)
        C_next = jnp.maximum(C_next, C)
        return C_next, jnp.any(C_next != C), it + 1

    C, _, _ = jax.lax.while_loop(cond, body, (C_seed, jnp.bool_(True), 0))
    return C


@partial(
    jax.jit,
    static_argnames=("tables", "row_capacity", "max_iters", "iter_hook"),
)
def masked_count_closure(
    C: jnp.ndarray,
    base: jnp.ndarray,
    tables: ProductionTables,
    src_mask: jnp.ndarray,
    row_capacity: int = 128,
    max_iters: int | None = None,
    iter_hook=None,
):
    """Source-restricted counting closure — the engine workload for
    ``semantics="count"`` (dense only; every backend pin aliases here via
    ``plan.count_engine_name`` — u32 saturating planes have no packed,
    frontier, or block-sparse layout).

    ``C`` is the (N, n, n) uint32 state (``base`` itself when cold, or a
    cached state for a warm restart), ``base`` the current
    :func:`count_base` tensor — the Jacobi recompute needs it as an
    explicit operand, unlike the idempotent algebras.  Returns
    ``(C, M, overflowed, iters)`` under the standard masked contract: rows of
    ``C`` selected by ``M`` equal the all-pairs :func:`count_closure`
    rows iff ``overflowed`` is False.  Masked-row exactness carries over
    from the Boolean argument with sums in place of ORs: every k
    contributing to an active row i is reachable from i, joins ``M``
    through the phase-A support closure, and its row converges by
    induction on derivation height.  The scatter combine is ``max`` —
    iterates increase monotonically from below, so max never loses a
    count, and it keeps the padding slots of the compacted index gather
    write-free."""
    from .closure import (
        _active_rows,
        _bool_matmul,
        _iter_event,
        _masked_limit,
        masked_closure,
    )

    n = C.shape[-1]
    if tables.n_prods == 0:
        return C, jnp.ones((n,), jnp.bool_), jnp.bool_(False), jnp.int32(0)
    R = min(row_capacity, n)
    b_idx = jnp.asarray(tables.b_idx, jnp.int32)
    c_idx = jnp.asarray(tables.c_idx, jnp.int32)
    limit = _masked_limit(C, max_iters)
    zero = jnp.uint32(0)

    # Phase A: Boolean support closure — settles M (and overflow) before
    # any counting happens, so phases B/C run on a fixed active-row set.
    T_sup, M, overflow, _ = masked_closure(
        (C > 0) | (base > 0), tables, src_mask,
        row_capacity=row_capacity, max_iters=max_iters,
    )
    idx, valid = _active_rows(M, R)
    T_rows = T_sup[:, idx, :] & valid[None, :, None]  # (N, R, n)
    lhs_T = T_rows[b_idx][:, :, idx] & valid[None, None, :]  # (P, R, R)

    # Phase B: divergence gfp on the compacted rows.  A cycle found in a
    # partial (overflowed) support is a cycle of the true support, so the
    # sentinel is never stamped prematurely.
    def g_cond(state):
        _, changed, it = state
        return changed & (it < limit)

    def g_body(state):
        X_rows, _, it = state
        lhs_X = X_rows[b_idx][:, :, idx] & valid[None, None, :]
        contrib = _bool_matmul(lhs_X, T_rows[c_idx]) | _bool_matmul(
            lhs_T, X_rows[c_idx]
        )
        X_next = X_rows & _scatter_or(contrib, tables)
        return X_next, jnp.any(X_next != X_rows), it + 1

    X_rows, _, _ = jax.lax.while_loop(
        g_cond, g_body, (T_rows, jnp.bool_(True), 0)
    )
    # stamp divergent entries (active rows only; invalid lanes write 0 —
    # a no-op under the scatter-max)
    C = C.at[:, idx, :].max(jnp.where(X_rows, SAT_COUNT, zero))

    # Phase C: saturating Jacobi over the settled active set.
    def cond(state):
        _, _, grew, overflow, it = state
        return grew & ~overflow & (it < limit)

    def body(state):
        C, M, _, _, it = state
        idx, valid = _active_rows(M, R)
        rows = jnp.where(valid[None, :, None], C[:, idx, :], zero)  # (N,R,n)
        # compact the contraction axis too: only rows in M can contribute
        lhs = jnp.where(
            valid[None, None, :], rows[b_idx][:, :, idx], zero
        )  # (P, R, R)
        prod = _count_mm(lhs, rows[c_idx])  # (P, R, n)
        base_r = jnp.where(valid[None, :, None], base[:, idx, :], zero)
        new_r = _sat_add(base_r, _scatter_sat_add(prod, tables))
        new_r = jnp.where(valid[None, :, None], new_r, zero)
        C_next = C.at[:, idx, :].max(new_r)
        M_next = M | jnp.any(rows != zero, axis=(0, 1))
        overflow = jnp.sum(M_next, dtype=jnp.int32) > R
        changed = C_next != C
        grew = jnp.any(changed) | jnp.any(M_next & ~M)
        _iter_event(iter_hook, it, M_next, changed, overflow)
        return C_next, M_next, grew, overflow, it + 1

    state = (C, M, ~overflow, overflow, 0)
    C, M, _, overflow, iters = jax.lax.while_loop(cond, body, state)
    return C, M, overflow, iters


# ---------------------------------------------------------------------- #
# Witness-path reconstruction ("simple search" of Theorem 5), host-side.
# ---------------------------------------------------------------------- #


class _DerivationBase:
    """Shared host-side index over one (graph, grammar) pair: edge
    membership by endpoint pair, binary productions grouped by LHS,
    terminal productions grouped by LHS.  Built once per batch by both
    witness reconstruction (:class:`PathExtractor`) and bounded all-path
    enumeration (:class:`DerivationIndex`)."""

    def __init__(self, graph: Graph, g: CNFGrammar) -> None:
        self.g = g
        self._edges: dict[tuple[int, int], list[str]] = {}
        for s, x, d in graph.edges:
            self._edges.setdefault((s, d), []).append(x)
        self._by_lhs: dict[int, list[tuple[int, int]]] = {}
        for a, b, c in g.binary_prods:
            self._by_lhs.setdefault(a, []).append((b, c))
        self._term_by_lhs: dict[int, list[str]] = {}
        for x, lhss in g.term_prods.items():
            for a in lhss:
                self._term_by_lhs.setdefault(a, []).append(x)


class PathExtractor(_DerivationBase):
    """Batched witness reconstruction over one (graph, grammar) pair.

    Hoists the graph/grammar index structures (:class:`_DerivationBase`)
    out of the per-pair extraction loop, so serving a result with
    thousands of witnesses builds them once instead of once per pair.
    Extraction itself runs on an explicit stack (not Python recursion) —
    witness lengths grow with the graph and would otherwise hit the
    interpreter recursion limit.
    """

    def extract(
        self, L: np.ndarray, nonterm: str, i: int, j: int
    ) -> list[tuple[int, str, int]]:
        """Reconstruct a path i ->* j derivable from ``nonterm`` whose
        length equals the recorded annotation ``L[nonterm, i, j]``.
        Raises KeyError if (i, j) is not in R_nonterm."""
        L = np.asarray(L)
        a0 = self.g.index_of(nonterm)
        if not np.isfinite(L[a0, i, j]):
            raise KeyError(f"({nonterm}, {i}, {j}) not in the relation")
        out: list[tuple[int, str, int]] = []
        stack = [(a0, i, j, float(L[a0, i, j]))]
        while stack:
            a, s, d, length = stack.pop()
            if length == 1.0:
                for x in self._term_by_lhs.get(a, ()):  # A -> x, edge (s,x,d)
                    if x in self._edges.get((s, d), ()):
                        out.append((s, x, d))
                        break
                else:
                    raise AssertionError(
                        "length-1 witness without a matching edge"
                    )
                continue
            for b, c in self._by_lhs.get(a, ()):
                lb = L[b, s, :]
                lc = L[c, :, d]
                ks = np.nonzero(
                    np.isfinite(lb) & np.isfinite(lc) & (lb + lc == length)
                )[0]
                if ks.size:
                    k = int(ks[0])
                    # LIFO: push the C-half first so the B-half emits first
                    stack.append((c, k, d, float(lc[k])))
                    stack.append((b, s, k, float(lb[k])))
                    break
            else:
                raise AssertionError(
                    "no consistent split — annotation invariant broken"
                )
        return out


def extract_path(
    L: np.ndarray,
    graph: Graph,
    g: CNFGrammar,
    nonterm: str,
    i: int,
    j: int,
) -> list[tuple[int, str, int]]:
    """One-shot wrapper around :class:`PathExtractor` (rebuilds the index
    structures per call — batch extraction should use the class)."""
    return PathExtractor(graph, g).extract(L, nonterm, i, j)


class DerivationIndex(_DerivationBase):
    """Packed derivation index: bounded all-path enumeration over one
    (closure, graph, grammar) triple.

    Generalizes :class:`PathExtractor`'s witness reconstruction from "one
    path whose length matches the recorded annotation" to "the first k
    distinct paths within a length bound": the same shared grammar/edge
    index (:class:`_DerivationBase`), plus the Boolean closure held
    bit-packed by rows *and* by columns, so the split candidates of a
    production ``A -> B C`` at ``(i, j)`` — the nodes t with ``T[B,i,t]``
    and ``T[C,t,j]`` — come from one bitwise AND over packed words
    instead of an O(n) scan per probe.  The closure also prunes the
    enumeration: a (nonterm, s, d) branch with no closure entry derives
    nothing at any length and is cut immediately.

    ``T`` must be exact on every row reachable from the queried sources
    (the full all-pairs closure, or a masked state whose mask covers the
    source — mask rows are exact and paths only traverse reachable rows).
    """

    def __init__(self, T: np.ndarray, graph: Graph, g: CNFGrammar) -> None:
        super().__init__(graph, g)
        self._T = np.asarray(T).astype(bool)
        self.n = self._T.shape[-1]
        # bit t of _rows[A, i] is T[A, i, t]; _cols is the transpose view
        # packed the same way, so splits() ANDs two contiguous words.
        self._rows = np.packbits(self._T, axis=-1)
        self._cols = np.packbits(self._T.transpose(0, 2, 1), axis=-1)

    def splits(self, b: int, i: int, c: int, j: int) -> np.ndarray:
        """Nodes t with T[b, i, t] and T[c, t, j], via packed AND."""
        words = self._rows[b, i] & self._cols[c, j]
        return np.nonzero(np.unpackbits(words, count=self.n))[0]

    def _enum(self, a: int, s: int, d: int, budget: int):
        """Yield edge-list paths ``s ->* d`` derivable from nonterminal
        ``a`` with 1 <= length <= budget, possibly with repeats (the same
        path can arise through different derivations — the public API
        dedupes).  Terminates because both halves of every split get a
        strictly smaller budget; recursion depth is O(budget)."""
        if budget < 1 or not self._T[a, s, d]:
            return
        for x in self._term_by_lhs.get(a, ()):
            if x in self._edges.get((s, d), ()):
                yield [(s, x, d)]
        if budget < 2:
            return
        for b, c in self._by_lhs.get(a, ()):
            for t in self.splits(b, s, c, d):
                t = int(t)
                for left in self._enum(b, s, t, budget - 1):
                    for right in self._enum(c, t, d, budget - len(left)):
                        yield left + right

    def extract_paths(
        self, nonterm: str, i: int, j: int, k: int, max_len: int
    ) -> list[list[tuple[int, str, int]]]:
        """Up to ``k`` distinct paths ``i ->* j`` derivable from
        ``nonterm``, each of length <= ``max_len``, shortest-budget-first
        within the enumeration order.  A nullable start contributes the
        empty path at ``i == j``, matching the relational pair set."""
        a0 = self.g.index_of(nonterm)
        out: list[list[tuple[int, str, int]]] = []
        seen: set[tuple] = set()
        if i == j and nonterm in self.g.nullable and k > 0:
            out.append([])
            seen.add(())
        for path in self._enum(a0, i, j, max_len):
            key = tuple(path)
            if key in seen:
                continue
            seen.add(key)
            out.append(path)
            if len(out) >= k:
                break
        return out


def extract_paths(
    T: np.ndarray,
    graph: Graph,
    g: CNFGrammar,
    nonterm: str,
    i: int,
    j: int,
    k: int = 10,
    max_len: int = 16,
) -> list[list[tuple[int, str, int]]]:
    """One-shot bounded all-path enumeration (rebuilds the packed index
    per call — batch extraction should use :class:`DerivationIndex`)."""
    return DerivationIndex(T, graph, g).extract_paths(nonterm, i, j, k, max_len)


# ---------------------------------------------------------------------- #
# Top-level query API.
# ---------------------------------------------------------------------- #


def _masked_allpairs(T: jnp.ndarray, tables: ProductionTables) -> jnp.ndarray:
    """The masked engine with every row seeded == the all-pairs closure."""
    from . import closure as _closure

    n = T.shape[-1]
    Tm, _, _, _ = _closure.masked_closure(
        T, tables, jnp.ones((n,), jnp.bool_), row_capacity=n
    )
    return Tm


def _blocksparse_allpairs(
    T: jnp.ndarray, tables: ProductionTables
) -> jnp.ndarray:
    """The block-sparse masked engine with every row seeded and unbounded
    block capacity == the all-pairs closure on occupied tiles."""
    from . import blocksparse as _bs

    n = T.shape[-1]
    Tm, _, _, _ = _bs.masked_blocksparse_closure(
        T, tables, jnp.ones((n,), jnp.bool_), row_capacity=n
    )
    return Tm


def closure_engines() -> dict:
    """Dispatch table of all-pairs closure engines by name."""
    from . import closure as _closure

    return {
        "dense": _closure.dense_closure,
        "frontier": _closure.frontier_closure,
        "bitpacked": _closure.bitpacked_closure,
        "opt": _closure.opt_closure,
        "masked": _masked_allpairs,
        "blocksparse": _blocksparse_allpairs,
    }


def evaluate_relational(
    graph: Graph,
    g: CNFGrammar,
    start: str,
    engine: str = "dense",
) -> set[tuple[int, int]]:
    """Full relational CFPQ: returns R_start restricted to real nodes,
    including the (m, m) pairs contributed by a nullable start symbol."""
    from .matrices import relations_from_matrix

    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    fn = closure_engines()[engine]
    T = fn(T0, tables)
    rel = relations_from_matrix(np.asarray(T), g, graph.n_nodes)[start]
    if start in g.nullable:
        rel |= {(m, m) for m in range(graph.n_nodes)}
    return rel


def evaluate_count(
    graph: Graph, g: CNFGrammar, start: str
) -> dict[tuple[int, int], int]:
    """Counting CFPQ: (i, j) -> number of derivations of ``start`` paths
    i ->* j (== distinct paths on an unambiguous grammar), saturating at
    :data:`SAT_COUNT`.  A nullable start contributes the empty path: one
    extra path per (m, m), saturating-added like any other."""
    tables = ProductionTables.from_grammar(g)
    C = np.asarray(count_closure(count_base(graph, g), tables))
    a0 = g.index_of(start)
    n = graph.n_nodes
    out: dict[tuple[int, int], int] = {}
    for i, j in zip(*np.nonzero(C[a0, :n, :n])):
        out[(int(i), int(j))] = int(C[a0, i, j])
    if start in g.nullable:
        for m in range(n):
            c = out.get((m, m), 0)
            out[(m, m)] = c + 1 if c < int(SAT_COUNT) else int(SAT_COUNT)
    return out


def evaluate_single_path(
    graph: Graph, g: CNFGrammar, start: str
) -> dict[tuple[int, int], list[tuple[int, str, int]]]:
    """Single-path CFPQ: one witness path per (i, j) in R_start, including
    the empty-path witnesses of a nullable start symbol (matching the pairs
    :func:`evaluate_relational` reports)."""
    tables = ProductionTables.from_grammar(g)
    T0 = init_matrix(graph, g)
    T, L = single_path_closure(T0, tables)
    L = np.asarray(L)
    a0 = g.index_of(start)
    n = graph.n_nodes
    ex = PathExtractor(graph, g)
    out = {}
    for i, j in zip(*np.nonzero(np.asarray(T)[a0, :n, :n])):
        out[(int(i), int(j))] = ex.extract(L, start, int(i), int(j))
    if start in g.nullable:
        for m in range(n):
            out.setdefault((m, m), [])  # empty path m pi m
    return out
