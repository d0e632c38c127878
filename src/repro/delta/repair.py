"""Row-level repair of materialized masked-closure states.

The engine caches, per grammar, a state ``(T, mask)`` where rows of ``T``
listed in ``mask`` equal the all-pairs closure rows (core/closure.py).  An
edge edit at source row ``u`` can only change closure rows ``i`` that can
*reach* ``u`` through base edges (the contrapositive of the masked-closure
dependency argument: row i is built entirely from rows reachable from i).
This module turns an :class:`~repro.core.graph.EdgeDelta` into the minimal
row surgery:

insertions (monotone)
    The cached ``T`` is a sound lower bound of the new closure, so the
    repair *re-seeds* the masked fixpoint with the inserted edges' source
    rows plus every cached-mask row that can reach one (ancestor set from a
    reverse-reachability sweep), warm-starting from the cached state.  Rows
    outside that ancestor set are untouched — their closure rows are
    provably unchanged.

deletions (non-monotone)
    Rows that could reach a deleted edge's source may have lost entries;
    they are conservatively *evicted*: reset to the new graph's base row
    and dropped from the mask (they warm-recompute on next touch).  All
    other rows provably never derived through the deleted edge and stay
    exact.

Invariants (tested bit-exactly in tests/test_delta.py)
------------------------------------------------------
* **Repair == recompute.**  After repair, rows of ``T`` under ``mask`` are
  identical to the corresponding rows of a from-scratch closure on the
  mutated graph.
* **Frozen-row bit-identity.**  Rows *outside* an insertion's ancestor set
  are handed to the repair closure as frozen context and come back
  bit-identical — byte-for-byte the cached rows, never "recomputed to the
  same value".  The single-path analog additionally preserves every frozen
  length annotation (freeze-on-first-discovery, core/semantics.py), which
  keeps previously extracted witnesses valid.
* **Eviction is conservative, never wrong.**  A deletion evicts exactly
  the rows that could reach a deleted edge's source (reset to base,
  dropped from the mask); surviving mask rows provably never derived
  through the deleted edge.

Both sweeps run on the *union* of the pre- and post-delta edge sets (the
current edges plus the deleted ones) — a sound over-approximation of either
graph's reachability, so one adjacency serves both directions.

Block-sparse states (``engine="blocksparse"``) ride the same surgery with
mixed granularity: this module's seed/ancestor/eviction computation stays
*row*-level (strictly finer than blocks — evicting or re-seeding a row is
always sound), while the repair closure it dispatches to
(``core/blocksparse.py``) runs *block*-granular — an insertion reactivates
the bit-tiles its seed rows touch, expansion skips fully-frozen tiles, and
frozen rows inside a reactivated tile stay bit-identical because the OR of
recomputed entries (a subset of the exact closure) into an already-exact
frozen row is a no-op.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import EdgeDelta, Graph
from repro.obs.trace import NULL_TRACER


def localize_state(state_dev):
    """Pull a mesh-sharded (multi-device) cached state back to one device.

    The distributed ``opt`` backend caches states sharded over its mesh;
    repair has no sharded variant — it is sized by the edit's blast
    radius, not the graph, so it always runs the single-device path.  A
    sharded state is therefore *evicted to the single-device path* here:
    gathered through the host once, repaired locally, and re-sharded by
    the next sharded query's executable.  Single-device states (including
    everything on non-opt backends) pass through untouched.
    """
    if (
        isinstance(state_dev, jax.Array)
        and len(state_dev.sharding.device_set) > 1
    ):
        return jnp.asarray(np.asarray(state_dev))
    return state_dev


def mirror_to_host(state_dev, tracer=NULL_TRACER) -> np.ndarray:
    """The host copy of a cached device state that answers are sliced
    from, taken under an ``engine.mirror`` span (``bytes``: the state's
    size).  The copy waits for the state, so the span holds the device to
    host transfer; a zero-copy view on the CPU backend."""
    with tracer.span("engine.mirror", cat="engine",
                     bytes=int(state_dev.nbytes)):
        return np.asarray(state_dev)


def placement_of(state_dev) -> str:
    """Placement tag of a cached closure state: ``"sharded"`` when it
    lives spread over >1 device, ``"local"`` otherwise.

    The engine records this in its per-grammar state metadata after every
    closure run *and* after every repair (which localizes sharded states
    via :func:`localize_state`) — it is a planner feature: consuming a
    state away from where it lives costs a host round-trip, which the
    cost model charges as a "move".
    """
    if (
        isinstance(state_dev, jax.Array)
        and len(state_dev.sharding.device_set) > 1
    ):
        return "sharded"
    return "local"


@dataclass
class DeltaStats:
    """Repair counters, surfaced through ``QueryResult.stats``.

    ``rows_repaired`` counts rows whose exactness the repair fixpoint
    (re-)established, ``rows_evicted`` cached rows dropped to base by a
    deletion, ``repair_iters`` closure-executable invocations (including
    capacity-overflow re-entries).  ``conj_repairs`` / ``conj_drops``
    record which side of the conjunctive delta contract ran per cached
    conjunctive state: insert-only warm re-seed repair, or the full state
    drop that any deletion forces (AND is non-monotone under row
    eviction; DELTA.md#conjunctive-states).  ``count_repairs`` /
    ``count_drops`` are the analogous pair for cached counting states:
    insert-only deltas recount affected rows from the new base (the
    Boolean warm re-seed would double-count — a count row is a sum, not
    a set, so folding new base edges into it is unsound), any deletion
    drops the state (DELTA.md#count-states).
    """

    rows_repaired: int = 0
    rows_evicted: int = 0
    repair_iters: int = 0
    conj_repairs: int = 0
    conj_drops: int = 0
    count_repairs: int = 0
    count_drops: int = 0

    def merge(self, other: "DeltaStats") -> None:
        self.rows_repaired += other.rows_repaired
        self.rows_evicted += other.rows_evicted
        self.repair_iters += other.repair_iters
        self.conj_repairs += other.conj_repairs
        self.conj_drops += other.conj_drops
        self.count_repairs += other.count_repairs
        self.count_drops += other.count_drops

    def as_dict(self) -> dict:
        return {
            "rows_repaired": self.rows_repaired,
            "rows_evicted": self.rows_evicted,
            "repair_iters": self.repair_iters,
            "conj_repairs": self.conj_repairs,
            "conj_drops": self.conj_drops,
            "count_repairs": self.count_repairs,
            "count_drops": self.count_drops,
        }


@dataclass(frozen=True)
class RepairPlan:
    """Row masks (padded length n) driving the state surgery.

    ``evict``: ancestors of deleted-edge sources — lose exactness.
    ``affected``: ancestors of inserted-edge sources — need re-closure.
    ``ins_sources``: inserted-edge source rows — their base entries grew.
    """

    evict: np.ndarray
    affected: np.ndarray
    ins_sources: np.ndarray

    @property
    def touches_anything(self) -> bool:
        return bool(
            self.evict.any() or self.affected.any() or self.ins_sources.any()
        )


def _reverse_adjacency(edges) -> dict[int, list[int]]:
    radj: dict[int, list[int]] = {}
    for i, _, j in edges:
        radj.setdefault(j, []).append(i)
    return radj


def reverse_reach_rows(
    n: int, edges, seeds, pad_to: int | None = None, radj=None
) -> np.ndarray:
    """Rows that can reach a seed row (seeds included): label-blind reverse
    BFS over the edge list, O(V + E) host work.  Pass a prebuilt ``radj``
    (:func:`_reverse_adjacency`) to amortize the edge walk over several
    sweeps.  The device analog (for edge lists too large to walk in
    Python) is ``core.closure.reverse_reachable_mask``."""
    size = pad_to if pad_to is not None else n
    mask = np.zeros(size, dtype=bool)
    seeds = [s for s in set(seeds) if 0 <= s < n]
    if not seeds:
        return mask
    if radj is None:
        radj = _reverse_adjacency(edges)
    stack = list(seeds)
    mask[seeds] = True
    while stack:
        v = stack.pop()
        for u in radj.get(v, ()):
            if not mask[u]:
                mask[u] = True
                stack.append(u)
    return mask


def plan_repair(graph: Graph, delta: EdgeDelta, pad_to: int) -> RepairPlan:
    """Build the row surgery plan for ``delta`` against the mutated
    ``graph`` (whose ``edges`` are already post-delta)."""
    union_edges = list(graph.edges) + list(delta.deleted)
    n = graph.n_nodes
    radj = (
        _reverse_adjacency(union_edges)
        if (delta.deleted_sources or delta.inserted_sources)
        else None
    )
    evict = reverse_reach_rows(
        n, union_edges, delta.deleted_sources, pad_to=pad_to, radj=radj
    )
    affected = reverse_reach_rows(
        n, union_edges, delta.inserted_sources, pad_to=pad_to, radj=radj
    )
    ins_sources = np.zeros(pad_to, dtype=bool)
    src = [u for u in delta.inserted_sources if u < n]
    if src:
        ins_sources[src] = True
    return RepairPlan(evict, affected, ins_sources)


#: floor of the base-entry buckets: a write's entries are padded to a power
#: of two at least this long, so the surgery compiles once per bucket.
ENTRY_BUCKET_MIN = 1024


def pad_entries(entries: np.ndarray, n: int) -> np.ndarray:
    """Pad a ``(3, k)`` int32 ``(a, i, j)`` entry list to its bucket: the
    next power of two, at least :data:`ENTRY_BUCKET_MIN`.  Padding entries
    name row ``n``, out of range, so the surgery drops them."""
    k = entries.shape[1]
    size = max(ENTRY_BUCKET_MIN, 1 << max(0, k - 1).bit_length())
    out = np.zeros((3, size), dtype=np.int32)
    out[1, k:] = n
    out[:, :k] = entries
    return out


@functools.partial(jax.jit, donate_argnums=0)
def row_surgery(state, evict, entries):
    """The touched rows' base surgery, on the device, for either state.

    ``state`` is a cached ``(|N|, n, n)`` state, Boolean (relational) or
    f32 lengths (single-path, picked by dtype); it is donated.  ``evict``
    is the ``(n,)`` evicted-row mask and ``entries`` the ``(3, K)`` padded
    base entries ``(a, i, j)`` of every touched row (:func:`pad_entries`).
    Evicted rows are cleared to absent (``False`` / ``inf``), then every
    base entry is set: ``True``, or length 1 where the entry is not
    finite yet — a finite length is never overwritten (the freeze
    contract), so an inserted source keeps its annotations.  Rows outside
    the entries and ``evict`` come back unchanged.
    """
    lengths = jnp.issubdtype(state.dtype, jnp.floating)
    absent = jnp.asarray(jnp.inf if lengths else False, state.dtype)
    state = jnp.where(evict[None, :, None], absent, state)
    a, i, j = entries
    if lengths:
        old = state.at[a, i, j].get(mode="fill", fill_value=jnp.inf)
        new = jnp.where(jnp.isfinite(old), old, 1.0)
    else:
        new = True
    return state.at[a, i, j].set(new, mode="drop")


def _repair_rows(
    state_host: np.ndarray,
    state_dev,
    mask: np.ndarray,
    plan: RepairPlan,
    base_entries_fn,
    run_closure,
    tracer=NULL_TRACER,
) -> tuple[np.ndarray, object, np.ndarray, DeltaStats]:
    """Shared row-surgery flow behind :func:`repair_state` and
    :func:`repair_single_path_state` — the two differ only in how a
    touched row merges with its base row, which :func:`row_surgery` picks
    by the state's dtype.  ``tracer`` times the host steps:
    ``repair.base_rows`` (the touched rows' base entries), ``repair.upload``
    (the entries' transfer and the device surgery, as dispatched) and the
    final ``engine.mirror``.

    1. base surgery on just the touched rows: grow inserted sources' base
       rows, reset evicted rows to the new base (cached entries above them
       may derive through a deleted edge; base-only is the sound floor to
       rebuild from).  The host lists the touched rows' base entries
       (``base_entries_fn(idx) -> (3, k)`` int32 ``(a, i, j)``), padded to
       a bucket; :func:`row_surgery` writes them into the device copy —
       an entries-sized transfer, and one executable per state and bucket.
    2. insertion repair: warm-start the monotone fixpoint from the cached
       state, seeded with the inserted sources plus every still-cached
       ancestor row.  Cached rows outside the ancestor set are FROZEN —
       provably unchanged by the delta, contracted against as constants,
       never recomputed (and returned bit-identical).
    """
    stats = DeltaStats()
    mask = np.array(mask, copy=True)
    state_dev = localize_state(state_dev)  # opt mesh states repair locally

    touched = plan.evict | plan.ins_sources
    dirty = False
    if touched.any():
        idx = np.nonzero(touched)[0]
        with tracer.span("repair.base_rows", cat="engine",
                         rows=len(idx)) as bsp:
            base = base_entries_fn(idx)
            entries = pad_entries(base, len(touched))
            bsp.set(entries=base.shape[1], bytes=int(entries.nbytes))
        stats.rows_evicted = int((mask & plan.evict).sum())
        mask &= ~plan.evict
        with tracer.span("repair.upload", cat="engine",
                         bytes=int(entries.nbytes)):
            state_dev = row_surgery(
                state_dev, jnp.asarray(plan.evict), jnp.asarray(entries)
            )
        dirty = True

    seed = (plan.affected & mask) | plan.ins_sources
    frozen = mask & ~plan.affected
    if seed.any():
        state_dev, M, calls = run_closure(state_dev, seed, frozen)
        M = np.asarray(M)
        stats.rows_repaired = int(M.sum())
        stats.repair_iters = calls
        # seed ⊆ M, so previously-exact affected rows are re-validated
        mask |= M
        dirty = True
    if dirty:
        state_host = mirror_to_host(state_dev, tracer)
    return state_host, state_dev, mask, stats


def repair_state(
    T_host: np.ndarray,
    T_dev,
    mask: np.ndarray,
    plan: RepairPlan,
    base_entries_fn,
    run_closure,
    tracer=NULL_TRACER,
) -> tuple[np.ndarray, object, np.ndarray, DeltaStats]:
    """Apply ``plan`` to one grammar's cached Boolean state.

    ``T_host`` / ``T_dev`` are the host view and device copy of the cached
    closure; ``T_host`` is returned as it is when the plan touches nothing,
    and the surgery never reads it.  Only the touched rows' base entries
    cross to the device — never a row or the whole matrix: evicted rows
    become their base row, inserted sources gain their base entries
    (``old | base``).  ``base_entries_fn(idx) -> (3, k)`` lists the
    mutated graph's base-matrix entries ``(a, i, j)`` of a row subset
    (:func:`repro.core.matrices.init_matrix_entries`);
    ``run_closure(T_dev, seed_mask, frozen_mask) -> (T_dev', M', n_calls)``
    runs the repair fixpoint to completion (handling capacity overflow).
    Both are supplied by the engine so repair stays agnostic of plan
    caches and backends; so is ``tracer``, which times the row surgery
    (:func:`_repair_rows`).  Rows under ``frozen_mask`` are exact on the
    mutated graph and are contracted against but never recomputed.
    ``T_dev`` is donated to the surgery: use the returned copy.

    Returns ``(T_host, T_dev, mask, stats)``; every returned row under
    ``mask`` equals the from-scratch closure row on the mutated graph.
    """
    return _repair_rows(
        T_host, T_dev, mask, plan, base_entries_fn, run_closure, tracer
    )


def repair_single_path_state(
    L_host: np.ndarray,
    L_dev,
    mask: np.ndarray,
    plan: RepairPlan,
    base_entries_fn,
    run_closure,
    tracer=NULL_TRACER,
) -> tuple[np.ndarray, object, np.ndarray, DeltaStats]:
    """Single-path analog of :func:`repair_state` for cached length states.

    ``L`` is the (|N|, n, n) f32 matrix of core/semantics.py —
    ``isfinite(L)`` is the Boolean closure, finite values are witness
    lengths frozen at first discovery.  The surgery is the same row plan,
    adapted to the freeze contract: previously finite entries are NEVER
    overwritten (witnesses recorded elsewhere split through them by exact
    length equality), so

    * inserted sources only *fill* entries that were absent (new base
      edges enter at length 1; existing annotations stay), then re-enter
      the repair fixpoint as seeds;
    * evicted rows reset wholesale to base lengths — and because any row
      whose recorded splits pass through an evicted row is itself an
      ancestor of the deleted edge (hence evicted too), surviving rows'
      annotations remain extraction-consistent.

    ``run_closure(L_dev, seed_mask, frozen_mask) -> (L_dev', M', n_calls)``
    runs the single-path repair fixpoint (semantics="single_path" through
    the engine's plan cache).  Returns ``(L_host, L_dev, mask, stats)``.
    """
    return _repair_rows(
        L_host, L_dev, mask, plan, base_entries_fn, run_closure, tracer
    )
