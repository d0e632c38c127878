"""Tiled block-sparse bitpacked closure state (``engine="blocksparse"``).

The dense engines materialize the full (N, n, n) Boolean tensor — the
stated scale ceiling of this reproduction: real CFPQ workloads (Hellings'
graph-database framing, the ``scipy.sparse`` exemplar line) are sparse,
and at n in the 10^5–10^6 range dense padding is unpayable.  This module
stores the closure as a **per-nonterminal active-block list over fixed
B×B bit-tiles**:

* A tile is the (B, B) Boolean submatrix of one nonterminal at block
  coordinates ``(rb, cb)``, bitpacked along columns into ``(B, B//32)``
  uint32 words (exactly :func:`repro.core.matrices.pack_bits` order:
  bit ``b`` of word ``w`` is column ``32w + b``).
* All occupied tiles of all nonterminals live slot-compacted in ONE
  device array ``tiles (S, B, B//32)``; a host-side index
  ``index[a][rb][cb] -> slot`` is the active-block list.  Materialized
  state is therefore O(occupied blocks), never O(n²).

The fixpoint is **host-driven**: block discovery (which (row-block,
k-block)×(k-block, col-block) pairs have occupied operands) is dynamic
sparsity that a fixed-shape jitted loop cannot express, so a Python
driver enumerates the occupied pairs — that enumeration IS the block
skipping — and hands each bucket of pairs to a jitted contraction step
(:func:`_contract_chunk`) that gathers operand tiles, runs the packed
Pallas tile kernel (:func:`repro.kernels.ops.tile_bitmm`), OR-combines
products per output block, and reports per-block change flags.  Newly
occupied blocks and changed blocks feed the next iteration's frontier;
pairs whose operands both went unchanged are never re-contracted.

Masking is block-granular: the active set is a set of row-*blocks*
(the block-level analog of the row-compacted masks in core/closure.py),
expanded along occupied blocks exactly like the row engines expand M —
the returned mask covers every row of every active block, which at
fixpoint is sound *and* exact (an inactive block's rows have no base
facts, hence empty closure rows).  Capacity is counted in **slots**
(occupied blocks): overflow returns the monotone partial state for the
engine's standard warm-restart ladder; a capacity of at least ``n`` is
treated as unbounded (the top of the ladder — the host driver has no
shape reason to cap growth there).

The wrappers below speak the masked-engine contract of core/closure.py
(``(T, tables, src_mask[, frozen_mask]) -> (T, M, overflow, iters)`` on dense
tensors) so ``engine="blocksparse"`` drops into the PlanKey/service
machinery unchanged; :meth:`BlockSparseState.from_graph` builds the
state straight from the edge list for the million-node path where the
dense tensor must never exist (benchmarks/bench_scaling.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph
from .grammar import CNFGrammar
from .matrices import ProductionTables, padded_size

#: default tile edge; must be a multiple of 32 and divide the padded n
#: (the LANE-padded sizes are multiples of 128, so 32/64/128 always fit).
DEFAULT_TILE = 128

#: pairs contracted per device call — bounds peak memory of the unpacked
#: (chunk, B, B) intermediates regardless of how many occupied pairs one
#: iteration discovers.
PAIR_CHUNK = 512

#: slot-store capacities and jit bucket sizes are padded to powers of two
#: from this floor so the executable cache stays O(log) per shape axis.
_MIN_BUCKET = 8


def _pow2_at_least(x: int, floor: int = _MIN_BUCKET) -> int:
    p = floor
    while p < x:
        p *= 2
    return p


def _pack_words_np(bits: np.ndarray) -> np.ndarray:
    """(…, m) bool -> (…, m//32) uint32, matching matrices.pack_bits."""
    m = bits.shape[-1]
    b = bits.reshape(*bits.shape[:-1], m // 32, 32).astype(np.uint32)
    return (b << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)


def _unpack_words_np(words: np.ndarray) -> np.ndarray:
    """(…, w) uint32 -> (…, 32w) bool, matching matrices.unpack_bits."""
    bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * 32).astype(bool)


def occupied_block_count(T: np.ndarray, tile: int = DEFAULT_TILE) -> int:
    """Occupied B×B blocks of a dense (N, n, n) Boolean tensor — the
    obs gauge behind ``blocksparse_occupied_blocks`` and the planner's
    ground truth for pricing this backend."""
    T = np.asarray(T)
    n = T.shape[-1]
    if n % tile:
        raise ValueError(f"matrix size {n} is not a multiple of tile {tile}")
    g = n // tile
    occ = T.reshape(T.shape[0], g, tile, g, tile).any(axis=(2, 4))
    return int(occ.sum())


def occupied_blocks_of_edges(
    n_nodes: int, edges, tile: int = DEFAULT_TILE
) -> int:
    """Distinct (i//B, j//B) block coordinates touched by an edge list —
    the label-blind base-graph occupancy estimate the planner prices
    ``engine="blocksparse"`` with (O(E), no matrix materialized)."""
    g = max(-(-n_nodes // tile), 1)
    return len({(i // tile) * g + (j // tile) for i, _, j in edges})


class BlockSparseState:
    """Slot-compacted block-sparse bitpacked closure state.

    Host-mutable (the fixpoint driver owns it single-threaded); only the
    tile payload lives on device.  Slots are monotone: bits are only ever
    OR-ed in, and a slot, once allocated, keeps its (a, rb, cb) identity
    for the state's lifetime — which is what makes overflow returns safe
    warm-restart points.
    """

    __slots__ = ("n", "tile", "grid", "n_nonterms", "tiles", "coords", "index")

    def __init__(self, n: int, n_nonterms: int, tile: int = DEFAULT_TILE):
        if tile <= 0 or tile % 32:
            raise ValueError(f"tile must be a positive multiple of 32: {tile}")
        if n % tile:
            raise ValueError(f"matrix size {n} is not a multiple of tile {tile}")
        self.n = n
        self.tile = tile
        self.grid = n // tile
        self.n_nonterms = n_nonterms
        self.tiles = jnp.zeros(
            (_MIN_BUCKET, tile, tile // 32), dtype=jnp.uint32
        )
        self.coords: list[tuple[int, int, int]] = []  # slot -> (a, rb, cb)
        self.index: list[dict[int, dict[int, int]]] = [
            {} for _ in range(n_nonterms)
        ]

    # ------------------------------------------------------------------ #
    @property
    def n_slots(self) -> int:
        return len(self.coords)

    @property
    def occupied(self) -> int:
        """Occupied blocks == live slots (zero tiles are never allocated:
        the driver checks products for nonzero before slotting them)."""
        return len(self.coords)

    def nbytes(self) -> int:
        """Materialized tile payload in bytes (∝ occupied blocks)."""
        return self.n_slots * self.tile * (self.tile // 32) * 4

    def alloc_slot(self, a: int, rb: int, cb: int) -> int:
        """Reserve the next slot for block (a, rb, cb), growing the device
        store to the next power-of-two capacity when full.  The tile
        content is whatever the caller scatters in afterwards."""
        slot = len(self.coords)
        cap = self.tiles.shape[0]
        if slot >= cap:
            grown = jnp.zeros(
                (_pow2_at_least(slot + 1), self.tile, self.tile // 32),
                dtype=jnp.uint32,
            )
            self.tiles = grown.at[:cap].set(self.tiles)
        self.coords.append((a, rb, cb))
        self.index[a].setdefault(rb, {})[cb] = slot
        return slot

    # ------------------------------------------------------------------ #
    @classmethod
    def from_dense(
        cls, T: np.ndarray, tile: int = DEFAULT_TILE
    ) -> "BlockSparseState":
        """Compact a dense (N, n, n) Boolean tensor (only occupied blocks
        are packed and slotted)."""
        T = np.asarray(T)
        state = cls(T.shape[-1], T.shape[0], tile)
        g = state.grid
        occ = T.reshape(T.shape[0], g, tile, g, tile).any(axis=(2, 4))
        payload = []
        for a, rb, cb in zip(*np.nonzero(occ)):
            state.coords.append((int(a), int(rb), int(cb)))
            state.index[int(a)].setdefault(int(rb), {})[int(cb)] = (
                len(state.coords) - 1
            )
            block = T[a, rb * tile : (rb + 1) * tile, cb * tile : (cb + 1) * tile]
            payload.append(_pack_words_np(block))
        if payload:
            cap = _pow2_at_least(len(payload))
            buf = np.zeros((cap, tile, tile // 32), dtype=np.uint32)
            buf[: len(payload)] = np.stack(payload)
            state.tiles = jnp.asarray(buf)
        return state

    @classmethod
    def from_graph(
        cls,
        graph: Graph,
        g: CNFGrammar,
        tile: int = DEFAULT_TILE,
        pad_to: int | None = None,
    ) -> "BlockSparseState":
        """Base state straight from the edge list — O(E) work and
        O(occupied blocks) memory, never materializing the dense tensor.
        This is the constructor the scale benchmarks drive: at n ≫ 10^4
        it is the only affordable entry point."""
        n = pad_to if pad_to is not None else padded_size(graph.n_nodes)
        state = cls(n, g.n_nonterms, tile)
        bufs: dict[tuple[int, int, int], np.ndarray] = {}
        for i, x, j in graph.edges:
            for a in g.term_prods.get(x, ()):
                key = (a, i // tile, j // tile)
                buf = bufs.get(key)
                if buf is None:
                    buf = bufs[key] = np.zeros(
                        (tile, tile // 32), dtype=np.uint32
                    )
                buf[i % tile, (j % tile) // 32] |= np.uint32(
                    1 << ((j % tile) % 32)
                )
        if bufs:
            keys = sorted(bufs)
            cap = _pow2_at_least(len(keys))
            payload = np.zeros((cap, tile, tile // 32), dtype=np.uint32)
            for slot, key in enumerate(keys):
                a, rb, cb = key
                state.coords.append(key)
                state.index[a].setdefault(rb, {})[cb] = slot
                payload[slot] = bufs[key]
            state.tiles = jnp.asarray(payload)
        return state

    def to_dense(self) -> np.ndarray:
        """Expand back to the dense (N, n, n) Boolean tensor (the masked
        engine contract speaks dense; the scale path never calls this)."""
        out = np.zeros((self.n_nonterms, self.n, self.n), dtype=bool)
        if not self.coords:
            return out
        host = np.asarray(self.tiles[: self.n_slots])
        B = self.tile
        for slot, (a, rb, cb) in enumerate(self.coords):
            out[a, rb * B : (rb + 1) * B, cb * B : (cb + 1) * B] = (
                _unpack_words_np(host[slot])
            )
        return out

    def pairs_for(
        self, a: int, i: int, nonterm_rows: bool = False
    ) -> set[tuple[int, int]]:
        """Debug/bench helper: nonzero (i, j) pairs of nonterminal ``a``
        (all rows when ``nonterm_rows``; row ``i`` otherwise) read from
        the packed tiles without densifying the whole state."""
        out: set[tuple[int, int]] = set()
        B = self.tile
        host = np.asarray(self.tiles[: self.n_slots])
        for rb, row in self.index[a].items():
            if not nonterm_rows and rb != i // B:
                continue
            for cb, slot in row.items():
                bits = _unpack_words_np(host[slot])
                rows = range(B) if nonterm_rows else [i % B]
                for r in rows:
                    for c in np.nonzero(bits[r])[0]:
                        out.add((rb * B + r, cb * B + int(c)))
        return out


# ---------------------------------------------------------------------- #
# The jitted contraction step: one bucket of occupied tile pairs.
# ---------------------------------------------------------------------- #

_SHIFTS = np.arange(32, dtype=np.uint32)  # numpy: importing starts no backend


@partial(jax.jit, static_argnames=("n_out", "use_kernel"))
def _contract_chunk(
    tiles: jnp.ndarray,  # (S, B, Bw) uint32 slot store
    l_idx: jnp.ndarray,  # (p,) int32 lhs slot per pair (pad: 0)
    r_idx: jnp.ndarray,  # (p,) int32 rhs slot per pair (pad: 0)
    seg: jnp.ndarray,  # (p,) int32 output segment per pair (pad: n_out)
    out_slot: jnp.ndarray,  # (n_out,) int32 existing slot per output (or 0)
    out_exists: jnp.ndarray,  # (n_out,) bool — out_slot valid?
    n_out: int,
    use_kernel: bool,
):
    """OR of per-pair tile products per output block, merged with the
    existing tile: returns ``(new (n_out, B, Bw), changed (n_out,),
    nonzero (n_out,))``.  Pad pairs point at segment ``n_out`` (dropped);
    pad outputs simply come back all-zero/unchanged."""
    from repro.kernels import ops as _kops
    from repro.kernels import ref as _kref

    lhs = tiles[l_idx]
    rhs = tiles[r_idx]
    prod = _kops.tile_bitmm(lhs, rhs) if use_kernel else _kref.bitmm_ref(lhs, rhs)
    # segment-OR on packed words: unpack to 0/1 bytes (segment_max has no
    # bitwise-OR sibling; max over {0,1} IS the OR), reduce, repack.
    bits = ((prod[..., None] >> _SHIFTS) & jnp.uint32(1)).astype(jnp.uint8)
    merged = jax.ops.segment_max(bits, seg, num_segments=n_out + 1)[:n_out]
    packed = (merged.astype(jnp.uint32) << _SHIFTS).sum(-1, dtype=jnp.uint32)
    old = jnp.where(out_exists[:, None, None], tiles[out_slot], jnp.uint32(0))
    new = old | packed
    changed = jnp.any(new != old, axis=(1, 2))
    nonzero = jnp.any(new != jnp.uint32(0), axis=(1, 2))
    return new, changed, nonzero


# ---------------------------------------------------------------------- #
# The host-driven fixpoint.
# ---------------------------------------------------------------------- #


def _activate(
    state: BlockSparseState,
    blk: int,
    active: set[int],
    to_expand: list[int],
    frontier: set[int],
) -> None:
    """Bring row-block ``blk`` into the active set: queue its occupied
    columns for reachability expansion and put its slots on the frontier —
    their lhs pairs have never been contracted under this mask, so the
    frontier filter must not skip them."""
    active.add(blk)
    to_expand.append(blk)
    for idx_a in state.index:
        row = idx_a.get(blk)
        if row:
            frontier.update(row.values())


def _blocksparse_fixpoint(
    state: BlockSparseState,
    tables: ProductionTables,
    active: set[int],
    to_expand: list[int],
    block_open: np.ndarray,
    capacity: int,
    max_iters: int | None,
    use_kernel: bool,
    iter_hook,
) -> tuple[bool, int]:
    """Run the block-sparse closure to fixpoint (or the first capacity
    overflow) in place; returns the overflow flag and the iterations run.

    ``active``/``to_expand`` carry the seed row-blocks (see
    :func:`_activate`); ``block_open[b]`` is False for blocks whose every
    row is frozen (delta repair) — those are contracted *against* but
    never activated, the block-granular analog of the frozen-row mask.
    ``capacity`` counts slots (occupied blocks); ``capacity >= n`` means
    unbounded (the warm-restart ladder's top).
    """
    B, G, N = state.tile, state.grid, state.n_nonterms
    unbounded = capacity >= state.n
    prods = list(zip(tables.a_idx, tables.b_idx, tables.c_idx))
    # |V|^2 |N| divergence guard plus mask-expansion slack — the old
    # n*N + n cap could truncate deep derivations before the fixpoint
    # (see closure._iter_limit).
    limit = (
        max_iters
        if max_iters is not None
        else state.n * state.n * N + state.n
    )
    frontier: set[int] = set(range(state.n_slots))
    overflow = False
    it = 0
    while it < limit:
        it += 1
        # 1. expand the active row-block set along occupied blocks (the
        # block-level analog of the masked engines' reach expansion)
        while to_expand:
            rb = to_expand.pop()
            for idx_a in state.index:
                row = idx_a.get(rb)
                if not row:
                    continue
                for cb in row:
                    if cb not in active and block_open[cb]:
                        _activate(state, cb, active, to_expand, frontier)
        if not unbounded and state.n_slots > capacity:
            overflow = True
        changed_blocks = 0
        pairs: list[tuple[int, int, tuple[int, int, int]]] = []
        if not overflow:
            # 2. enumerate occupied (row-block, k-block)×(k-block,
            # col-block) pairs — only pairs with at least one frontier
            # operand can produce new bits (both-unchanged pairs were
            # contracted when an operand last changed)
            for a, b, c in prods:
                idx_b, idx_c = state.index[b], state.index[c]
                for rb in idx_b.keys() & active:
                    for kb, ls in idx_b[rb].items():
                        row_c = idx_c.get(kb)
                        if not row_c:
                            continue
                        for cb, rs in row_c.items():
                            if ls in frontier or rs in frontier:
                                pairs.append((ls, rs, (a, rb, cb)))
        if pairs:
            # 3. contract in bounded chunks; each chunk scatters before
            # the next gathers, so later pairs see earlier products
            # (Gauss–Seidel style — sound for a monotone closure and
            # strictly faster to converge than frozen-snapshot sweeps)
            new_frontier: set[int] = set()
            for lo in range(0, len(pairs), PAIR_CHUNK):
                chunk = pairs[lo : lo + PAIR_CHUNK]
                key_ids: dict[tuple[int, int, int], int] = {}
                seg = [key_ids.setdefault(k, len(key_ids)) for _, _, k in chunk]
                out_keys = list(key_ids)
                n_out = _pow2_at_least(len(out_keys))
                p_pad = _pow2_at_least(len(chunk))
                l_idx = np.zeros(p_pad, np.int32)
                r_idx = np.zeros(p_pad, np.int32)
                seg_arr = np.full(p_pad, n_out, np.int32)
                for p, (ls, rs, _) in enumerate(chunk):
                    l_idx[p], r_idx[p], seg_arr[p] = ls, rs, seg[p]
                out_slot = np.zeros(n_out, np.int32)
                out_exists = np.zeros(n_out, bool)
                for oi, (a, rb, cb) in enumerate(out_keys):
                    s = state.index[a].get(rb, {}).get(cb)
                    if s is not None:
                        out_slot[oi] = s
                        out_exists[oi] = True
                new_t, changed, nonzero = _contract_chunk(
                    state.tiles,
                    jnp.asarray(l_idx),
                    jnp.asarray(r_idx),
                    jnp.asarray(seg_arr),
                    jnp.asarray(out_slot),
                    jnp.asarray(out_exists),
                    n_out,
                    use_kernel,
                )
                changed = np.asarray(changed)
                nonzero = np.asarray(nonzero)
                # 4. two-phase allocation: products were computed first,
                # so all-zero results never occupy a slot
                alloc = [
                    (oi, key)
                    for oi, key in enumerate(out_keys)
                    if not out_exists[oi] and nonzero[oi]
                ]
                if not unbounded and state.n_slots + len(alloc) > capacity:
                    overflow = True
                    alloc = []  # keep existing-slot progress, drop growth
                rows, slots = [], []
                for oi in range(len(out_keys)):
                    if out_exists[oi] and changed[oi]:
                        rows.append(oi)
                        slots.append(int(out_slot[oi]))
                for oi, (a, rb, cb) in alloc:
                    rows.append(oi)
                    slots.append(state.alloc_slot(a, rb, cb))
                    # 5. newly-occupied-block detection: a fresh block may
                    # reach blocks the mask hasn't visited yet
                    if cb not in active and block_open[cb]:
                        _activate(state, cb, active, to_expand, new_frontier)
                if rows:
                    state.tiles = state.tiles.at[
                        jnp.asarray(slots, jnp.int32)
                    ].set(new_t[jnp.asarray(rows, jnp.int32)])
                    new_frontier.update(slots)
                    changed_blocks += len(rows)
                if overflow:
                    break
            frontier = new_frontier
        if iter_hook is not None:
            iter_hook(
                it, min(len(active) * B, state.n), changed_blocks, overflow
            )
        if overflow or (not pairs) or changed_blocks == 0:
            # fixpoint: nothing changed and nothing new activated (any
            # activation enqueues frontier slots, which produce pairs)
            if not overflow and to_expand:
                continue  # a just-allocated block still needs expansion
            break
    return overflow, it


def _rows_of_blocks(active: set[int], tile: int, n: int) -> np.ndarray:
    M = np.zeros(n, dtype=bool)
    for rb in active:
        M[rb * tile : (rb + 1) * tile] = True
    return M


# ---------------------------------------------------------------------- #
# Masked-engine wrappers (the PlanKey-facing contract).
# ---------------------------------------------------------------------- #


def _check_tile(n: int, tile: int) -> None:
    """Shape validation shared by the wrappers — before any shortcut, so
    an illegal tile fails loudly even for trivial grammars."""
    if tile <= 0 or tile % 32:
        raise ValueError(f"tile must be a positive multiple of 32: {tile}")
    if n % tile:
        raise ValueError(f"matrix size {n} is not a multiple of tile {tile}")


def masked_blocksparse_closure(
    T,
    tables: ProductionTables,
    src_mask,
    row_capacity: int = 128,
    tile: int = DEFAULT_TILE,
    max_iters: int | None = None,
    use_kernel: bool = True,
    iter_hook=None,
):
    """Source-restricted block-sparse closure with the standard masked
    contract: ``(T, M, overflow, iters)``, rows under ``M`` exact at fixpoint,
    monotone partial state + ``overflow=True`` when the occupied-block
    count outgrows ``row_capacity`` (reinterpreted as *block* capacity —
    the service's bucket ladder grows it exactly like row capacities).

    Host-driven: ``T`` is compacted to tiles, the fixpoint runs on the
    occupied-block lists, and the result densifies back.  ``iter_hook``
    is called directly per iteration with ``(iteration, active_rows,
    changed_blocks, overflow)`` — changed units are blocks here.
    """
    T_host = np.asarray(T)
    n = T_host.shape[-1]
    _check_tile(n, tile)
    if tables.n_prods == 0:
        return (jnp.asarray(T), jnp.ones((n,), jnp.bool_), jnp.bool_(False),
                jnp.int32(0))
    mask_host = np.asarray(src_mask)
    state = BlockSparseState.from_dense(T_host, tile)
    active: set[int] = set()
    to_expand: list[int] = []
    frontier: set[int] = set()  # _activate's additions are re-added below
    block_open = np.ones(state.grid, dtype=bool)
    for rb in {int(r) // tile for r in np.nonzero(mask_host)[0]}:
        _activate(state, rb, active, to_expand, frontier)
    overflow, iters = _blocksparse_fixpoint(
        state, tables, active, to_expand, block_open,
        row_capacity, max_iters, use_kernel, iter_hook,
    )
    M = _rows_of_blocks(active, tile, n) | mask_host
    return (
        jnp.asarray(state.to_dense()),
        jnp.asarray(M),
        jnp.bool_(overflow),
        jnp.int32(iters),
    )


def masked_blocksparse_repair_closure(
    T,
    tables: ProductionTables,
    src_mask,
    frozen_mask,
    row_capacity: int = 128,
    tile: int = DEFAULT_TILE,
    max_iters: int | None = None,
    use_kernel: bool = True,
    iter_hook=None,
):
    """Block-granular delta repair: seed blocks are reactivated from the
    non-frozen seed rows (insert = reactivate touched blocks), expansion
    skips fully-frozen blocks, and the returned mask excludes frozen rows
    (matching ``masked_repair_closure``'s ``M | (reach & ~frozen)``).

    Frozen rows stay bit-identical for free: tile products are subsets of
    the exact closure, and frozen rows already hold their exact closure
    bits, so the OR into a tile's frozen lanes adds nothing.  Delete-side
    ancestor eviction happens upstream in delta/repair.py at row
    granularity (strictly finer than blocks — sound either way).
    """
    T_host = np.asarray(T)
    n = T_host.shape[-1]
    _check_tile(n, tile)
    if tables.n_prods == 0:
        return (jnp.asarray(T), jnp.ones((n,), jnp.bool_), jnp.bool_(False),
                jnp.int32(0))
    frozen_host = np.asarray(frozen_mask)
    seed = np.asarray(src_mask) & ~frozen_host
    state = BlockSparseState.from_dense(T_host, tile)
    block_open = ~frozen_host.reshape(state.grid, tile).all(axis=1)
    active: set[int] = set()
    to_expand: list[int] = []
    frontier: set[int] = set()
    for rb in {int(r) // tile for r in np.nonzero(seed)[0]}:
        _activate(state, rb, active, to_expand, frontier)
    overflow, iters = _blocksparse_fixpoint(
        state, tables, active, to_expand, block_open,
        row_capacity, max_iters, use_kernel, iter_hook,
    )
    M = (_rows_of_blocks(active, tile, n) & ~frozen_host) | seed
    return (
        jnp.asarray(state.to_dense()),
        jnp.asarray(M),
        jnp.bool_(overflow),
        jnp.int32(iters),
    )


# ---------------------------------------------------------------------- #
# Standalone closure over the compacted state (the million-node path).
# ---------------------------------------------------------------------- #


def blocksparse_closure_state(
    graph: Graph,
    g: CNFGrammar,
    tile: int = DEFAULT_TILE,
    sources=None,
    use_kernel: bool = True,
    max_iters: int | None = None,
) -> BlockSparseState:
    """All-pairs (or source-restricted) closure computed *entirely* on the
    compacted state — the dense tensor is never built, so memory stays
    proportional to occupied blocks.  This is the entry point
    ``benchmarks/bench_scaling.py`` scales along the n × density grid."""
    state = BlockSparseState.from_graph(graph, g, tile)
    active: set[int] = set()
    to_expand: list[int] = []
    frontier: set[int] = set()
    block_open = np.ones(state.grid, dtype=bool)
    if sources is None:
        seed_blocks = {rb for idx_a in state.index for rb in idx_a}
    else:
        seed_blocks = {int(s) // tile for s in sources}
    for rb in seed_blocks:
        _activate(state, rb, active, to_expand, frontier)
    _blocksparse_fixpoint(
        state, tables := ProductionTables.from_grammar(g), active, to_expand,
        block_open, state.n, max_iters, use_kernel, None,
    )
    del tables
    return state
