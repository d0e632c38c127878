"""Batched single-/multi-source CFPQ serving.

``QueryEngine`` is bound to one graph and serves queries over any number of
grammars.  A batch is coalesced per (grammar, semantics): the union of all
requested source rows is computed in ONE masked-closure call (see
core/closure.py), then each request slices its rows out.  Per grammar the
engine keeps a *materialized* closure state ``(T, mask)`` — rows listed in
``mask`` are already exact — so repeated or overlapping queries against an
unchanged graph are pure row slices (no device work at all), and new
sources warm-start the monotone fixpoint from the cached state instead of
from T0.

Single-path queries (``semantics="single_path"``, paper Section 5) are
served the same way from a second materialized state per grammar: the
(N, n, n) f32 length matrix of core/semantics.py (``isfinite`` of it IS the
Boolean closure), maintained by masked single-path closures with the same
row-capacity bucket ladder, plus batched witness reconstruction
(``PathExtractor``) over the host copy at slice time.

Cache states reported per request:
  ``hit``   every requested row was already materialized;
  ``warm``  the masked closure ran, seeded from previous state;
  ``miss``  first closure for this (graph, grammar).

Graph edits committed through ``Graph.insert_edges`` / ``delete_edges`` (or
``QueryEngine.apply_delta``) advance the graph's version counter and are
ingested as *row-level repair* of the materialized states (delta/repair.py)
instead of dropping them; each ingested delta advances the engine epoch
(delta/txn.py).  Out-of-band edits (mutating ``graph.edges`` directly) are
still caught by a per-batch edge-set comparison — even when they coincide
with logged edits — and fall back to dropping every materialized state.
Compiled executables survive both paths — they depend only on the grammar
and padded size, not on the data.
"""
from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocksparse import (
    occupied_block_count,
    occupied_blocks_of_edges,
)
from repro.core.conjunctive import ConjunctiveGrammar, ConjunctiveTables
from repro.core.conjunctive import init_matrix as conj_init_matrix
from repro.core.conjunctive import init_matrix_rows as conj_init_matrix_rows
from repro.core.grammar import CNFGrammar
from repro.core.graph import Graph
from repro.core.matrices import (
    ProductionTables,
    init_matrix,
    init_matrix_entries,
    padded_size,
)
from repro.core.semantics import (
    DerivationIndex,
    PathExtractor,
    SAT_COUNT,
    base_lengths,
    count_base,
    count_base_rows,
)
from repro.delta.repair import (
    DeltaStats,
    localize_state,
    mirror_to_host,
    placement_of,
    plan_repair,
    repair_single_path_state,
    repair_state,
)
from repro.delta.txn import EpochClock, Snapshot
from repro.obs.instruments import EngineMetrics
from repro.obs.trace import NULL_TRACER, iteration_scope
from repro.shard.mesh import explicit_axes

from .config import EngineConfig
from .plan import (
    CompiledClosureCache,
    PlanKey,
    bucket_for,
    conj_engine_name,
    count_engine_name,
    mesh_key_of,
    repair_engine_name,
    sp_engine_name,
)
from .planner import PlanDecision, PlanFeatures, Planner
from .stats import QueryStats


#: cache states from warmest to coldest (``QueryStats.cache``)
_CACHE_ORDER = ("hit", "warm", "miss")


def grammar_key(g: CNFGrammar | ConjunctiveGrammar):
    """Value identity of a grammar (CNFGrammar itself is mutable).

    Conjunctive grammars key under a distinct leading tag with their full
    conjunct structure, so a CNF grammar and a conjunctive one can never
    collide even if their nonterminal/terminal tables coincide."""
    if isinstance(g, ConjunctiveGrammar):
        return (
            "conjunctive",
            g.nonterms,
            tuple(sorted(g.term_prods)),
            g.conj_prods,
        )
    return (
        tuple(g.nonterms),
        tuple(sorted((x, tuple(v)) for x, v in g.term_prods.items())),
        tuple(g.binary_prods),
        frozenset(g.nullable),
    )


@dataclass(frozen=True)
class Query:
    """One CFPQ request.

    ``sources=None`` asks for the all-pairs relation; otherwise only pairs
    whose source is listed are computed/returned.  ``semantics`` is
    ``"relational"`` (pair set), ``"single_path"`` (one witness path per
    pair, paper Section 5), ``"conjunctive"`` (upper-approximate
    intersection relations, paper Section 7 — requires a
    :class:`~repro.core.conjunctive.ConjunctiveGrammar`), or ``"count"``
    (per-pair path counts in the saturating semiring,
    ``repro.core.semantics.SAT_COUNT`` meaning "at least 2^32 - 1 paths"
    — requires an ordinary CNF grammar; results carry
    ``QueryResult.counts``).
    """

    grammar: CNFGrammar | ConjunctiveGrammar
    start: str
    sources: tuple[int, ...] | None = None
    semantics: str = "relational"


@dataclass
class QueryResult:
    query: Query
    pairs: set[tuple[int, int]]
    paths: dict[tuple[int, int], list[tuple[int, str, int]]] | None
    stats: QueryStats
    #: per-pair path counts (``semantics="count"`` only): values are
    #: exact below ``SAT_COUNT``; the sentinel means "at least that many"
    counts: dict[tuple[int, int], int] | None = None


@dataclass
class _GrammarState:
    grammar: CNFGrammar
    tables: ProductionTables
    T: jnp.ndarray | None = None  # (N, n, n) bool closure state
    T_host: np.ndarray | None = None  # host copy for slicing
    mask: np.ndarray | None = None  # rows of T that are exact
    # single-path state, cached next to the Boolean one: the (N, n, n) f32
    # length matrix (isfinite == the Boolean closure on masked rows) plus
    # its own row mask — the two semantics materialize independently.
    sp_L: jnp.ndarray | None = None
    sp_L_host: np.ndarray | None = None
    sp_mask: np.ndarray | None = None
    # counting state (semantics="count"), cached beside the other two: the
    # (N, n, n) uint32 path-count matrix in the saturating semiring, its
    # own row mask, and the base tensor the Jacobi recompute re-adds each
    # iteration (kept on device so warm closures don't rebuild it).
    cnt_C: jnp.ndarray | None = None
    cnt_C_host: np.ndarray | None = None
    cnt_mask: np.ndarray | None = None
    cnt_base: jnp.ndarray | None = None
    extractor: PathExtractor | None = None  # edge/production index cache
    # packed all-path enumeration index over the Boolean closure state;
    # invalidated whenever T_host changes (closure run or delta)
    deriv: DerivationIndex | None = None
    # witness memo keyed (start, i, j): valid as long as the graph and the
    # frozen annotations are — i.e. until the next ingested delta (warm
    # closure runs only add entries, they never rewrite frozen ones)
    sp_paths: dict = field(default_factory=dict)
    # planner-visible state metadata: where each cached tensor lives
    # ("local" | "sharded" | "none") — kept current across queries AND
    # repairs (repair localizes sharded states; recording that here is
    # what keeps the planner's cache-temperature feature from mis-costing
    # a just-evicted sharded state) — and which backend last served it.
    placement: str = "none"
    sp_placement: str = "none"
    cnt_placement: str = "none"
    served_by: str = ""
    sp_served_by: str = ""
    cnt_served_by: str = ""


class QueryEngine:
    """Batched CFPQ query service over one graph."""

    def __init__(
        self,
        graph: Graph,
        engine: str | None = None,
        plans: CompiledClosureCache | None = None,
        row_capacity: int | None = None,
        mesh=None,
        *,
        config: EngineConfig | None = None,
        tracer=None,
        metrics=None,
    ) -> None:
        legacy = {
            k: v
            for k, v in (
                ("engine", engine),
                ("row_capacity", row_capacity),
                ("mesh", mesh),
            )
            if v is not None
        }
        if config is not None and legacy:
            raise ValueError(
                "pass engine/mesh/row_capacity through EngineConfig, not "
                f"alongside config= (got both: {sorted(legacy)})"
            )
        if config is None:
            if legacy:
                # legacy kwarg spelling: honored (with the legacy default
                # backend, dense — not the planner) but deprecated
                warnings.warn(
                    "QueryEngine(graph, engine=..., mesh=..., "
                    "row_capacity=...) is deprecated; use "
                    "QueryEngine(graph, config=EngineConfig(...)) — "
                    "engine='auto' (the new default) routes through the "
                    "cost-based planner, backend strings stay valid as "
                    "explicit pins",
                    DeprecationWarning,
                    stacklevel=2,
                )
                config = EngineConfig(
                    engine=engine if engine is not None else "dense",
                    mesh=mesh,
                    row_capacity=(
                        row_capacity if row_capacity is not None else 128
                    ),
                )
            else:
                config = EngineConfig()
        if config.mesh is not None and not (
            {"data", "model"} <= set(config.mesh.axis_names)
        ):
            # fail fast with an actionable message — MeshPlan.from_mesh
            # would otherwise KeyError deep inside the first plan compile
            raise ValueError(
                "opt mesh must name 'data' and 'model' axes "
                f"(got {tuple(config.mesh.axis_names)})"
            )
        explicit = () if config.mesh is None else explicit_axes(config.mesh)
        if explicit:
            # the sharded closures place operands with
            # with_sharding_constraint, which refuses Explicit axes — and
            # would only say so inside the first plan compile
            raise ValueError(
                f"opt mesh axes must be Auto, but {explicit} are not; "
                "build the mesh with repro.shard.make_mesh (or "
                "jax.make_mesh(..., axis_types=(AxisType.Auto,) * 2))"
            )
        self.graph = graph
        self.config = config
        #: configured engine name — ``"auto"`` means planner-routed; the
        #: backend that actually served a request is in its stats
        self.engine = config.engine
        # Device mesh for sharded execution ("opt" pinned, or "auto" when
        # the planner picks the sharded executable): masked closures shard
        # the compacted row block over it (PlanKey carries its shape
        # identity); None runs everything single-device.
        self.mesh = config.mesh
        self._mesh_key = mesh_key_of(config.mesh)
        self.plans = plans if plans is not None else CompiledClosureCache()
        self.row_capacity = config.row_capacity
        # the cost-based executable chooser; a pinned backend bypasses the
        # cost model (planner.decide(pin=...)) but still records decisions
        self.planner = Planner(config.resolved_profile())
        self._pin = None if config.engine == "auto" else config.engine
        self.n = padded_size(graph.n_nodes)
        self._states: dict[tuple, _GrammarState] = {}
        self._edge_set = frozenset(graph.edges)  # content served last
        self._n_nodes = graph.n_nodes
        self._version = graph.version
        self.clock = EpochClock(version=graph.version)
        self.delta_stats = DeltaStats()  # cumulative over the engine's life
        # Reentrancy guard for the serving layer (repro.serve): cache and
        # state mutation is not atomic, so query_batch/apply_delta hold
        # this across their whole body.  An RLock, not a Lock — apply_delta
        # re-enters through _check_graph-triggered ingestion paths.
        self._lock = threading.RLock()
        # Observability (repro.obs, OBSERVABILITY.md): the tracer opens
        # planner.decide / closure.execute / delta.repair spans (nesting
        # under whatever span is current — the serving loop's window span
        # when driven through CFPQServer) and, when it wants iteration
        # events, routes the engine onto *instrumented* plan keys.  The
        # default NULL_TRACER records nothing and keeps every PlanKey
        # uninstrumented; ``metrics`` is a MetricsRegistry (the process
        # default when None) fed cache/closure/delta counters.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = EngineMetrics.on(metrics)

    def set_tracer(self, tracer) -> None:
        """Install a tracer after construction (the serving loop shares
        its tracer with the engine it drives)."""
        self.tracer = tracer if tracer is not None else NULL_TRACER

    def set_metrics(self, registry) -> None:
        """Re-point the engine's metric families at ``registry`` (the
        serving loop funnels engine counters into the registry its
        exposition endpoint serves)."""
        self.metrics = EngineMetrics.on(registry)

    # ------------------------------------------------------------------ #
    def query(self, q: Query, snapshot: Snapshot | None = None) -> QueryResult:
        return self.query_batch([q], snapshot=snapshot)[0]

    def query_batch(
        self,
        queries: list[Query],
        snapshot: Snapshot | None = None,
        stats_extra: dict | None = None,
    ) -> list[QueryResult]:
        """Serve a batch: one closure call per (grammar, semantics) group.

        ``snapshot`` (from :meth:`snapshot`) pins the epoch the caller
        expects to read; if a delta was committed since, the batch raises
        ``StaleSnapshotError`` instead of serving rows of a newer graph.
        ``stats_extra`` entries are merged into every result's stats — the
        async serving loop uses it to tag coalesced batches (flush reason,
        window size) atomically with the batch itself.  Results also carry
        ``batch_total`` (queries submitted together) and ``batch_groups``
        (closure-call groups they were sliced into).
        """
        with self._lock, self.tracer.span(
            "engine.read", cat="engine", batch=len(queries)
        ) as rsp:
            self._check_graph()
            self.clock.validate(snapshot)
            results: list[QueryResult | None] = [None] * len(queries)
            groups: dict[tuple, list[int]] = {}
            for qi, q in enumerate(queries):
                self.validate_query(q)
                groups.setdefault(
                    (grammar_key(q.grammar), q.semantics), []
                ).append(qi)
            for (gkey, semantics), qidx in groups.items():
                state = self._state_for(gkey, queries[qidx[0]].grammar)
                batch = [queries[i] for i in qidx]
                if semantics == "single_path":
                    outs = self._serve_single_path(state, batch)
                elif semantics == "count":
                    outs = self._serve_count(state, batch)
                else:  # relational and conjunctive share the bool-state path
                    outs = self._serve_relational(
                        state, batch, semantics=semantics
                    )
                for i, out in zip(qidx, outs):
                    results[i] = out
            for out in results:
                out.stats["batch_total"] = len(queries)  # type: ignore[union-attr]
                out.stats["batch_groups"] = len(groups)  # type: ignore[union-attr]
                if stats_extra:
                    out.stats.update(stats_extra)  # type: ignore[union-attr]
            if rsp:
                rsp.set(
                    semantics=",".join(sorted({sem for _, sem in groups})),
                    # the coldest group's cache state names the batch's
                    cache=max(
                        (out.stats.cache for out in results),  # type: ignore[union-attr]
                        key=_CACHE_ORDER.index, default="hit",
                    ),
                )
            return results  # type: ignore[return-value]

    # ------------------------------------------------------------------ #
    # Delta ingestion (serving layer of the delta subsystem; DELTA.md).
    # ------------------------------------------------------------------ #
    def snapshot(self) -> Snapshot:
        """Pin the current epoch for cross-batch read consistency."""
        with self._lock:  # (epoch, version) must not tear across a writer
            return self.clock.snapshot()

    def apply_delta(
        self,
        insert: list[tuple[int, str, int]] = (),
        delete: list[tuple[int, str, int]] = (),
    ) -> DeltaStats:
        """Commit edge edits and repair materialized closures in place.

        Deletions are applied first, then insertions; both are folded into
        one repair pass.  Returns this delta's repair stats (the engine
        also accumulates them into every result's stats).
        """
        insert, delete = list(insert), list(delete)
        with self._lock, self.tracer.span(
            "engine.write", cat="engine",
            inserted=len(insert), deleted=len(delete),
        ):
            self._check_graph()  # settle pending/out-of-band edits first
            if delete:
                self.graph.delete_edges(delete)
            if insert:
                self.graph.insert_edges(insert)
            if self.graph.version == self._version:
                return DeltaStats()  # edits were all no-ops
            return self._ingest_delta()

    def _ingest_delta(self, delta=None) -> DeltaStats:
        """Fold the graph's edge log since the last-served version into
        row-level repair of every cached grammar state."""
        g = self.graph
        if delta is None:
            delta = g.delta_since(self._version)
        stats = DeltaStats()
        if delta:
            # context-managed so repair fixpoints started inside nest
            # under this span (planner.decide / closure.execute parents)
            with self.tracer.span(
                "delta.repair",
                cat="engine",
                inserted=len(delta.inserted),
                deleted=len(delta.deleted),
            ) as dsp:
                with self.tracer.span("repair.plan", cat="engine") as psp:
                    plan = plan_repair(g, delta, self.n)
                    psp.set(evict=int(plan.evict.sum()),
                            affected=int(plan.affected.sum()))
                for state in self._states.values():
                    state.extractor = None  # edge indices are stale
                    state.deriv = None  # packed closure index too
                    state.sp_paths.clear()  # memoized witnesses may walk them

                    if isinstance(state.tables, ConjunctiveTables):
                        # conjunctive states have their own delta contract
                        # (DELTA.md#conjunctive-states): insert-only = warm
                        # re-seed, any delete = full drop (AND is
                        # non-monotone under row eviction)
                        self._repair_conjunctive(state, delta, plan, stats)
                        continue

                    def base_entries(idx, grammar=state.grammar):
                        return init_matrix_entries(g, grammar, idx)

                    if state.T is not None and state.mask is not None:
                        T_np = (
                            state.T_host
                            if state.T_host is not None
                            else mirror_to_host(state.T, self.tracer)
                        )

                        def run(T_dev, seed, frozen, tables=state.tables,
                                st=state):
                            seed_np = np.asarray(seed)
                            d = self._decide(
                                st, seed_np, seed_np, "relational", "warm",
                                repair=True,
                            )
                            st.served_by = d.engine
                            return self._run_fixpoint(
                                tables, T_dev, seed, frozen, decision=d
                            )[:3]  # repair never falls back; drop the event

                        T_host, T_dev, mask_new, st = repair_state(
                            T_np, state.T, np.asarray(state.mask), plan,
                            base_entries, run, self.tracer,
                        )
                        state.T = T_dev
                        state.T_host = T_host
                        state.mask = mask_new
                        # repair entrypoints localize sharded states (eviction
                        # to one device) and run single-device executables —
                        # record the post-repair placement so the planner's
                        # cache-temperature/placement feature doesn't mis-cost
                        # the just-evicted state on the next query
                        state.placement = placement_of(T_dev)
                        stats.merge(st)
                    if state.sp_L is not None and state.sp_mask is not None:
                        # single-path states repair too: insertions warm-start
                        # the min-plus row repair (frozen rows bit-identical),
                        # deletions evict affected rows to base lengths.
                        L_np = (
                            state.sp_L_host
                            if state.sp_L_host is not None
                            else mirror_to_host(state.sp_L, self.tracer)
                        )

                        def run_sp(L_dev, seed, frozen, tables=state.tables,
                                   st=state):
                            seed_np = np.asarray(seed)
                            d = self._decide(
                                st, seed_np, seed_np, "single_path", "warm",
                                repair=True,
                            )
                            st.sp_served_by = d.engine
                            return self._run_fixpoint(
                                tables, L_dev, seed, frozen,
                                semantics="single_path", decision=d,
                            )[:3]

                        L_host, L_dev, sp_mask, st = repair_single_path_state(
                            L_np, state.sp_L, np.asarray(state.sp_mask), plan,
                            base_entries, run_sp, self.tracer,
                        )
                        state.sp_L = L_dev
                        state.sp_L_host = L_host
                        state.sp_mask = sp_mask
                        state.sp_placement = placement_of(L_dev)
                        stats.merge(st)
                    if state.cnt_C is not None and state.cnt_mask is not None:
                        # counting states have their own delta contract
                        # (DELTA.md#count-states): insert-only = recount
                        # affected rows from the new base, any delete =
                        # full drop
                        self._repair_count(state, delta, plan, stats)
                dsp.set(**stats.as_dict())
            self.metrics.observe_delta(stats)
        self._version = g.version
        self._edge_set = frozenset(g.edges)
        self.delta_stats.merge(stats)
        self.clock.advance(g.version)
        self.metrics.delta_epoch.set(self.clock.epoch)
        return stats

    def _repair_conjunctive(
        self, state: _GrammarState, delta, plan, stats: DeltaStats
    ) -> None:
        """Apply one delta to a cached conjunctive state (the conjunctive
        side of the delta contract, DELTA.md#conjunctive-states).

        **Any deletion drops the whole state.**  The row-repair machinery
        of the other semantics evicts affected rows and recontracts them
        against trusted frozen rows — but under AND a frozen row is not
        trustworthy context: removing one conjunct's support can retract
        entries in rows the reverse-reachability blast radius never
        touches through the *other* conjuncts' dependencies, so there is
        no sound frozen set short of everything.  Dropping is principled,
        not lazy.

        **Insert-only deltas repair by warm re-seed.**  Inserts only grow
        the fixpoint (AND of monotone products is monotone), so the cached
        state is a valid warm start: OR the new base edges into the
        inserted-source rows, then re-enter the ordinary masked
        conjunctive closure seeded with the affected rows (ancestors of
        inserted sources) plus the sources themselves.  Previously-exact
        rows re-converge instantly; no repair-variant executable exists
        or is needed.
        """
        if state.T is None or state.mask is None:
            return
        if delta.deleted:
            stats.rows_evicted += int(np.asarray(state.mask).sum())
            stats.conj_drops += 1
            state.T = state.T_host = state.mask = None
            state.placement = "none"
            state.served_by = ""
            return
        mask = np.array(state.mask, copy=True)
        state_dev = localize_state(state.T)
        T_host = (
            state.T_host if state.T_host is not None
            else mirror_to_host(state.T, self.tracer)
        )
        if plan.ins_sources.any():
            # base-row surgery: OR the new edges into the inserted-source
            # rows (entries only grow — no eviction on the insert path)
            idx = np.nonzero(plan.ins_sources)[0]
            base = conj_init_matrix_rows(
                self.graph, state.grammar, idx, pad_to=self.n
            )
            patch = T_host[:, idx, :] | base
            jidx = jnp.asarray(idx.astype(np.int32))
            state_dev = state_dev.at[:, jidx, :].set(jnp.asarray(patch))
        seed = (plan.affected & mask) | plan.ins_sources
        if seed.any():
            d = self._decide(state, seed, seed, "conjunctive", "warm")
            state.served_by = d.engine
            state_dev, M, calls, _ = self._run_fixpoint(
                state.tables, state_dev, seed,
                semantics="conjunctive", decision=d,
            )
            mask |= M
            stats.rows_repaired += int(np.asarray(M).sum())
            stats.repair_iters += calls
            stats.conj_repairs += 1
        state.T = state_dev
        state.T_host = mirror_to_host(state_dev, self.tracer)
        state.mask = mask
        state.placement = placement_of(state_dev)

    def _repair_count(
        self, state: _GrammarState, delta, plan, stats: DeltaStats
    ) -> None:
        """Apply one delta to a cached counting state (the count side of
        the delta contract, DELTA.md#count-states).

        **Any deletion drops the whole state.**  A deletion can retract
        counts anywhere in the blast radius and there is no subtractive
        inverse in the saturating semiring (a saturated entry forgets how
        much of it the deleted edge carried), so the row-repair machinery
        has nothing sound to freeze against.  The state recounts from
        scratch on next touch.

        **Insert-only deltas recount affected rows.**  The Boolean warm
        re-seed (OR the new base edges into cached rows, re-close) is
        unsound for counts — a count row is a *sum*, not a set, so
        folding new base entries into already-accumulated counts double
        counts every path that existed before the delta.  Instead:
        rebuild the base tensor, reset every affected cached row to its
        new base row, and re-enter the masked counting closure seeded
        with those rows.  Unaffected mask rows cannot reach an inserted
        edge, so their counts are provably unchanged and they re-enter
        the fixpoint as exact, Jacobi-stable context.
        """
        if delta.deleted:
            stats.rows_evicted += int(np.asarray(state.cnt_mask).sum())
            stats.count_drops += 1
            state.cnt_C = state.cnt_C_host = state.cnt_mask = None
            state.cnt_base = None
            state.cnt_placement = "none"
            state.cnt_served_by = ""
            return
        mask = np.array(state.cnt_mask, copy=True)
        state.cnt_base = count_base(self.graph, state.grammar, pad_to=self.n)
        C_dev = localize_state(state.cnt_C)
        reset = (plan.affected & mask) | plan.ins_sources
        if reset.any():
            idx = np.nonzero(reset)[0]
            rows = count_base_rows(
                self.graph, state.grammar, idx, pad_to=self.n
            )
            jidx = jnp.asarray(idx.astype(np.int32))
            C_dev = C_dev.at[:, jidx, :].set(jnp.asarray(rows))
            d = self._decide(state, reset, reset, "count", "warm")
            state.cnt_served_by = d.engine
            C_dev, M, calls, _ = self._run_fixpoint(
                state.tables, C_dev, reset,
                semantics="count", decision=d, cnt_base=state.cnt_base,
            )
            mask |= M
            stats.rows_repaired += int(np.asarray(M).sum())
            stats.repair_iters += calls
            stats.count_repairs += 1
        state.cnt_C = C_dev
        state.cnt_C_host = mirror_to_host(C_dev, self.tracer)
        state.cnt_mask = mask
        state.cnt_placement = placement_of(C_dev)

    # ------------------------------------------------------------------ #
    def _check_graph(self) -> None:
        """Reconcile with the graph: logged edits repair row-wise; any edit
        the log cannot account for (``graph.edges`` touched directly) drops
        every materialized state.  The repair path is taken only when the
        current edge set is exactly the last-served set transformed by the
        log — an out-of-band edit concurrent with logged edits therefore
        still forces full invalidation instead of being masked."""
        g = self.graph
        actual = frozenset(g.edges)
        if g.version != self._version:
            try:
                delta = g.delta_since(self._version)
            except ValueError:
                # Log compacted past our version: the edit set is unknowable.
                # If the content still equals what we served (the compacted
                # tail was a net no-op), just resync the version; otherwise
                # fall through to full invalidation below.
                delta = None
                if g.n_nodes == self._n_nodes and actual == self._edge_set:
                    self._version = g.version
                    return
            if delta is not None:
                expected = (
                    self._edge_set | set(delta.inserted)
                ) - set(delta.deleted)
                if g.n_nodes == self._n_nodes and actual == expected:
                    self._ingest_delta(delta)
                    return
        if actual != self._edge_set or g.n_nodes != self._n_nodes:
            self._states.clear()  # out-of-band edit: full invalidation
            self._edge_set = actual
            self._n_nodes = g.n_nodes
            self._version = g.version
            self.n = padded_size(g.n_nodes)
            self.clock.advance(g.version)

    def _state_for(self, gkey: tuple, g) -> _GrammarState:
        state = self._states.get(gkey)
        if state is None:
            tables = (
                ConjunctiveTables.from_grammar(g)
                if isinstance(g, ConjunctiveGrammar)
                else ProductionTables.from_grammar(g)
            )
            state = _GrammarState(g, tables)
            self._states[gkey] = state
        return state

    def validate_query(self, q: Query) -> None:
        """Raise ``ValueError`` for a malformed query.  ``query_batch``
        validates every member; admission layers (repro.serve) call this
        per query at submit time so one bad request is rejected at its
        caller instead of failing the whole coalesced batch."""
        if q.semantics not in (
            "relational", "single_path", "conjunctive", "count"
        ):
            raise ValueError(f"unknown semantics {q.semantics!r}")
        conj_grammar = isinstance(q.grammar, ConjunctiveGrammar)
        if conj_grammar != (q.semantics == "conjunctive"):
            raise ValueError(
                f"semantics {q.semantics!r} does not match grammar type "
                f"{type(q.grammar).__name__} (ConjunctiveGrammar queries "
                'must use semantics="conjunctive" and vice versa)'
            )
        for m in q.sources or ():
            if not 0 <= m < self.graph.n_nodes:
                raise ValueError(f"source {m} outside graph")

    # ------------------------------------------------------------------ #
    def _need_mask(self, batch: list[Query]) -> np.ndarray | None:
        """Union of requested source rows; None means all-pairs."""
        need = np.zeros(self.n, dtype=bool)
        for q in batch:
            if q.sources is None:
                return None
            need[list(q.sources)] = True
        return need

    def _place_state(self, T, sharded: bool):
        """Match a cached state's placement to the executable consuming it.

        Sharded (opt-with-mesh) executables expect the state spread over
        the mesh: a state committed elsewhere (e.g. localized by a repair)
        is pulled through the host and handed over uncommitted — the
        executable re-places it under its own sharding.  Single-device
        executables (every repair, or opt without a mesh) get a
        mesh-sharded state localized by the one shared helper
        (:func:`repro.delta.repair.localize_state`; repair entrypoints
        have usually done this already).  Either way the round-trip only
        happens when placement actually changes.
        """
        if self.mesh is None or not isinstance(T, jax.Array):
            return T
        if not sharded:
            return localize_state(T)
        if T.sharding.device_set != set(self.mesh.devices.flat):
            return np.asarray(T)
        return T

    def _decide(
        self,
        state: _GrammarState,
        seed: np.ndarray,
        new: np.ndarray,
        semantics: str,
        cache: str,
        repair: bool = False,
    ) -> PlanDecision:
        """Build the planner features for one closure call and decide.

        Every feature is something the engine already has on hand: the
        seed mask (warm rows + requested rows), how many of those are new,
        graph density, grammar size, the cached state's temperature and
        placement, and whether a mesh is available.
        """
        if semantics == "single_path":
            placement = state.sp_placement
        elif semantics == "count":
            placement = state.cnt_placement
        else:
            placement = state.placement
        tables = state.tables
        f = PlanFeatures(
            n=self.n,
            seed_rows=int(seed.sum()),
            new_rows=int(new.sum()),
            density=len(self.graph.edges) / max(self.graph.n_nodes, 1),
            n_prods=max(tables.n_prods, 1),
            n_nonterms=tables.n_nonterms,
            semantics=semantics,
            repair=repair,
            cache=cache,
            placement=placement,
            mesh_devices=(
                int(self.mesh.devices.size) if self.mesh is not None else 0
            ),
            # label-blind base-graph occupancy (O(E) host count) prices the
            # blocksparse candidate; the padded n is always a multiple of
            # every legal tile, so eligibility only needs the count itself
            occupied_blocks=occupied_blocks_of_edges(
                self.n, self.graph.edges, self.config.tile
            ),
            tile=self.config.tile,
            conjuncts=getattr(tables, "n_conjuncts", 0),
        )
        return self.planner.decide(
            f, pin=self._pin, min_capacity=self.row_capacity
        )

    def _run_fixpoint(
        self,
        tables: ProductionTables,
        T,
        seed: np.ndarray,
        frozen: np.ndarray | None = None,
        semantics: str = "relational",
        decision: PlanDecision | None = None,
        cnt_base=None,
    ):
        """Run the masked closure to completion from ``seed`` rows, growing
        the capacity bucket on overflow (monotone warm restarts, so no work
        is lost).  With ``frozen`` (delta repair) the run uses the repair
        variant: frozen rows are contracted against but never recomputed,
        so capacity tracks the edit's blast radius, not the cache size.
        ``semantics="single_path"`` runs the length-annotated closures on
        the f32 state instead (same signatures, same bucket ladder).
        With a mesh (opt backend) the non-repair executables are sharded —
        repair always runs the single-device path, so sharded states are
        localized first and re-shard on the next query.

        ``decision`` names the executable the planner picked; every
        capacity overflow is a fallback observation point — when
        :meth:`Planner.should_fallback` fires, the *remaining* closure is
        re-dispatched onto the decision's fallback backend at full
        capacity through the same monotone warm restart that grows
        buckets (all masked engines share the ``(T, mask)`` signature, so
        switching backends mid-closure is just a different executable on
        the same state).  At most one fallback per run; pinned decisions
        and repairs never fall back.

        With a live ``closure.execute`` span, the executables' own
        iteration counts are read back and summed over the warm restarts
        into its ``iterations`` attribute; untraced, nothing more is read.

        Returns ``(T_device, M_host, n_calls, fallback_event)``."""
        mask = np.asarray(seed)
        repair = frozen is not None
        single_path = semantics == "single_path"
        if decision is None:  # direct callers (tests/tools) skip planning
            decision = self.planner.decide(
                PlanFeatures(
                    n=self.n,
                    seed_rows=int(mask.sum()),
                    new_rows=int(mask.sum()),
                    density=0.0,
                    n_prods=max(tables.n_prods, 1),
                    n_nonterms=tables.n_nonterms,
                    semantics=semantics,
                    repair=repair,
                    conjuncts=getattr(tables, "n_conjuncts", 0),
                ),
                pin=self._pin or "dense",
                min_capacity=self.row_capacity,
            )
        # the decision names the backend; PlanKey aliasing still applies
        # (bitpacked single-path keys dense, opt repair keys bitpacked,
        # conjunctive collapses onto its dense/bitpacked executables)
        if single_path:
            eng_name = sp_engine_name(decision.engine, repair=repair)
        elif semantics == "conjunctive":
            eng_name = conj_engine_name(decision.engine)
        elif semantics == "count":
            eng_name = count_engine_name(decision.engine)
        elif repair:
            eng_name = repair_engine_name(decision.engine)
        else:
            eng_name = decision.engine
        # every repair executable is single-device; only the masked opt
        # query path carries the mesh identity
        mesh_k = self._mesh_key if (not repair and eng_name == "opt") else ()
        T = self._place_state(T, sharded=bool(mesh_k))
        n_frozen = 0
        cap_c = 0
        if repair:
            frozen_dev = jnp.asarray(frozen)
            n_frozen = int(np.asarray(frozen).sum())
        cap = bucket_for(max(decision.row_capacity, int(mask.sum())), self.n)
        if repair and (
            single_path or eng_name not in ("bitpacked", "blocksparse")
        ):
            # dense/frontier (and every single-path) repair compacts the
            # contraction axis over active + frozen rows; the Boolean
            # bitpacked repair (also serving opt) contracts full packed
            # words instead
            cap_c = bucket_for(max(cap, int(mask.sum()) + n_frozen), self.n)
        calls = 0
        iters = 0
        fallback_event: dict | None = None
        tracer = self.tracer
        with tracer.span(
            "closure.execute",
            cat="engine",
            engine=eng_name,
            semantics=semantics,
            repair=repair,
            seed_rows=int(mask.sum()),
        ) as csp:
            while True:
                # iteration events need an instrumented executable — a
                # distinct PlanKey, so the untraced path keeps running the
                # bit-identical uninstrumented build.  The opt closures
                # take no hook (SPMD callbacks fire per device).
                instrumented = (
                    tracer.wants_iterations and eng_name != "opt"
                )
                misses_before = self.plans.stats.compile_misses
                exe = self.plans.get(
                    PlanKey(
                        tables,
                        eng_name,
                        self.n,
                        cap,
                        repair=repair,
                        ctx_capacity=cap_c,
                        semantics=semantics,
                        mesh=mesh_k,
                        instrumented=instrumented,
                        tile=(
                            self.config.tile
                            if eng_name == "blocksparse"
                            else 0
                        ),
                    ),
                    mesh=self.mesh,
                    provenance="pinned" if decision.pinned else "planned",
                )
                self.metrics.observe_cache(
                    hit=self.plans.stats.compile_misses == misses_before
                )
                with iteration_scope(
                    tracer.iteration_sink(csp) if instrumented else None
                ):
                    if repair:
                        T, M, overflow, it = exe(
                            T, jnp.asarray(mask), frozen_dev
                        )
                    elif semantics == "count":
                        # counting executables take the base tensor as an
                        # extra operand (the Jacobi recompute re-adds it)
                        T, M, overflow, it = exe(
                            T, cnt_base, jnp.asarray(mask)
                        )
                    else:
                        T, M, overflow, it = exe(T, jnp.asarray(mask))
                    calls += 1
                    done = not bool(overflow)
                    if csp:
                        iters += int(it)
                    if done:
                        break
                mask = np.asarray(M)  # monotone warm restart, larger capacity
                grown = int(mask.sum())
                if fallback_event is None:
                    trigger = self.planner.should_fallback(
                        decision, grown, self.n, calls
                    )
                    if trigger is not None:
                        # the pick's assumptions were violated: re-dispatch
                        # the remaining closure onto the fallback executable
                        # at full capacity (no work lost — same warm restart)
                        fb = decision.fallback_engine
                        fallback_event = {
                            "from": eng_name,
                            "to": fb,
                            "trigger": trigger,
                            "at_call": calls,
                            "active_rows": grown,
                        }
                        csp.add_event(
                            "planner.fallback",
                            tracer.clock(),
                            **fallback_event,
                        )
                        if single_path:
                            eng_name = sp_engine_name(fb, repair=False)
                        elif semantics == "conjunctive":
                            eng_name = conj_engine_name(fb)
                        elif semantics == "count":
                            eng_name = count_engine_name(fb)
                        else:
                            eng_name = fb
                        mesh_k = (
                            self._mesh_key if eng_name == "opt" else ()
                        )
                        T = self._place_state(T, sharded=bool(mesh_k))
                        cap = self.n
                        self.planner.note_fallback()
                        continue
                # overflow implies the active set outgrew cap or (repair) the
                # context outgrew cap_c, so at least one bucket grows strictly.
                # Blocksparse overflows on *occupied blocks* (summed over
                # nonterminals), which the mask's row count need not exceed —
                # double unconditionally so the ladder always terminates
                # (capacity >= n runs unbounded).
                if eng_name == "blocksparse":
                    cap = bucket_for(max(2 * cap, grown), self.n)
                else:
                    cap = bucket_for(max(cap, grown), self.n)
                if cap_c:
                    cap_c = bucket_for(max(cap_c, grown + n_frozen), self.n)
                csp.add_event(
                    "warm_restart",
                    tracer.clock(),
                    capacity=cap,
                    active_rows=grown,
                    at_call=calls,
                )
            csp.set(calls=calls, active_rows=int(np.asarray(M).sum()),
                    iterations=iters)
        self.metrics.observe_closure(eng_name, calls)
        return T, np.asarray(M), calls, fallback_event

    def _ensure_rows(
        self,
        state: _GrammarState,
        batch: list[Query],
        semantics: str = "relational",
    ) -> tuple[str, PlanDecision | None, dict | None]:
        """Materialize closure rows covering the batch (the Boolean state,
        or the f32 length state for ``semantics="single_path"``); returns
        ``(cache_status, decision, fallback_event)`` — the latter two are
        None on a pure cache hit (no closure ran, nothing was planned)."""
        single_path = semantics == "single_path"
        count = semantics == "count"
        need = self._need_mask(batch)
        if need is None:
            need = np.ones(self.n, dtype=bool)
            need[self.graph.n_nodes :] = False  # padding rows are empty
        if single_path:
            mask, cur = state.sp_mask, state.sp_L
        elif count:
            mask, cur = state.cnt_mask, state.cnt_C
        else:
            mask, cur = state.mask, state.T
        if mask is not None and (need <= mask).all():
            return "hit", None, None
        status = "miss" if cur is None else "warm"
        if cur is None:
            if semantics == "conjunctive":
                cur = conj_init_matrix(self.graph, state.grammar, pad_to=self.n)
            elif count:
                state.cnt_base = count_base(
                    self.graph, state.grammar, pad_to=self.n
                )
                cur = state.cnt_base
            else:
                cur = init_matrix(self.graph, state.grammar, pad_to=self.n)
                if single_path:
                    cur = base_lengths(cur)
            mask = np.zeros(self.n, dtype=bool)
        mask = np.asarray(mask)
        with self.tracer.span(
            "planner.decide", cat="engine", semantics=semantics, cache=status
        ) as psp:
            decision = self._decide(
                state, mask | need, need & ~mask, semantics, status
            )
            psp.set(route=decision.label, pinned=decision.pinned)
        out, M, _, fb = self._run_fixpoint(
            state.tables, cur, mask | need, semantics=semantics,
            decision=decision,
            cnt_base=state.cnt_base if count else None,
        )
        served = fb["to"] if fb else decision.engine
        if single_path:
            state.sp_L, state.sp_mask = out, M
            state.sp_L_host = mirror_to_host(out, self.tracer)
            state.sp_placement = placement_of(out)
            state.sp_served_by = served
        elif count:
            state.cnt_C = out
            state.cnt_C_host = mirror_to_host(out, self.tracer)
            state.cnt_mask = M
            state.cnt_placement = placement_of(out)
            state.cnt_served_by = served
        else:
            state.T, state.mask = out, M
            state.T_host = mirror_to_host(out, self.tracer)
            state.placement = placement_of(out)
            state.served_by = served
            state.deriv = None  # packed index is a view of stale T_host
            if served == "blocksparse":
                self.metrics.observe_blocksparse(
                    occupied_block_count(state.T_host, self.config.tile)
                )
        return status, decision, fb

    def _serve_relational(
        self,
        state: _GrammarState,
        batch: list[Query],
        semantics: str = "relational",
    ) -> list[QueryResult]:
        """Serve a bool-state batch: the relational fast path, and (with
        ``semantics="conjunctive"``) the conjunctive one — identical
        caching/slicing over the (N, n, n) bool state, different closure
        executables underneath (plan.CONJ_ENGINES)."""
        t0 = time.perf_counter()
        status, decision, fb = self._ensure_rows(
            state, batch, semantics=semantics
        )
        latency = time.perf_counter() - t0
        nn = self.graph.n_nodes
        T = state.T_host
        stats = QueryStats(
            latency_s=latency,
            cache=status,
            # the backend that materialized the served rows — on a cache
            # hit that is whoever ran last, not whoever would run next
            engine=state.served_by or self.engine,
            semantics=semantics,
            batched_with=len(batch),
            active_rows=int(state.mask.sum()),
            epoch=self.clock.epoch,
            planner=decision.to_dict() if decision is not None else None,
            fallback=fb,
        )
        stats.update(self.delta_stats.as_dict())
        stats.update(self.plans.stats.as_dict())
        outs = []
        with self.tracer.span("engine.slice", cat="engine") as ssp:
            for q in batch:
                a0 = state.grammar.index_of(q.start)
                rows = range(nn) if q.sources is None else q.sources
                pairs: set[tuple[int, int]] = set()
                for i in rows:
                    pairs.update(
                        (i, int(j)) for j in np.nonzero(T[a0, i, :nn])[0]
                    )
                if q.start in state.grammar.nullable:
                    pairs |= {(m, m) for m in rows}  # empty path m pi m
                outs.append(QueryResult(q, pairs, None, stats.copy()))
            ssp.set(pairs=sum(len(o.pairs) for o in outs))
        return outs

    def _serve_count(
        self, state: _GrammarState, batch: list[Query]
    ) -> list[QueryResult]:
        """Serve a counting batch: identical caching/slicing over the
        (N, n, n) uint32 state (plan.COUNT_ENGINES underneath).  Counts
        are exact below :data:`~repro.core.semantics.SAT_COUNT`; the
        sentinel means "at least that many paths"."""
        t0 = time.perf_counter()
        status, decision, fb = self._ensure_rows(
            state, batch, semantics="count"
        )
        latency = time.perf_counter() - t0
        nn = self.graph.n_nodes
        C = state.cnt_C_host
        active = int(state.cnt_mask.sum())
        self.metrics.observe_count_state(active)
        stats = QueryStats(
            latency_s=latency,
            cache=status,
            engine=state.cnt_served_by or self.engine,
            semantics="count",
            batched_with=len(batch),
            active_rows=active,
            epoch=self.clock.epoch,
            planner=decision.to_dict() if decision is not None else None,
            fallback=fb,
        )
        stats.update(self.delta_stats.as_dict())
        stats.update(self.plans.stats.as_dict())
        sat = int(SAT_COUNT)
        outs = []
        with self.tracer.span("engine.slice", cat="engine") as ssp:
            for q in batch:
                a0 = state.grammar.index_of(q.start)
                rows = range(nn) if q.sources is None else q.sources
                pairs: set[tuple[int, int]] = set()
                counts: dict[tuple[int, int], int] = {}
                for i in rows:
                    row = C[a0, i, :nn]
                    for j in np.nonzero(row)[0]:
                        pairs.add((i, int(j)))
                        counts[(i, int(j))] = int(row[j])
                if q.start in state.grammar.nullable:
                    for m in rows:  # empty path m pi m is one more path
                        c = counts.get((m, m), 0)
                        counts[(m, m)] = c + 1 if c < sat else sat
                        pairs.add((m, m))
                outs.append(
                    QueryResult(q, pairs, None, stats.copy(), counts=counts)
                )
            ssp.set(pairs=sum(len(o.pairs) for o in outs))
        return outs

    def extract_paths(
        self,
        grammar: CNFGrammar,
        start: str,
        m: int,
        n: int,
        k: int = 10,
        max_len: int = 16,
    ) -> list[list[tuple[int, str, int]]]:
        """Up to ``k`` distinct paths ``m ->* n`` derivable from ``start``,
        each of length <= ``max_len`` (bounded all-path enumeration,
        :class:`~repro.core.semantics.DerivationIndex`).

        Materializes Boolean closure rows for source ``m`` through the
        ordinary relational cache, then enumerates over the packed
        derivation index — which is cached on the grammar state and
        rebuilt only when the closure state changes (new rows
        materialized, or a delta ingested)."""
        with self._lock:
            self._check_graph()
            q = Query(grammar, start, sources=(m,))
            self.validate_query(q)
            if not 0 <= n < self.graph.n_nodes:
                raise ValueError(f"target {n} outside graph")
            state = self._state_for(grammar_key(grammar), grammar)
            self._ensure_rows(state, [q])
            if state.deriv is None:
                state.deriv = DerivationIndex(
                    state.T_host, self.graph, grammar
                )
            return state.deriv.extract_paths(start, m, n, k, max_len)

    def _serve_single_path(
        self, state: _GrammarState, batch: list[Query]
    ) -> list[QueryResult]:
        t0 = time.perf_counter()
        status, decision, fb = self._ensure_rows(
            state, batch, semantics="single_path"
        )
        L = state.sp_L_host
        nn = self.graph.n_nodes
        # state-scoped memo: repeated/overlapping sources — within a batch
        # or across hot-serve batches — extract each witness exactly once
        # per delta epoch; results get copies so callers can't alias it
        memo = state.sp_paths
        sliced = []
        looked_up = extracted = 0
        with self.tracer.span("engine.slice", cat="engine") as ssp:
            if state.extractor is None:  # invalidated on every ingested delta
                state.extractor = PathExtractor(self.graph, state.grammar)
            extractor = state.extractor
            for q in batch:
                a0 = state.grammar.index_of(q.start)
                rows = range(nn) if q.sources is None else q.sources
                pairs: set[tuple[int, int]] = set()
                paths: dict[tuple[int, int], list[tuple[int, str, int]]] = {}
                for i in rows:
                    for j in np.nonzero(np.isfinite(L[a0, i, :nn]))[0]:
                        pairs.add((i, int(j)))
                        key = (q.start, i, int(j))
                        looked_up += 1
                        path = memo.get(key)
                        if path is None:
                            path = memo[key] = extractor.extract(
                                L, q.start, i, int(j)
                            )
                            extracted += 1
                        paths[(i, int(j))] = list(path)
                if q.start in state.grammar.nullable:
                    for m in rows:  # empty path m pi m, as in relational
                        if (m, m) not in pairs:
                            pairs.add((m, m))
                            paths[(m, m)] = []
                sliced.append((q, pairs, paths))
            ssp.set(pairs=sum(len(p) for _, p, _ in sliced),
                    extracted=extracted, memo_hits=looked_up - extracted)
        # latency includes witness extraction — the dominant per-request
        # host cost on hot serves — not just the closure work
        latency = time.perf_counter() - t0
        stats = QueryStats(
            latency_s=latency,
            cache=status,
            engine=state.sp_served_by or self.engine,
            semantics="single_path",
            batched_with=len(batch),
            active_rows=int(state.sp_mask.sum()),
            epoch=self.clock.epoch,
            planner=decision.to_dict() if decision is not None else None,
            fallback=fb,
        )
        stats.update(self.delta_stats.as_dict())
        stats.update(self.plans.stats.as_dict())
        return [
            QueryResult(q, pairs, paths, stats.copy())
            for q, pairs, paths in sliced
        ]
