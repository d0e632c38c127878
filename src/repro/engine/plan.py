"""Query planning: the compiled-closure cache.

A closure executable is determined by ``(grammar tables, engine, padded n,
row capacity)`` — all static shape/constant information.  jax.jit already
memoizes traces by static args, but the service wants the reuse *explicit
and observable* (cache hit/miss counters in per-request stats) and wants to
skip Python-side dispatch entirely on the hot path, so this cache stores
the AOT ``lower(...).compile()`` executable per plan key.

Row capacities are bucketed (powers of two from 128 up to n) so warm
restarts after an active-set overflow reuse at most O(log n) distinct
executables per grammar instead of compiling per exact source count.

Invariants
----------
* **PlanKey identity.**  A compiled executable is a pure function of its
  :class:`PlanKey` — ``(tables, engine, n, row_capacity, repair,
  ctx_capacity, semantics, mesh)`` — and of *nothing else*.  In
  particular it never depends on graph data, so executables survive every
  delta (row repair and full invalidation alike) and may be shared across
  engines serving different graphs of the same padded size.  ``mesh`` is
  the device-mesh shape identity of sharded (``opt``) plans, ``()``
  otherwise; the concrete mesh object is supplied at build time.
* **Key aliasing is semantic.**  :func:`sp_engine_name` collapses keys
  exactly where the underlying closure function is shared (bitpacked
  single-path aliases to dense; the one single-path repair function keys
  as dense for every backend), so cache-hit counters reflect real reuse.
* **Stable across processes in shape only.**  Keys hash grammar tables by
  value; nothing here persists executables — the cache is per process.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import blocksparse as _blocksparse
from repro.core import closure as _closure
from repro.core import semantics as _semantics
from repro.core.matrices import ProductionTables

#: masked (source-restricted) closure per backend — the serving fast path.
#: ``opt`` is the distributed packed-exchange engine: the only backend
#: whose executables take a mesh identity (PlanKey.mesh) and shard the
#: compacted row block; without a mesh it runs the same math one-device.
#: ``blocksparse`` is the tiled occupied-block engine (core/blocksparse.py):
#: host-driven, so its cache entries are plain callables, not AOT
#: executables — see :meth:`CompiledClosureCache._build`.
MASKED_ENGINES = {
    "dense": _closure.masked_closure,
    "frontier": _closure.masked_frontier_closure,
    "bitpacked": _closure.masked_bitpacked_closure,
    "opt": _closure.masked_opt_closure,
    "blocksparse": _blocksparse.masked_blocksparse_closure,
}

#: repair closure per backend — delta ingestion (frozen-row warm restart;
#: the frontier backend shares the dense repair path: repair iterations are
#: already delta-shaped, there is no second frontier to exploit).  The opt
#: backend is deliberately absent: it has no sharded repair variant, and
#: :func:`repair_engine_name` — the single source of truth for that
#: routing — aliases its repair keys onto the bitpacked executable.
REPAIR_ENGINES = {
    "dense": _closure.masked_repair_closure,
    "frontier": _closure.masked_repair_closure,
    "bitpacked": _closure.masked_bitpacked_repair_closure,
    "blocksparse": _blocksparse.masked_blocksparse_repair_closure,
}

#: masked single-path (length-annotated) closure per backend.  Lengths are
#: f32 — there is no packed layout to exploit — so the bitpacked backend
#: routes through the dense min-plus path (see :func:`sp_engine_name`);
#: the opt backend shards the compacted min-plus row block over the mesh.
SP_ENGINES = {
    "dense": _semantics.masked_single_path_closure,
    "frontier": _semantics.masked_frontier_single_path_closure,
    "opt": _semantics.masked_opt_single_path_closure,
}

#: masked conjunctive closure per backend (``semantics="conjunctive"``).
#: Only two real variants exist: the dense MXU path and the packed-word
#: path.  The frontier (delta) trick is unsound under AND — a conjunct's
#: delta-only product misses pairs whose other conjuncts completed in
#: earlier iterations — and the opt/blocksparse treatments have no
#: conjunctive variant yet, so :func:`conj_engine_name` aliases every
#: backend onto these two executables.
CONJ_ENGINES = {
    "dense": _semantics.masked_conjunctive_closure,
    "bitpacked": _semantics.masked_bitpacked_conjunctive_closure,
}

#: masked counting closure (``semantics="count"``).  One real variant: the
#: u32 saturating planes have no packed word layout, no frontier delta
#: trick (the Jacobi recompute always re-reads full rows), and no sharded
#: or block-tiled treatment — :func:`count_engine_name` aliases every
#: backend onto the dense executable, the same collapse the conjunctive
#: family uses.
COUNT_ENGINES = {
    "dense": _semantics.masked_count_closure,
}


def count_engine_name(engine: str) -> str:
    """Backend name to key counting plans under: always ``dense`` — there
    is exactly one masked counting executable (see :data:`COUNT_ENGINES`),
    so every backend's count PlanKeys collapse onto it and cache-hit
    counters reflect the real reuse."""
    return "dense"


def conj_engine_name(engine: str) -> str:
    """Backend name to key conjunctive plans under: packed backends
    (bitpacked, opt, blocksparse) alias to the bitpacked conjunctive
    executable, everything else (dense, frontier) to the dense one —
    chosen so PlanKeys collapse exactly where the underlying closure
    function is shared (conjunctive plans never carry a mesh: there is
    no sharded conjunctive variant)."""
    return "bitpacked" if engine in ("bitpacked", "opt", "blocksparse") \
        else "dense"


def sp_engine_name(engine: str, repair: bool = False) -> str:
    """Backend name to key single-path plans under, chosen so PlanKeys
    collapse onto one compiled executable wherever the underlying function
    is shared: engines without a length-annotated variant (bitpacked)
    alias to dense, and the repair variant — one function serves every
    backend — always keys as dense (repair runs single-device even for
    the distributed opt backend)."""
    if repair:
        return "dense"
    return engine if engine in SP_ENGINES else "dense"


def repair_engine_name(engine: str) -> str:
    """Backend name to key Boolean repair plans under.  The opt backend
    keys as ``bitpacked``: repair is sized by an edit's blast radius, not
    by the graph, so it always runs the single-device packed path — the
    PlanKey collapse makes the opt and bitpacked backends share one
    compiled repair executable (and keeps ``mesh`` out of repair keys)."""
    return "bitpacked" if engine == "opt" else engine


def mesh_key_of(mesh) -> tuple:
    """:attr:`PlanKey.mesh` identity of a ``jax.sharding.Mesh`` — the
    ``(axis_name, size)`` pairs, ``()`` for ``None`` (single device)."""
    if mesh is None:
        return ()
    return tuple(
        (str(a), int(s)) for a, s in zip(mesh.axis_names, mesh.devices.shape)
    )


def row_buckets(n: int) -> list[int]:
    """Allowed row capacities for padded size n: 128, 256, ... , n."""
    out: list[int] = []
    r = 128
    while r < n:
        out.append(r)
        r *= 2
    out.append(n)
    return out


def bucket_for(n_rows: int, n: int) -> int:
    """Smallest bucket holding ``n_rows`` active rows."""
    for r in row_buckets(n):
        if r >= n_rows:
            return r
    return n


@dataclass(frozen=True)
class PlanKey:
    """Everything that determines a compiled closure executable.

    ``repair`` selects the delta-repair variant: same backend, but the
    executable takes an extra frozen-row mask and signature
    ``(T, src_mask, frozen_mask) -> (T, mask, overflow, iters)``.
    ``ctx_capacity`` is the repair contraction-context bucket (active plus
    frozen rows) on the dense/frontier backends; 0 when unused.
    ``semantics`` selects the state algebra: ``"relational"`` executables
    run on the (N, n, n) bool matrix, ``"single_path"`` ones on the
    (N, n, n) f32 length matrix (isfinite == the Boolean closure), and
    ``"conjunctive"`` ones on the bool matrix under the AND-of-products
    iteration — their ``tables`` is a
    :class:`~repro.core.conjunctive.ConjunctiveTables`, whose value hash
    covers the conjunct structure, so two conjunctive grammars share an
    executable exactly when their index form coincides.  ``"count"``
    executables run on the (N, n, n) uint32 path-count matrix in the
    saturating semiring and take the base tensor as an extra operand —
    signature ``(C, base, src_mask) -> (C, mask, overflow, iters)`` — because
    the Jacobi recompute re-adds the base each iteration instead of
    folding it into the state.  Signatures are otherwise identical.
    ``mesh`` is the mesh identity for sharded (``opt``) executables — the
    ``(axis_name, size)`` tuple of the device mesh the plan partitions
    over, ``()`` for single-device plans.  Two engines sharing a plans
    cache reuse an executable only when their mesh shapes agree; the
    concrete device assignment is supplied at build time
    (:meth:`CompiledClosureCache.get`), not part of the identity.
    ``instrumented`` selects the observability build: the loop body bakes
    in the :func:`repro.obs.trace.emit_iteration` host callback at each
    iteration boundary.  It IS part of the identity — a tracer that wants
    iteration events gets a distinct executable, and the uninstrumented
    hot path stays bit-identical to a build without observability (the
    zero-overhead contract, tested in tests/test_obs.py).  Sharded
    (``opt``) plans never instrument (SPMD host callbacks fire per
    device); engine/service.py enforces that.
    """

    tables: ProductionTables
    engine: str
    n: int  # padded matrix size
    row_capacity: int
    repair: bool = False
    ctx_capacity: int = 0
    semantics: str = "relational"
    mesh: tuple = ()
    instrumented: bool = False
    #: bit-tile edge of block-sparse plans (``row_capacity`` then counts
    #: occupied *blocks*, not rows); 0 for every other backend so existing
    #: keys are unchanged.
    tile: int = 0


@dataclass
class PlanStats:
    """Compile-cache counters plus *provenance* tallies.

    Provenance records **who asked** for each executable — ``"planned"``
    (cost-based planner decision), ``"pinned"`` (caller named the
    backend), or any caller-supplied tag — without touching PlanKey
    identity: a planner-requested executable and a pinned one with the
    same key share one compilation, and the tallies make that sharing
    observable instead of folding routing into the cache key.
    """

    compile_misses: int = 0
    compile_hits: int = 0
    #: provenance tag -> requests (hits + misses) under that tag
    provenance: dict = field(default_factory=dict)

    def note_provenance(self, tag: str | None) -> None:
        if tag:
            self.provenance[tag] = self.provenance.get(tag, 0) + 1

    def as_dict(self) -> dict:
        return {
            "compile_misses": self.compile_misses,
            "compile_hits": self.compile_hits,
        }


class CompiledClosureCache:
    """AOT-compiled masked-closure executables keyed on PlanKey.

    ``get(key)`` returns a callable
    ``(T, src_mask) -> (T, mask, overflow, iters)`` with the grammar
    tables and row capacity baked in; a repeated key never retraces (the
    executable is reused as-is).
    """

    def __init__(self) -> None:
        self._exe: dict[PlanKey, object] = {}
        self.stats = PlanStats()

    def __len__(self) -> int:
        return len(self._exe)

    def get(self, key: PlanKey, mesh=None, provenance: str | None = None):
        """Executable for ``key``.  Sharded keys (``key.mesh != ()``) need
        the concrete ``jax.sharding.Mesh`` on a cache miss — the mesh
        carries the device assignment, the key only its shape identity.
        ``provenance`` tags the request origin (``"planned"`` /
        ``"pinned"``) in :class:`PlanStats` — observability only, never
        part of the key, so routing changes can't fragment the cache."""
        self.stats.note_provenance(provenance)
        exe = self._exe.get(key)
        if exe is None:
            self.stats.compile_misses += 1
            exe = self._exe[key] = self._build(key, mesh)
        else:
            self.stats.compile_hits += 1
        return exe

    def _lower_ctx(self, key: PlanKey, mesh):
        """(mesh context manager, MeshPlan-or-None) for lowering ``key``:
        sharded opt executables trace their ``with_sharding_constraint``
        specs against the ambient mesh."""
        import contextlib

        if not key.mesh:
            return contextlib.nullcontext(), None
        if mesh is None or mesh_key_of(mesh) != key.mesh:
            raise ValueError(
                f"PlanKey has mesh identity {key.mesh} but got "
                f"{'no mesh' if mesh is None else mesh_key_of(mesh)}"
            )
        from repro.shard.plans import MeshPlan

        return mesh, MeshPlan.from_mesh(mesh)

    @staticmethod
    def _hook_kw(key: PlanKey) -> dict:
        """``iter_hook`` kwarg of an instrumented build: the stable
        module-level trampoline (never a per-run closure, so the
        executable stays cacheable across tracer sessions).  The opt
        engine has no hook parameter — service.py never requests
        instrumented opt keys."""
        if not key.instrumented:
            return {}
        from repro.obs.trace import emit_iteration

        return {"iter_hook": emit_iteration}

    def _build(self, key: PlanKey, mesh=None):
        if key.engine == "blocksparse" and key.semantics == "relational":
            # Host-driven engine: block discovery is dynamic sparsity that
            # a fixed-shape AOT program cannot express, so the cache entry
            # is a plain callable with the statics bound — the per-chunk
            # device contraction inside it is jitted and shape-bucketed,
            # which is where the compile reuse this cache exists for
            # actually lives.  (Single-path blocksparse keys never reach
            # here: sp_engine_name aliases them to dense.)
            kw = {
                "row_capacity": key.row_capacity,
                "tile": key.tile or _blocksparse.DEFAULT_TILE,
                **self._hook_kw(key),
            }
            if key.repair:

                def exe_repair(T, src_mask, frozen_mask):
                    return _blocksparse.masked_blocksparse_repair_closure(
                        T, key.tables, src_mask, frozen_mask, **kw
                    )

                return exe_repair

            def exe(T, src_mask):
                return _blocksparse.masked_blocksparse_closure(
                    T, key.tables, src_mask, **kw
                )

            return exe
        ctx, plan = self._lower_ctx(key, mesh)
        m = jax.ShapeDtypeStruct((key.n,), jnp.bool_)
        if key.semantics == "single_path":
            L = jax.ShapeDtypeStruct(
                (key.tables.n_nonterms, key.n, key.n), jnp.float32
            )
            if key.repair:  # one repair variant serves every backend
                kw = {"row_capacity": key.row_capacity, **self._hook_kw(key)}
                if key.ctx_capacity:
                    kw["ctx_capacity"] = key.ctx_capacity
                return _semantics.masked_single_path_repair_closure.lower(
                    L, key.tables, m, m, **kw
                ).compile()
            fn = SP_ENGINES[key.engine]
            kw = {"row_capacity": key.row_capacity}
            if key.engine == "opt":
                kw["plan"] = plan
            else:
                kw.update(self._hook_kw(key))
            with ctx:
                return fn.lower(L, key.tables, m, **kw).compile()
        if key.semantics == "conjunctive":
            # ``key.tables`` is a ConjunctiveTables here; conjunctive plans
            # never carry repair/mesh — insert repair re-enters the ordinary
            # masked closure (delta/DELTA.md#conjunctive-states) and there
            # is no sharded conjunctive variant.
            T = jax.ShapeDtypeStruct(
                (key.tables.n_nonterms, key.n, key.n), jnp.bool_
            )
            fn = CONJ_ENGINES[key.engine]
            kw = {"row_capacity": key.row_capacity, **self._hook_kw(key)}
            return fn.lower(T, key.tables, m, **kw).compile()
        if key.semantics == "count":
            # One dense executable serves every backend (count_engine_name);
            # count plans never carry repair/mesh — insert repair re-seeds
            # affected rows and re-enters this same closure
            # (delta/DELTA.md#count-states), and there is no sharded
            # counting variant.
            C = jax.ShapeDtypeStruct(
                (key.tables.n_nonterms, key.n, key.n), jnp.uint32
            )
            fn = COUNT_ENGINES[key.engine]
            kw = {"row_capacity": key.row_capacity, **self._hook_kw(key)}
            return fn.lower(C, C, key.tables, m, **kw).compile()
        T = jax.ShapeDtypeStruct(
            (key.tables.n_nonterms, key.n, key.n), jnp.bool_
        )
        if key.repair:
            fn = REPAIR_ENGINES[key.engine]
            kw = {"row_capacity": key.row_capacity, **self._hook_kw(key)}
            if key.ctx_capacity:  # dense/frontier compact the contraction
                kw["ctx_capacity"] = key.ctx_capacity
            return fn.lower(T, key.tables, m, m, **kw).compile()
        fn = MASKED_ENGINES[key.engine]
        kw = {"row_capacity": key.row_capacity}
        if key.engine == "opt":
            kw["plan"] = plan
        else:
            kw.update(self._hook_kw(key))
        with ctx:
            return fn.lower(T, key.tables, m, **kw).compile()
