"""Faults planted under the timed path, for the control runs and the
fault tests: each must turn ``correct`` false.

``stale``   a write commits to the graph and the epoch, but the cached
            closure rows are left as they were (a step that returns its
            state unchanged): the guarantee that a read sees every
            acknowledged write is broken
``half``    only the first half of each coalesced batch is computed; the
            rest get the answers of the computed half
``alter``   one answer of each batch gets one pair more or less, where
            the engine produces it
"""
from __future__ import annotations

import dataclasses

FAULTS = ("stale", "half", "alter")


def install(name: str, engine) -> None:
    """Plant fault ``name`` in ``engine`` (its instance attributes only)."""
    if name == "stale":
        _stale(engine)
    elif name in ("half", "alter"):
        _batch(engine, name)
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")


def _stale(engine) -> None:
    from repro.delta.repair import DeltaStats

    def ingest(delta=None):
        g = engine.graph
        engine._version = g.version
        engine._edge_set = frozenset(g.edges)
        engine.clock.advance(g.version)
        return DeltaStats()

    engine._ingest_delta = ingest


def _batch(engine, name: str) -> None:
    query_batch = engine.query_batch

    def faulty(queries, *a, **k):
        if name == "alter":
            out = query_batch(queries, *a, **k)
            src = out[0].query.sources[0]
            out[0].pairs = out[0].pairs ^ {(src, src)}
            return out
        h = max(1, len(queries) // 2)
        done = query_batch(queries[:h], *a, **k)
        return done + [
            dataclasses.replace(done[i % h], query=q)
            for i, q in enumerate(queries[h:])
        ]

    engine.query_batch = faulty
