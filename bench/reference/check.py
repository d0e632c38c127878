"""Decide ``correct``: every read's answer against the plain reference at
the epoch the server says it served, once the window has closed.

The writes were applied in schedule order by one writer, so epoch
``e0 + k`` is the seed's graph plus the first ``k`` writes.  For each
epoch that some read was served at, the Hellings worklist
(``bench/reference/hellings.py``) computes the start symbol's relation
over that graph.  The configuration's semantics names the comparison of a
read with that relation, a file found by name:
``bench/compare/<semantics>.py``.

One number is compared, with the limit 0 (an exact comparison):
``wrong``, the requests that the reference does not bear out.  It sums
what the comparison counts (``answers``: reads whose answer differs from
the reference row; for single-path also ``witnesses``: witnesses that
are no path of the graph or whose labels the grammar does not derive)
and

``epochs``     reads served at an epoch before the last write
               acknowledged when they were sent, or after the last write
               begun when they were answered; writes acknowledged at
               another epoch than their turn
``lost``       reads or writes that failed or were not answered within
               the grace past the window's close (a shed read is refused,
               not lost: it counts in the latency only)

Every read that was answered is compared.
"""
from __future__ import annotations

from collections import defaultdict

from bench.reference.hellings import hellings


def check_run(config: dict, graph, ops, run, e0: int, compare):
    """``(checks, parts)``: the number compared with its limit, and the
    counts it sums.  ``compare`` is the semantics' comparison."""
    cnf = config["grammar"]["reference_cnf"]
    start = config["grammar"]["start"]
    writes = [op for op in ops if op.kind == "write"]

    lost = sum(r.outcome in ("failed", "lost") for r in run.reads)
    lost += sum(w.outcome != "ok" for w in run.writes)
    order = sum(
        w.outcome == "ok" and w.epoch != e0 + k + 1
        for k, w in enumerate(run.writes)
    )
    by_epoch: dict[int, list] = defaultdict(list)
    for r in run.reads:
        if r.outcome != "ok":
            continue
        k = r.stats["epoch"] - e0
        if not (r.acked_before <= k <= r.started_before_done) or not (
            0 <= k <= len(writes)
        ):
            order += 1
            continue
        by_epoch[k].append(r)

    found: dict[str, int] = defaultdict(int)
    edges = set(graph.edges)
    for k in range(max(by_epoch, default=-1) + 1):
        if k > 0:
            w = writes[k - 1]
            edges.difference_update(w.delete)
            edges.update(w.insert)
        if k not in by_epoch:
            continue
        rows: dict[int, set] = defaultdict(set)
        for i, j in hellings(edges, cnf).get(start, ()):
            rows[i].add((i, j))
        for key, n in compare(edges, config, by_epoch[k], rows).items():
            found[key] += n
    parts = {"answers": 0, **found, "epochs": order, "lost": lost}
    return {"wrong": {"value": sum(parts.values()), "limit": 0}}, parts

