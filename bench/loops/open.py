"""Loop generator ``open``: an open-loop mix of reads and writes.

``rate_per_s * seconds`` operations are due over the window, one in
``write_every`` a write.

* Reads arrive as a Poisson process (``bench.traffic.arrivals``: the same
  gaps for every seed).  ``arrival_order`` puts the gaps in an order drawn
  from ``--seed`` (``"seed"``) or from the graph's ``structure_seed``
  (``"structure"``: every seed's reads are due at the same times, so the
  reads that queue behind each write are the same few).  Each is a
  single-source read
  whose source is drawn Zipf (exponent ``zipf``) over a seeded
  permutation of the graph's ``read_sources``.
* Writes come from one writer at a fixed period, ``write_every /
  rate_per_s`` seconds, the first half a period in, in the order the
  mix's write rule (``"writes"``) gives.  A write whose label is listed
  in ``readback`` is followed, on its acknowledgement, by a read of its
  subject: the writer reads its write back.

Every request is timed from when it was due, so a late generator counts.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from bench.harness import GRACE_S, ReadRec, WriteRec
from bench.traffic import Op, Zipf, arrivals, with_inverse


def schedule(mix: dict, graph, seed: int, seconds: float,
             writes) -> list[Op]:
    """The window's operations in due order."""
    rng = np.random.default_rng([0, seed])
    rate = mix["rate_per_s"]
    every = int(mix["write_every"])
    n_ops = max(1, int(round(rate * seconds)))
    n_writes = n_ops // every
    period = every / rate
    order = mix["arrival_order"]
    if order == "seed":
        arng = rng
    elif order == "structure":
        arng = np.random.default_rng([4, graph.structure_seed])
    else:
        raise ValueError(f"unknown arrival_order {order!r}")
    due = arrivals(n_ops - n_writes, rate * (1 - 1 / every), seconds, arng)
    sources = Zipf(graph.read_sources, mix["zipf"], rng).draw(
        rng, size=len(due))
    ops = [Op(float(t), "read", source=int(s))
           for t, s in zip(due, sources)]
    readback = set(mix.get("readback", ()))
    for k, (kind, triple) in enumerate(writes.triples(mix, graph, n_writes)):
        edges = tuple(with_inverse([triple]))
        ops.append(Op(
            (k + 0.5) * period, "write",
            insert=edges if kind == "insert" else (),
            delete=edges if kind == "delete" else (),
            readback=triple[0] if triple[1] in readback else None,
        ))
    return sorted(ops, key=lambda o: o.due)


def warmup(mix: dict, graph, seed: int, writes) -> list[list[Op]]:
    """Set-up's operations, in steps: every path the window takes, with
    writes that cancel out, so that the window starts on the seed's graph
    with its rows materialised.  A read misses and materialises; a batch
    hits; an insert repairs the full cache; a delete of the same edge
    evicts it; a read re-materialises; a batch hits again."""
    both = tuple(with_inverse([writes.warmup_triple(mix, graph)]))
    rng = np.random.default_rng([1, seed])
    src = [int(s) for s in Zipf(graph.read_sources, mix["zipf"], rng).draw(
        rng, size=9)]
    return [
        [Op(0, "read", source=src[0])],
        [Op(0, "read", source=s) for s in src[1:]],
        [Op(0, "write", insert=both)],
        [Op(0, "read", source=src[0])],
        [Op(0, "write", delete=both)],
        [Op(0, "read", source=src[0])],
        [Op(0, "read", source=s) for s in src[1:]],
    ]


async def drive(srv, ops, query_of, t0: float, seconds: float):
    """Submit ``ops`` at ``t0 + op.due`` (reads concurrently, writes by one
    writer in order); return (reads, writes) once every one has ended or
    ``GRACE_S`` past the window's close has gone by."""
    from repro.serve import Overloaded

    reads: list[ReadRec] = []
    writes: list[WriteRec] = []
    progress = {"acked": 0, "started": 0}
    tasks: list[asyncio.Task] = []

    async def read(source: int, due_abs: float, readback: bool = False):
        rec = ReadRec(source=source, due=due_abs - t0, readback=readback)
        reads.append(rec)
        await asyncio.sleep(max(0.0, due_abs - time.perf_counter()))
        rec.late = time.perf_counter() - due_abs
        rec.acked_before = progress["acked"]
        try:
            res = await srv.submit(query_of(source))
        except Overloaded:
            rec.outcome = "shed"
            return
        except Exception as exc:  # noqa: BLE001 — recorded as a lost answer
            rec.outcome = "failed"
            rec.stats = {"error": repr(exc)}
            return
        rec.t_done = time.perf_counter() - t0
        rec.started_before_done = progress["started"]
        rec.outcome = "ok"
        rec.pairs, rec.paths = res.pairs, res.paths
        st = res.stats
        rec.stats = {
            "cache": st.cache, "epoch": st.epoch,
            "queue_delay_s": st.queue_delay_s,
            "batch_exec_s": st.batch_exec_s,
            "window_batch": st.window_batch,
        }

    async def writer(wops):
        for op in wops:
            due_abs = t0 + op.due
            rec = WriteRec(due=op.due)
            writes.append(rec)
            await asyncio.sleep(max(0.0, due_abs - time.perf_counter()))
            rec.late = time.perf_counter() - due_abs
            progress["started"] += 1
            try:
                ds = await srv.apply_delta(op.insert, op.delete)
            except Exception as exc:  # noqa: BLE001 — a lost write
                rec.outcome = "failed"
                rec.delta = {"error": repr(exc)}
                return  # later writes would not match the schedule
            # read in the step that resumed us: no later write has begun
            rec.epoch = srv.engine.clock.epoch
            rec.t_done = time.perf_counter() - t0
            rec.outcome = "ok"
            rec.delta = ds.as_dict()
            progress["acked"] += 1
            if op.readback is not None:
                tasks.append(asyncio.ensure_future(
                    read(op.readback, time.perf_counter(), readback=True)
                ))

    for op in ops:
        if op.kind == "read":
            tasks.append(asyncio.ensure_future(read(op.source, t0 + op.due)))
    wtask = asyncio.ensure_future(writer([o for o in ops if o.kind == "write"]))
    deadline = t0 + seconds + GRACE_S
    await asyncio.wait([wtask], timeout=max(0.0, deadline - time.perf_counter()))
    pending = [t for t in tasks if not t.done()]
    if pending:
        await asyncio.wait(
            pending, timeout=max(0.0, deadline - time.perf_counter())
        )
    for t in [wtask, *tasks]:
        if not t.done():
            t.cancel()
    await asyncio.gather(wtask, *tasks, return_exceptions=True)
    return reads, writes
