"""Each traffic mix is a pure function of its file and the seed, and
every seed offers the same amount of work in another order."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import traffic
from bench.harness import ROOT, plugin

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [
    (w["traffic"], next(c for c in SPEC["configs"] if c["name"] == w["config"]))
    for w in SPEC["workloads"]
]
BIG_SEED = 2**31 + 12345  # seeds may pass 32 signed bits


def parts(mix_name: str, conf: dict):
    """The mix, and the loop, write and graph generators it and its
    configuration name."""
    mix = traffic.load_mix(mix_name)
    cfg = json.loads((ROOT / conf["file"]).read_text())
    return (mix, plugin("loops", mix["loop"]), plugin("writes", mix["writes"]),
            plugin("graphs", cfg["generator"]))


def small(conf: dict) -> dict:
    cfg = json.loads((ROOT / conf["file"]).read_text())
    return dict(cfg, n_classes=300, n_instances=900, n_nodes=1200)


def structural(ops, graph):
    """The writes in generation-order ids (classes and instances by their
    generation index), with their due times."""
    index = {int(v): ("c", k) for k, v in enumerate(graph.classes)}
    index.update({int(v): ("i", k) for k, v in enumerate(graph.instances)})
    return [
        (o.due, [(index[a], x, index[b]) for a, x, b in o.insert],
         [(index[a], x, index[b]) for a, x, b in o.delete])
        for o in ops if o.kind == "write"
    ]


@pytest.mark.parametrize("mix_name,conf", CELLS, ids=[c[0] for c in CELLS])
def test_schedule_is_seeded(mix_name, conf):
    mix, loop, writes, graphs = parts(mix_name, conf)
    cfg = small(conf)
    g1 = graphs.build(cfg, BIG_SEED)
    a = loop.schedule(mix, g1, BIG_SEED, 60.0, writes)
    b = loop.schedule(mix, graphs.build(cfg, BIG_SEED), BIG_SEED, 60.0,
                      writes)
    assert a == b
    g2 = graphs.build(cfg, 7)
    c = loop.schedule(mix, g2, 7, 60.0, writes)
    assert a != c
    # the same read gaps in another order, the same writes renamed
    def gaps(ops):
        return np.sort(np.diff([0.0] + [o.due for o in ops if o.kind == "read"]))

    assert np.allclose(gaps(a), gaps(c))
    if mix["arrival_order"] == "structure":  # the same due times, too
        assert [o.due for o in a if o.kind == "read"] == [
            o.due for o in c if o.kind == "read"]
    assert structural(a, g1) == structural(c, g2)
    assert len(a) == round(mix["rate_per_s"] * 60.0)
    assert all(0 < o.due < 60.0 for o in a)
    n_writes = sum(o.kind == "write" for o in a)
    assert n_writes == len(a) // mix["write_every"] > 0


@pytest.mark.parametrize("mix_name,conf", CELLS, ids=[c[0] for c in CELLS])
def test_writes_apply_in_order(mix_name, conf):
    mix, loop, writes, graphs = parts(mix_name, conf)
    mix = dict(mix, write_every=3)
    g = graphs.build(small(conf), 11)
    edges = set(g.edges)
    for op in loop.schedule(mix, g, 11, 60.0, writes):
        if op.kind == "read":
            assert op.source in set(g.classes.tolist())
            continue
        assert all(e in edges for e in op.delete)
        assert not any(e in edges for e in op.insert)
        edges.difference_update(op.delete)
        edges.update(op.insert)
        for o, label, s in op.insert:
            if label == "subClassOf":  # stays acyclic: points to older
                assert g.order[s] < g.order[o]
                assert op.readback == o


def test_graph_is_the_same_tree_relabelled():
    conf = SPEC["configs"][0]
    cfg = small(conf)
    graphs = plugin("graphs", cfg["generator"])
    a, b = graphs.build(cfg, 1), graphs.build(cfg, 2)
    assert len(a.edges) == len(b.edges) and a.edges != b.edges
    inv = {int(v): k for k, v in enumerate(b.classes)}
    to_b = {int(v): int(b.classes[k]) for k, v in enumerate(a.classes)}
    sub_a = {(to_b[o], to_b[s]) for o, x, s in a.edges if x == "subClassOf"}
    sub_b = {(o, s) for o, x, s in b.edges if x == "subClassOf"}
    assert sub_a == sub_b and len(inv) == cfg["n_classes"]
