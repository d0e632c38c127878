"""Write rule ``ontology``: which triples a mix whose ``"writes"`` is
``"ontology"`` inserts and deletes, on a graph from ``bench/graphs/
ontology.py``.

Labels (``write_labels``) and, within a label, kinds (``write_kinds``)
come in counts fixed by the mix's weights.  A write is one triple and its
inverse:

- insert ``type``: a uniform instance, a class drawn Zipf (``zipf``);
- insert ``subClassOf``: a uniform class and a uniform class generated
  before it, so the ``subClassOf`` graph stays acyclic;
- delete: a uniform existing triple of the label.

The triples are drawn from the graph's ``structure_seed``, not from
``--seed``, which only renames their nodes: every seed writes the same
triples of the same tree, so that seeds change which nodes are read and
in what order, not how much work there is.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import EdgeModel, Zipf, write_mix


def triples(mix: dict, graph, count: int) -> list[tuple[str, tuple]]:
    """``count`` writes, in the order they are sent: (kind, triple)."""
    rng = np.random.default_rng([2, graph.structure_seed])
    zipf = Zipf(graph.classes, mix["zipf"], rng)
    model = EdgeModel(graph.edges)
    out = []
    for kind, label in write_mix(mix, count, rng):
        t = _triple(kind, label, graph, model, zipf, rng)
        if kind == "insert":
            model.add(t)
        else:
            model.remove(t)
        out.append((kind, t))
    return out


def warmup_triple(mix: dict, graph) -> tuple:
    """A ``type`` triple that is not in the graph: set-up inserts and then
    deletes it, so that the window starts on the seed's graph."""
    rng = np.random.default_rng([3, graph.structure_seed])
    zipf = Zipf(graph.classes, mix["zipf"], rng)
    return _triple("insert", "type", graph, EdgeModel(graph.edges), zipf,
                   rng)


def _triple(kind: str, label: str, graph, model: EdgeModel, zipf: Zipf,
            rng) -> tuple:
    if kind == "delete":
        lst = model.by_label[label]
        return lst[int(rng.integers(len(lst)))]
    if kind != "insert":
        raise ValueError(f"unknown write kind {kind!r}")
    while True:
        if label == "type":
            o = int(graph.instances[int(rng.integers(len(graph.instances)))])
            s = int(zipf.draw(rng))
        elif label == "subClassOf":
            o = int(graph.classes[int(rng.integers(1, len(graph.classes)))])
            s = int(graph.classes[int(rng.integers(graph.order[o]))])
        else:
            raise ValueError(f"no insert rule for label {label!r}")
        if (o, label, s) not in model:
            return (o, label, s)
