"""Graph generator ``ontology``: a configuration whose ``"generator"`` is
``"ontology"`` gets its graph from :func:`build`.

The generator is a copy of the program's ``ontology_graph`` (a
``subClassOf`` tree over classes plus ``type`` edges from instances, each
triple ``(o, p, s)`` stored with its inverse ``(s, p_r, o)``), kept here so
that no change to the program moves the data.  Its shape comes from the
configuration's fixed ``structure_seed``: every run holds the same tree and
the same ``type`` fan-in.  ``--seed`` relabels the nodes by a permutation,
so two seeds give the same work in another order.

Configuration keys: ``n_classes``, ``n_instances``, ``n_nodes`` (padded
with isolated nodes), ``branching``, ``structure_seed``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench.traffic import with_inverse


@dataclass
class OntologyGraph:
    """Edges (with inverses), padded node count, and the node roles that
    traffic draws from, in generation order (``classes[k]`` is the k-th
    class generated, whatever the seed's ids).  ``order[v]`` is the
    generation index of class ``v`` (a ``subClassOf`` edge always points
    to a lower one).  ``structure_seed`` made the shape."""

    n_nodes: int
    edges: list[tuple[int, str, int]]
    classes: np.ndarray
    instances: np.ndarray
    order: dict[int, int]
    structure_seed: int

    @property
    def read_sources(self) -> np.ndarray:
        """The nodes single-source reads start from: the classes."""
        return self.classes


def build(config: dict, seed: int) -> OntologyGraph:
    """The configuration's ontology, node ids permuted by ``seed``."""
    n_classes = config["n_classes"]
    n_instances = config["n_instances"]
    branching = config["branching"]
    rng = np.random.default_rng(config["structure_seed"])
    triples: list[tuple[int, str, int]] = []
    for c in range(1, n_classes):
        parent = int(rng.integers(max(0, (c - 1) // branching), c))
        triples.append((c, "subClassOf", parent))
    for i in range(n_instances):
        c = int(rng.integers(0, n_classes))
        triples.append((n_classes + i, "type", c))
    n_real = n_classes + n_instances
    n_nodes = config["n_nodes"]
    if n_nodes < n_real:
        raise ValueError(f"n_nodes {n_nodes} < {n_real} generated nodes")
    perm = np.random.default_rng(seed).permutation(n_real)
    relabeled = [(int(perm[o]), p, int(perm[s])) for o, p, s in triples]
    classes = perm[:n_classes].astype(np.int64)
    return OntologyGraph(
        n_nodes=n_nodes,
        edges=with_inverse(relabeled),
        classes=classes,
        instances=perm[n_classes:].astype(np.int64),
        order={int(v): k for k, v in enumerate(classes)},
        structure_seed=config["structure_seed"],
    )
