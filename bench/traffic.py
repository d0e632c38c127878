"""What every traffic mix shares: the mix file, scheduled operations, and
the seeded arithmetic that arrival and write generators draw with.

A mix is a data file, ``bench/traffic/<mix>.json``.  It names the two
generators that read it, each a file found by name:

* ``"loop"``: ``bench/loops/<loop>.py``, which turns the mix and
  ``--seed`` into the window's schedule and set-up steps and drives the
  server through them;
* ``"writes"`` (a mix with writes): ``bench/writes/<rule>.py``, which
  picks the triples written, by rules that keep the graph's own shape.

The rest of the file is their parameters.  Nothing here knows a label,
a node role or an arrival process.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
INVERSE = "_r"


@dataclass(frozen=True)
class Op:
    """One scheduled operation, due ``due`` seconds into the window."""

    due: float
    kind: str  # "read" | "write"
    source: int = -1  # read: the source node
    insert: tuple = ()  # write: edges inserted (triple and inverse)
    delete: tuple = ()  # write: edges deleted
    readback: int | None = None  # write: source read back on its ack


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if "loop" not in mix:
        raise ValueError(f"traffic {name!r} names no loop generator")
    return mix


def with_inverse(triples) -> list[tuple[int, str, int]]:
    """Each triple ``(o, p, s)`` and its inverse ``(s, p_r, o)``."""
    out = []
    for o, p, s in triples:
        out.append((o, p, s))
        out.append((s, p + INVERSE, o))
    return out


def arrivals(n: int, rate: float, seconds: float, rng) -> np.ndarray:
    """``n`` due times in (0, seconds) of a Poisson process at ``rate``
    (the arithmetic of ``repro.serve.loadgen.poisson_arrivals``), with the
    gaps fixed to the quantiles of the exponential distribution and put
    in a seeded order: every seed offers the same gaps."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    return due * (seconds * (1 - 0.5 / n) / due[-1])


def exact_counts(weights: dict, count: int) -> dict:
    """``count`` split in proportion to ``weights`` by largest remainder
    (ties to the first key)."""
    total = sum(weights.values())
    share = {k: count * w / total for k, w in weights.items()}
    n = {k: int(v) for k, v in share.items()}
    rest = sorted(share, key=lambda k: n[k] - share[k])
    for k in rest[: count - sum(n.values())]:
        n[k] += 1
    return n


def write_mix(mix: dict, count: int, rng) -> list[tuple[str, str]]:
    """``count`` (kind, label) pairs: labels in exact counts
    (``write_labels``), kinds in exact counts within each label
    (``write_kinds``), in an order drawn from ``rng``."""
    out = []
    for label, n in exact_counts(mix["write_labels"], count).items():
        for kind, m in exact_counts(mix["write_kinds"], n).items():
            out += [(kind, label)] * m
    return [out[i] for i in rng.permutation(len(out))]


class Zipf:
    """Draws over ``items`` ranked by a seeded permutation, P(rank k)
    proportional to k^-s."""

    def __init__(self, items: np.ndarray, s: float, rng) -> None:
        self.items = rng.permutation(items)
        w = np.arange(1, len(items) + 1, dtype=np.float64) ** -s
        self.p = w / w.sum()

    def draw(self, rng, size=None):
        return self.items[rng.choice(len(self.items), size=size, p=self.p)]


class EdgeModel:
    """The forward triples of a graph as writes change it, with uniform
    choice among one label's triples."""

    def __init__(self, edges) -> None:
        self.by_label: dict[str, list] = {}
        self.where: dict[tuple, int] = {}
        for e in edges:
            if not e[1].endswith(INVERSE):
                self.add(e)

    def add(self, e: tuple) -> None:
        lst = self.by_label.setdefault(e[1], [])
        self.where[e] = len(lst)
        lst.append(e)

    def remove(self, e: tuple) -> None:
        lst = self.by_label[e[1]]
        k = self.where.pop(e)
        last = lst.pop()
        if last != e:
            lst[k] = last
            self.where[last] = k

    def __contains__(self, e: tuple) -> bool:
        return e in self.where
