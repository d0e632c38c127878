"""From a ``jax.profiler`` trace to device busy time, contraction time and
the breakdown of the result line.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<k>``; its ``XLA Ops`` line holds
one event per device operation, with start and duration in nanoseconds
on the host's clock.  Host threads are ``/host:CPU`` lines, where the
benchmark's own ``bench.read`` / ``bench.write`` annotations (one per
engine call) say what the host was doing while the device was idle.

Each event's name is the HLO instruction's text.  Busy time is the union
of a chip's operation intervals, averaged over the chips that ran any; a
``while`` loop's interval holds its body's operations, so loops count
once there and not at all in the per-operation times.  A contraction is
an instruction named for a convolution or dot (the MXU products; XLA
names a fusion after its hero) or a Pallas ``bitmm`` call.  The
``Async XLA Ops`` line (DMA copies, the host mirror among them) is not
compute and is left out of busy time.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
#: an instruction named after a product: XLA names a fusion after its
#: hero (``convolution_compare_fusion``); Pallas calls keep their kernel's
CONTRACTION_WORDS = ("convolution", "dot", "bitmm")
#: control flow whose interval holds its body's operations
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float = 0.0
    contraction_s: float = 0.0
    op_seconds: dict = field(default_factory=dict)  # op name -> seconds
    idle_gaps: list = field(default_factory=list)  # longest: (host did, s)
    n_ops: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps, key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps],
        }


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_op(text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event, whose name is
    the HLO instruction's text: ``%name = <shape> opcode(operands), ...``."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return name.lstrip("%"), m.group(1) if m else ""


def is_contraction(name: str) -> bool:
    return any(w in name for w in CONTRACTION_WORDS)


def union_length(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total covered length and the merged intervals (any unit)."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def reduce_trace(trace_dir: Path, window_s: float | None = None) -> DeviceTrace:
    """Reduce the newest trace under ``trace_dir``.  ``window_s`` is the
    traced window's length on the host clock; without it, the span from
    the first to the last event of the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    per_chip: list[list[tuple[float, float]]] = []
    op_ns: dict[str, float] = defaultdict(float)
    contraction_ns = 0.0
    annotations: list[tuple[float, float, str]] = []
    t_lo, t_hi = float("inf"), float("-inf")
    n_ops = 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ivs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    a, d = float(ev.start_ns), float(ev.duration_ns)
                    ivs.append((a, a + d))
                    n_ops += 1
                    name, opcode = parse_op(ev.name)
                    if opcode in CONTAINERS:
                        continue  # its body's operations are counted
                    op_ns[name] += d
                    if is_contraction(name):
                        contraction_ns += d
            if ivs:
                per_chip.append(ivs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = float(ev.start_ns)
                    t_lo = min(t_lo, a)
                    t_hi = max(t_hi, a + float(ev.duration_ns))
                    if ev.name.startswith("bench."):
                        annotations.append(
                            (a, a + float(ev.duration_ns), ev.name[6:])
                        )
    busy_ns = 0.0
    merged0: list[tuple[float, float]] = []
    for k, ivs in enumerate(per_chip):
        covered, merged = union_length(ivs)
        busy_ns += covered
        t_lo = min(t_lo, merged[0][0])
        t_hi = max(t_hi, merged[-1][1])
        if k == 0:
            merged0 = merged
    # idle gaps of the first chip, the trace's ends included; only the
    # longest are named, by what the host was doing
    edges = [t_lo, *[x for iv in merged0 for x in iv], t_hi]
    spans = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    longest = heapq.nlargest(10, spans, key=lambda ab: ab[1] - ab[0])
    gaps = [(host_activity(annotations, a, b), (b - a) / 1e9)
            for a, b in longest]
    chips = max(1, len(per_chip))
    if window_s is None:
        window_s = max(0.0, (t_hi - t_lo) / 1e9)
    return DeviceTrace(
        window_s=window_s,
        busy_s=busy_ns / chips / 1e9,
        contraction_s=contraction_ns / chips / 1e9,
        op_seconds={k: v / chips / 1e9 for k, v in op_ns.items()},
        idle_gaps=gaps,
        n_ops=n_ops,
    )


def host_activity(annotations, a: float, b: float) -> str:
    """What the host was doing over most of the idle gap (a, b): the
    benchmark's engine-call annotation that covers most of it, else
    ``serve loop`` (no engine call in flight)."""
    best, best_cover = "serve loop", 0.0
    for s, e, name in annotations:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = f"engine {name} call", cover
    return best if best_cover > (b - a) / 2 else "serve loop"
