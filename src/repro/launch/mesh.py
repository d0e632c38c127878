"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod's worth).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the pod axis carries
pure data parallelism (gradient all-reduce crosses pods on DCI/ICI-slow
links — which is why train batches shard over ('pod', 'data') and the
gradient-compression path exists, see train/compression.py).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from repro.shard.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for multi-device CPU tests (host-platform devices)."""
    return make_mesh((data, model))
