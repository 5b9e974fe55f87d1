"""Mesh construction for the production topology.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is a second (slow, DCN-linked) data-parallel axis; gradients
cross it once per step, optionally int8-compressed (optim/compress.py).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "batch_axes_of",
]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests (axes exist, extent 1)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
