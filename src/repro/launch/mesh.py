"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the pod
axis is an outer data-parallel axis (gradient all-reduce crosses the
inter-pod links; decode shards batch across pods).

Defined as functions so importing this module never touches jax device
state; ``dryrun.main()`` pins the CPU with 512 host devices before any
JAX import.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many devices this host actually has."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))
