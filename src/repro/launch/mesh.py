"""Production mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first). Every
mesh here is Auto-typed: shardings are propagated by the compiler, and
``jax.make_mesh`` would otherwise default to Explicit axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis is pure
    data parallelism (gradient all-reduce crosses the DCN/ICI pod boundary).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist locally, as a 1D (data,) mesh — used by tests
    and the CPU-scale examples."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
