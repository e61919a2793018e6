"""Jit'd public wrapper: platform dispatch for the IVF probe scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ivf_scan_pallas
from .ref import ivf_scan_ref


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def ivf_scan(queries: jax.Array, cells: jax.Array, extent: jax.Array,
             list_vecs: jax.Array, impl: str = "auto",
             interpret: bool = False) -> jax.Array:
    """Score each query against the members of its probed IVF cells.

    queries [Q, d]; cells [Q, P] int32 probed cell ids; extent [C] int32,
    the number of real (prefix) members of each cell of the feature-major
    store ``list_vecs`` [C, D, L] (``kernel.store_shape``: member ``j`` of
    cell ``c`` is ``list_vecs[c, :d, j]``, features ``d:`` are zero).
    Returns scores [Q, P, L] f32,
    minus the squared L2 distance, ``-inf`` at members
    ``>= extent[cell]``. On the TPU only the probed cells' real member
    blocks are read (see ``kernel.py``).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return ivf_scan_ref(queries, cells, extent, list_vecs)
    q = jnp.asarray(queries, jnp.float32)
    q_t = jnp.pad(q, ((0, 0), (0, list_vecs.shape[1] - q.shape[1]))).T
    out = ivf_scan_pallas(q_t, cells, extent, list_vecs,
                          interpret=interpret)
    return out.reshape(*cells.shape, -1)[:, :, :list_vecs.shape[2]]
