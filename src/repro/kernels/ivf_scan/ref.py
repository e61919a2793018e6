"""Pure-jnp oracle for the IVF probe scan: the padded gather and einsum.

This is the probe's XLA formulation: it materialises the
``[Q, P, D, L]`` gather of the probed cells and scores every member slot,
the padding included, then masks the slots at and past each cell's
extent. Off the TPU it is the production path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import EXACT


def ivf_scan_ref(queries: jax.Array, cells: jax.Array, extent: jax.Array,
                 list_vecs: jax.Array) -> jax.Array:
    """Scores [Q, P, L] = ``2 q.x - ||x||^2 - ||q||^2`` (minus the
    squared L2 distance) of each query against the members of its probed
    cells ``cells`` [Q, P] in the feature-major store ``list_vecs``
    [C, D, L]; ``-inf`` at members ``>= extent[cell]``."""
    q = jnp.asarray(queries, jnp.float32)
    d = q.shape[1]
    vecs = jnp.asarray(list_vecs, jnp.float32)[cells][:, :, :d]  # [Q,P,d,L]
    s = (2.0 * jnp.einsum("qd,qpdc->qpc", q, vecs, precision=EXACT)
         - jnp.sum(vecs * vecs, 2)
         - jnp.sum(q * q, -1)[:, None, None])
    member = jnp.arange(list_vecs.shape[2])
    return jnp.where(member < extent[cells][..., None], s, -jnp.inf)
