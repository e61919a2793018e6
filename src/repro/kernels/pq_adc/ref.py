"""Pure-jnp oracle for the fused PQ ADC scan (LUT build + gather + top-k)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import EXACT


def pq_adc_ref(queries: jax.Array, codebooks: jax.Array, codes: jax.Array,
               k: int) -> tuple[jax.Array, jax.Array]:
    """ADC top-k. queries [Q, d] (d = m * dsub), codebooks [m, ksub, dsub],
    codes [N, m] integer. Returns (scores [Q, k], indices [Q, k]); scores
    are negative squared asymmetric distances (higher = closer), i.e.
    ``-||q - decode(codes)||^2`` computed through the LUT, never through a
    materialized reconstruction."""
    q = queries.astype(jnp.float32)
    cb = codebooks.astype(jnp.float32)
    m, ksub, dsub = cb.shape
    qn = q.shape[0]
    n = codes.shape[0]
    qs = q.reshape(qn, m, dsub)
    lut = (jnp.sum(qs * qs, -1)[:, :, None]
           - 2 * jnp.einsum("qms,mjs->qmj", qs, cb, precision=EXACT)
           + jnp.sum(cb * cb, -1)[None, :, :])          # [Q, m, ksub]
    lut_flat = lut.reshape(qn, m * ksub)
    offs = (codes.astype(jnp.int32)
            + (jnp.arange(m, dtype=jnp.int32) * ksub)[None, :])  # [N, m]
    g = jnp.take(lut_flat, offs.reshape(-1), axis=1)    # [Q, N*m]
    dist = g.reshape(qn, n, m).sum(-1)
    return jax.lax.top_k(-dist, k)
