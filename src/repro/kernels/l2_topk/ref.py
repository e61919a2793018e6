"""Pure-jnp oracle for the fused distance+top-k scan."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import EXACT, NEG_INF, PAD_ID, canonicalize_pads


def l2_topk_ref(queries: jax.Array, db: jax.Array, k: int,
                metric: str = "euclidean", db_mask: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Exact k-NN scores/indices. Scores are similarities (higher = closer):
    euclidean -> negative squared distance; cosine -> cosine similarity on
    pre-normalized inputs (the caller normalizes). ``db_mask`` (bool [N])
    tombstones rows: masked rows never appear in the result — their slots
    come back as (NEG_INF, -1) when fewer than k rows survive."""
    q = queries.astype(jnp.float32)
    d = db.astype(jnp.float32)
    if metric == "euclidean":
        s = 2.0 * jnp.matmul(q, d.T, precision=EXACT) \
            - jnp.sum(d * d, -1)[None, :] \
            - jnp.sum(q * q, -1)[:, None]
    elif metric == "cosine":
        s = jnp.matmul(q, d.T, precision=EXACT)
    else:
        raise ValueError(metric)
    if db_mask is None:
        return jax.lax.top_k(s, k)
    s = jnp.where(db_mask[None, :], s, NEG_INF)
    vals, idx = jax.lax.top_k(s, k)
    idx = jnp.where(vals <= NEG_INF / 2, PAD_ID, idx)
    return canonicalize_pads(vals, idx)
