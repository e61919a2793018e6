"""k-NN preservation metrics (paper Section 3.1, Definitions 1-2).

P_overall (Eq. 4) = (1/kN) sum_a |N_k^X(a) ∩ N_k^X'(a)|  — the fraction of
original k-nearest neighbors retained after dimensionality reduction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import EXACT


def pairwise_distances(q: jax.Array, db: jax.Array, metric: str = "euclidean",
                       chunk: int = 1024) -> jax.Array:
    """[Q, N] distance matrix (smaller = closer), chunked over queries."""
    q = q.astype(jnp.float32)
    db = db.astype(jnp.float32)
    if metric == "cosine":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        dn = db / jnp.maximum(jnp.linalg.norm(db, axis=-1, keepdims=True), 1e-12)
        return 1.0 - jnp.matmul(qn, dn.T, precision=EXACT)
    if metric == "euclidean":
        q2 = jnp.sum(q * q, -1)[:, None]
        d2 = jnp.sum(db * db, -1)[None, :]
        sq = jnp.maximum(
            q2 - 2.0 * jnp.matmul(q, db.T, precision=EXACT) + d2, 0.0)
        return jnp.sqrt(sq)
    raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("k", "metric", "exclude_self"))
def knn_indices(q: jax.Array, db: jax.Array, k: int, metric: str = "euclidean",
                exclude_self: bool = False) -> jax.Array:
    """Indices of the k nearest db rows for each query row. ``exclude_self``
    masks the diagonal (q and db are the same collection)."""
    d = pairwise_distances(q, db, metric)
    if exclude_self:
        n = d.shape[0]
        d = d + jnp.eye(n, d.shape[1], dtype=d.dtype) * jnp.inf
    _, idx = jax.lax.top_k(-d, k)
    return idx


def preservation_accuracy(
    x_orig: jax.Array | np.ndarray,
    x_red: jax.Array | np.ndarray,
    k: int = 5,
    metric: str = "euclidean",
    metric_reduced: Optional[str] = None,
) -> float:
    """P_overall (Eq. 4): mean fraction of original k-NN retained in reduced space.

    The same collection serves as anchors and database, self excluded —
    matching the paper's evaluation protocol.
    """
    x_orig = jnp.asarray(x_orig)
    x_red = jnp.asarray(x_red)
    mr = metric_reduced or metric
    idx_o = knn_indices(x_orig, x_orig, k, metric, exclude_self=True)
    idx_r = knn_indices(x_red, x_red, k, mr, exclude_self=True)
    return float(set_overlap(idx_o, idx_r))


@jax.jit
def set_overlap(idx_a: jax.Array, idx_b: jax.Array) -> jax.Array:
    """Mean |A_i ∩ B_i| / k for two [N, k] index matrices."""
    inter = (idx_a[:, :, None] == idx_b[:, None, :]).any(-1)  # [N, k]
    return jnp.mean(inter.astype(jnp.float32))


def recall_at_k(pred_idx: jax.Array, true_idx: jax.Array) -> float:
    """Retrieval recall: fraction of true top-k found in predicted top-k."""
    return float(set_overlap(true_idx, pred_idx))
