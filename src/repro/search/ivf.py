"""IVF (inverted-file) coarse quantization in JAX — beyond-paper search tier.

FAISS-style two-level index: k-means coarse centroids partition the corpus;
queries probe the ``nprobe`` nearest cells and scan only those lists. On
TPU, ragged inverted lists become a *padded dense* layout ([n_cells,
cell_cap, d] + validity mask) so the probe scan is a fixed-shape gather +
batched matmul — no host-side indirection in the hot path.

Composes with the paper's RAE: build the IVF over the *reduced* corpus
(R^m) and rerank in R^n — compression shrinks both the centroid search and
the list scan, while kappa(W) (Eq. 16) bounds the extra recall loss.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import EXACT


@dataclass
class IVFIndex:
    centroids: jax.Array   # [C, d]
    lists: jax.Array       # [C, cap] int32 corpus row ids (-1 = pad)
    list_vecs: jax.Array   # [C, cap, d] padded member vectors
    list_mask: jax.Array   # [C, cap] bool
    spill: int             # rows dropped by the cap (0 in healthy builds)


def kmeans(x: jax.Array, n_clusters: int, iters: int = 10,
           seed: int = 0) -> jax.Array:
    """Plain Lloyd's k-means (k-means++-lite init via random distinct rows)."""
    n = x.shape[0]
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = x[idx]

    # x is an argument, not a closure: a closed-over array is baked into
    # the program as a constant (the whole corpus) and constant-folded
    @jax.jit
    def step(cent, x):
        d2 = (jnp.sum(x * x, 1)[:, None]
              - 2 * jnp.matmul(x, cent.T, precision=EXACT)
              + jnp.sum(cent * cent, 1)[None, :])
        assign = jnp.argmin(d2, 1)
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        cnt = jax.ops.segment_sum(jnp.ones(n), assign,
                                  num_segments=n_clusters)
        new = sums / jnp.maximum(cnt, 1.0)[:, None]
        # keep empty clusters where they were
        return jnp.where(cnt[:, None] > 0, new, cent), assign

    assign = None
    for _ in range(iters):
        cent, assign = step(cent, x)
    return cent, assign


def build(corpus: jax.Array, n_cells: int, cell_cap: Optional[int] = None,
          kmeans_iters: int = 10, seed: int = 0) -> IVFIndex:
    corpus = jnp.asarray(corpus, jnp.float32)
    n, d = corpus.shape
    cent, assign = kmeans(corpus, n_cells, kmeans_iters, seed)
    assign = np.asarray(assign)
    cap = cell_cap or int(np.ceil(2.5 * n / n_cells))
    # vectorized list fill (the Python row loop took minutes at 1M rows):
    # stable-sort rows by cell, so each row's slot is its rank within its
    # cell — identical layout to filling in ascending row order
    order = np.argsort(assign, kind="stable")
    sorted_cells = assign[order]
    starts = np.searchsorted(sorted_cells, np.arange(n_cells), side="left")
    pos = np.arange(n) - starts[sorted_cells]
    keep = pos < cap
    lists = np.full((n_cells, cap), -1, np.int32)
    lists[sorted_cells[keep], pos[keep]] = order[keep].astype(np.int32)
    spill = int(n - keep.sum())
    mask = lists >= 0
    safe = np.where(mask, lists, 0)
    vecs = np.asarray(corpus)[safe]
    return IVFIndex(centroids=cent,
                    lists=jnp.asarray(lists),
                    list_vecs=jnp.asarray(vecs),
                    list_mask=jnp.asarray(mask),
                    spill=spill)


def search(index: IVFIndex, queries: jax.Array, k: int, nprobe: int = 8
           ) -> tuple[jax.Array, jax.Array]:
    """Probe the nprobe nearest cells per query. Returns (scores [Q, k],
    corpus row ids [Q, k]); scores = -squared-euclidean (higher = closer)."""
    q = jnp.asarray(queries, jnp.float32)
    cent = index.centroids
    d2c = (jnp.sum(q * q, 1)[:, None]
           - 2 * jnp.matmul(q, cent.T, precision=EXACT)
           + jnp.sum(cent * cent, 1)[None, :])
    _, cells = jax.lax.top_k(-d2c, nprobe)          # [Q, P]
    vecs = index.list_vecs[cells]                   # [Q, P, cap, d]
    ids = index.lists[cells]                        # [Q, P, cap]
    mask = index.list_mask[cells]
    s = (2.0 * jnp.einsum("qd,qpcd->qpc", q, vecs, precision=EXACT)
         - jnp.sum(vecs * vecs, -1)
         - jnp.sum(q * q, -1)[:, None, None])
    s = jnp.where(mask, s, -jnp.inf)
    qn, p, cap = s.shape
    v, flat = jax.lax.top_k(s.reshape(qn, p * cap), k)
    return v, jnp.take_along_axis(ids.reshape(qn, p * cap), flat, axis=1)


def recall_vs_exact(index: IVFIndex, corpus: jax.Array, queries: jax.Array,
                    k: int, nprobe: int) -> float:
    from ..core.metrics import knn_indices, set_overlap

    exact = knn_indices(jnp.asarray(queries), jnp.asarray(corpus), k)
    _, got = search(index, queries, k, nprobe)
    return float(set_overlap(exact, got))
