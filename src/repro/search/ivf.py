"""IVF (inverted-file) coarse quantization in JAX — beyond-paper search tier.

FAISS-style two-level index: k-means coarse centroids partition the corpus;
queries probe the ``nprobe`` nearest cells and scan only those lists. On
TPU, ragged inverted lists become a *padded dense* layout (ids and a
validity mask [n_cells, cell_cap], each cell's members a prefix of its
slots) so the probe scan has fixed shapes — no host-side indirection in
the hot path. The member vectors are stored feature-major,
``[n_cells, D, L]`` (``kernels.ivf_scan.kernel.store_shape``): the
dimension padded to whole sublanes and the capacity to whole lanes, so the
TPU keeps the store row-major and the ``ivf_scan`` kernel streams only the
probed cells' real member blocks from it. Elsewhere the scan is the padded
gather and einsum.

Composes with the paper's RAE: build the IVF over the *reduced* corpus
(R^m) and rerank in R^n — compression shrinks both the centroid search and
the list scan, while kappa(W) (Eq. 16) bounds the extra recall loss.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import EXACT
from ..kernels.ivf_scan import ivf_scan
from ..kernels.ivf_scan.kernel import block_cols, store_shape


@dataclass
class IVFIndex:
    centroids: jax.Array   # [C, d]
    lists: jax.Array       # [C, cap] int32 corpus row ids (-1 = pad)
    list_vecs: jax.Array   # [C, D, L] member j of cell c = [c, :d, j]
    list_mask: jax.Array   # [C, cap] bool
    extent: jax.Array      # [C] int32 members per cell (its prefix length)
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
    return IVFIndex(centroids=cent,
                    lists=jnp.asarray(lists),
                    list_vecs=pack_store(np.asarray(corpus)[safe]),
                    list_mask=jnp.asarray(mask),
                    extent=jnp.asarray(mask.sum(axis=1), jnp.int32),
                    spill=spill)


def pack_store(rows: np.ndarray) -> jax.Array:
    """The feature-major device store of host member rows [C, cap, d]."""
    n_cells, cap, d = rows.shape
    store = np.zeros(store_shape(n_cells, cap, d), np.float32)

    def put(c):
        store[c, :d, :cap] = rows[c].T

    # a cell at a time on every core: numpy's strided copy runs on one
    # core at ~0.7 GB/s, seconds for a 1M-row store
    with ThreadPoolExecutor() as pool:
        list(pool.map(put, range(n_cells)))
    return jnp.asarray(store)


def store_rows(index: IVFIndex) -> np.ndarray:
    """The member rows [C, cap, d] of ``index``'s store, independent of the
    device layout (the saved and hashed form): a strided view of the
    store's host value, read-only where the backend shares its memory."""
    cap = index.lists.shape[1]
    d = index.centroids.shape[1]
    return np.swapaxes(np.asarray(index.list_vecs)[:, :d, :cap], 1, 2)


def probe_bytes(index: IVFIndex, sizes: np.ndarray) -> np.ndarray:
    """Bytes of the store the ``ivf_scan`` kernel reads for probed cells
    holding ``sizes`` members (any shape): whole blocks up to each extent,
    one block for an empty cell, clipped at the store's width."""
    _, depth, width = index.list_vecs.shape
    block = block_cols(width, depth)
    blocks = np.maximum(-(-np.asarray(sizes) // block), 1)
    return np.minimum(blocks * block, width) * depth * 4


def search(index: IVFIndex, queries: jax.Array, k: int, nprobe: int = 8
           ) -> tuple[jax.Array, jax.Array]:
    """Probe the nprobe nearest cells per query. Returns (scores [Q, k],
    corpus row ids [Q, k]); scores = -squared-euclidean (higher = closer)."""
    v, ids, _ = probe(index, queries, k, nprobe)
    return v, ids


def probe(index: IVFIndex, queries: jax.Array, k: int, nprobe: int = 8
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`search`, also returning the probed cells [Q, nprobe] (what
    the host's stats read). ``index.list_mask`` may have holes inside a
    cell's prefix (tombstones folded in); ``index.extent`` is the prefix
    length the scan reads."""
    q = jnp.asarray(queries, jnp.float32)
    cent = index.centroids
    d2c = (jnp.sum(q * q, 1)[:, None]
           - 2 * jnp.matmul(q, cent.T, precision=EXACT)
           + jnp.sum(cent * cent, 1)[None, :])
    _, cells = jax.lax.top_k(-d2c, nprobe)          # [Q, P]
    ids = index.lists[cells]                        # [Q, P, cap]
    mask = index.list_mask[cells]
    qn, p, cap = ids.shape
    s = ivf_scan(q, cells, index.extent, index.list_vecs)[:, :, :cap]
    s = jnp.where(mask, s, -jnp.inf)
    v, flat = jax.lax.top_k(s.reshape(qn, p * cap), k)
    return (v, jnp.take_along_axis(ids.reshape(qn, p * cap), flat, axis=1),
            cells)


def recall_vs_exact(index: IVFIndex, corpus: jax.Array, queries: jax.Array,
                    k: int, nprobe: int) -> float:
    from ..core.metrics import knn_indices, set_overlap

    exact = knn_indices(jnp.asarray(queries), jnp.asarray(corpus), k)
    _, got = search(index, queries, k, nprobe)
    return float(set_overlap(exact, got))
