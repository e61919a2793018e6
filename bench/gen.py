"""Synthetic embedding corpora, made on the device from a seed.

The mixture model is that of ``repro.data.synthetic.embedding_corpus``
(kept here so the benchmark's data cannot change under a program PR),
rewritten in ``jax.random``: a shared low-rank basis with a power-law
spectrum, one mildly rotated copy of it per cluster, cluster centres in
the dominant half of the shared subspace, and isotropic noise. Rows are
i.i.d. draws from the mixture, so the query pool is a held-out draw from
the same mixture, as SIFT and Cohere ship their query sets apart from the
base set.

Rows are made in fixed-size blocks; block ``b`` depends only on the seed
and ``b``, so the reference can make any block again without the rest.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65536


@dataclass(frozen=True)
class DataSpec:
    rows: int
    dim: int
    query_pool: int
    n_clusters: int
    intrinsic: int
    spectrum_decay: float = 0.7
    noise: float = 0.02
    normalize: bool = False
    block_rows: int = BLOCK_ROWS

    @classmethod
    def from_config(cls, cfg: dict) -> "DataSpec":
        d = cfg["data"]
        return cls(rows=cfg["rows"], dim=cfg["dim"],
                   query_pool=cfg["query_pool"], n_clusters=d["n_clusters"],
                   intrinsic=d["intrinsic"],
                   spectrum_decay=d.get("spectrum_decay", 0.7),
                   noise=d.get("noise", 0.02),
                   normalize=d.get("normalize", False),
                   block_rows=d.get("block_rows", BLOCK_ROWS))

    @property
    def n_blocks(self) -> int:
        return -(-self.rows // self.block_rows)


def _keys(seed: int):
    root = jax.random.key(seed)
    return (jax.random.fold_in(root, 0), jax.random.fold_in(root, 1),
            jax.random.fold_in(root, 2))


@partial(jax.jit, static_argnames=("dim", "n_clusters", "intrinsic",
                                   "decay"))
def _mixture(key, *, dim, n_clusters, intrinsic, decay):
    r = intrinsic
    k_shared, k_pert, k_centre = jax.random.split(key, 3)
    spec = jnp.arange(1, r + 1, dtype=jnp.float32) ** (-decay)
    shared, _ = jnp.linalg.qr(jax.random.normal(k_shared, (dim, r)))
    pert = 0.15 * jax.random.normal(k_pert, (n_clusters, dim, r))
    bases, _ = jnp.linalg.qr(shared[None] + pert)          # [C, dim, r]
    half = max(r // 2, 1)
    cz = jnp.zeros((n_clusters, r)).at[:, :half].set(
        1.5 * jax.random.normal(k_centre, (n_clusters, half)) * spec[:half])
    centres = jnp.matmul(cz, shared.T, precision="highest")  # [C, dim]
    return bases, centres, spec


@partial(jax.jit, static_argnames=("n", "noise", "normalize"))
def _rows(key, bases, centres, spec, *, n, noise, normalize):
    n_clusters, dim, r = bases.shape
    k_c, k_z, k_n = jax.random.split(key, 3)
    cluster = jax.random.randint(k_c, (n,), 0, n_clusters)
    z = jax.random.normal(k_z, (n, r)) * spec[None, :]

    def add_cluster(j, acc):
        part = jnp.matmul(z, bases[j].T)
        return acc + jnp.where((cluster == j)[:, None], part, 0.0)

    x = jax.lax.fori_loop(0, n_clusters, add_cluster,
                          jnp.zeros((n, dim), jnp.float32))
    x = x + centres[cluster] + noise * jax.random.normal(k_n, (n, dim))
    if normalize:
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    return x


class Corpus:
    """The deployment's data for one seed: corpus blocks and query pool."""

    def __init__(self, spec: DataSpec, seed: int):
        self.spec = spec
        self._k_mix, self._k_rows, self._k_queries = _keys(seed)
        self._mix: dict = {}

    def _mixture_on(self, device):
        if device not in self._mix:
            s = self.spec
            key = self._k_mix if device is None else jax.device_put(
                self._k_mix, device)
            self._mix[device] = _mixture(
                key, dim=s.dim, n_clusters=s.n_clusters,
                intrinsic=s.intrinsic, decay=s.spectrum_decay)
        return self._mix[device]

    def _draw(self, key, n, device):
        s = self.spec
        if device is not None:
            key = jax.device_put(key, device)
        return _rows(key, *self._mixture_on(device), n=n, noise=s.noise,
                     normalize=s.normalize)

    def block(self, b: int, device=None) -> jax.Array:
        """Rows ``[b * block_rows, (b + 1) * block_rows)``, always a full
        block (one compiled shape); rows past ``rows`` are not corpus."""
        return self._draw(jax.random.fold_in(self._k_rows, b),
                          self.spec.block_rows, device)

    def queries(self, device=None) -> np.ndarray:
        """The held-out query pool, on the host."""
        return np.asarray(self._draw(self._k_queries, self.spec.query_pool,
                                     device))

    def host_rows(self, devices=(None,)) -> np.ndarray:
        """The whole corpus as one host array. Blocks are made round robin
        on ``devices``; each is copied out while later ones are made."""
        s = self.spec
        out = np.empty((s.rows, s.dim), np.float32)
        pending: deque = deque()

        def copy_out(b, blk):
            lo = b * s.block_rows
            hi = min(lo + s.block_rows, s.rows)
            out[lo:hi] = np.asarray(blk)[:hi - lo]

        for b in range(s.n_blocks):
            pending.append((b, self.block(b, devices[b % len(devices)])))
            if len(pending) > len(devices):
                copy_out(*pending.popleft())
        while pending:
            copy_out(*pending.popleft())
        return out
