"""The exact search that decides ``correct``.

Plain ``jax.numpy`` over corpus rows held in blocks on one or more
devices: squared L2 distances at ``precision="highest"`` (float32 on the
TPU's MXU; the control asks for a lower one), a running top-k per device, and a final merge on the host in
which ties go to the smaller row id. It shares nothing with the program
under test. ``rescore`` recomputes distances in float64 on the host.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Shard:
    """Corpus blocks resident on one device: ``rows`` [nb, B, d], block j
    holding global rows ``starts[j] .. starts[j] + valid[j]``."""

    rows: jax.Array
    starts: jax.Array   # [nb] int32
    valid: jax.Array    # [nb] int32


def shard_blocks(make_block, n_rows: int, block_rows: int, devices
                 ) -> list[Shard]:
    """Place blocks ``make_block(b, device)`` round robin on ``devices``."""
    n_blocks = -(-n_rows // block_rows)
    shards = []
    for i, dev in enumerate(devices):
        mine = list(range(i, n_blocks, len(devices)))
        if not mine:
            continue
        rows = jnp.stack([make_block(b, dev) for b in mine])
        starts = np.array([b * block_rows for b in mine], np.int32)
        valid = np.minimum(block_rows, n_rows - starts).astype(np.int32)
        shards.append(Shard(rows=rows,
                            starts=jax.device_put(starts, dev),
                            valid=jax.device_put(valid, dev)))
    return shards


def shards_from_array(x: np.ndarray, block_rows: int, devices
                      ) -> list[Shard]:
    """Blocks of a host array (tests, and corpora made on the host)."""
    x = np.asarray(x, np.float32)

    def make_block(b, dev):
        blk = x[b * block_rows:(b + 1) * block_rows]
        blk = np.pad(blk, ((0, block_rows - blk.shape[0]), (0, 0)))
        return jax.device_put(blk, dev)

    return shard_blocks(make_block, x.shape[0], block_rows, devices)


def _bf16(a: jax.Array) -> jax.Array:
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def dot(q: jax.Array, x: jax.Array, precision: str) -> jax.Array:
    """``q @ x.T`` at ``highest`` (float32) or at ``high`` (three bf16
    passes: hi*hi + hi*lo + lo*hi, written out so it means the same on
    every backend)."""
    exact = partial(jnp.matmul, precision="highest")  # bf16 products: exact
    if precision == "highest":
        return exact(q, x.T)
    if precision == "high":
        qh, xh = _bf16(q), _bf16(x)
        ql, xl = _bf16(q - qh), _bf16(x - xh)
        return exact(qh, xh.T) + exact(qh, xl.T) + exact(ql, xh.T)
    raise ValueError(f"unknown precision {precision!r}")


@partial(jax.jit, static_argnames=("k", "precision"))
def _scan_shard(q, rows, starts, valid, *, k, precision):
    """Exact top-k (smallest squared L2) of ``q`` over one device's blocks."""
    qn = jnp.sum(q * q, axis=1)[:, None]
    nq = q.shape[0]
    b = rows.shape[1]
    col = jnp.arange(b, dtype=jnp.int32)

    def step(carry, blk):
        best_d, best_i = carry
        x, start, n_valid = blk
        d = (qn - 2.0 * dot(q, x, precision)
             + jnp.sum(x * x, axis=1)[None, :])
        d = jnp.where(col[None, :] < n_valid, d, jnp.inf)
        kk = min(k, b)
        neg, pos = jax.lax.top_k(-d, kk)
        # running results first: among equal distances, top_k keeps the
        # earlier column, and every running id is below this block's ids
        cand_d = jnp.concatenate([best_d, -neg], axis=1)
        cand_i = jnp.concatenate([best_i, pos + start], axis=1)
        neg2, sel = jax.lax.top_k(-cand_d, k)
        return (-neg2, jnp.take_along_axis(cand_i, sel, axis=1)), None

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init, (rows, starts, valid))
    return best_d, best_i


def exact_topk(queries: np.ndarray, shards: list[Shard], k: int,
               precision: str = "highest", q_chunk: int = 512
               ) -> tuple[np.ndarray, np.ndarray]:
    """Exact k nearest rows by squared L2: (distances [Q, k] ascending,
    ids [Q, k]); ties go to the smaller id."""
    queries = np.asarray(queries, np.float32)
    nq = queries.shape[0]
    q_chunk = min(q_chunk, nq)
    out_d = np.empty((nq, k), np.float32)
    out_i = np.empty((nq, k), np.int64)
    for lo in range(0, nq, q_chunk):
        q = queries[lo:lo + q_chunk]
        n = q.shape[0]
        q = np.pad(q, ((0, q_chunk - n), (0, 0)))  # one compiled shape
        parts = [_scan_shard(jax.device_put(q, s.rows.devices().pop()),
                             s.rows, s.starts, s.valid, k=k,
                             precision=precision) for s in shards]
        d = np.concatenate([np.asarray(p[0])[:n] for p in parts], axis=1)
        i = np.concatenate([np.asarray(p[1])[:n] for p in parts], axis=1)
        i = np.where(i < 0, np.iinfo(np.int64).max, i.astype(np.int64))
        order = np.lexsort((i, d), axis=1)[:, :k]
        out_d[lo:lo + n] = np.take_along_axis(d, order, axis=1)
        out_i[lo:lo + n] = np.take_along_axis(i, order, axis=1)
    return out_d, out_i


def rescore(corpus: np.ndarray, queries: np.ndarray, ids: np.ndarray
            ) -> np.ndarray:
    """Squared L2 of each query [S, d] to its rows ``ids`` [S, k], in
    float64 on the host; an id outside the corpus scores +inf."""
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < corpus.shape[0])
    rows = corpus[np.where(ok, ids, 0)].astype(np.float64)
    q = np.asarray(queries, np.float64)[:, None, :]
    d = np.sum(np.square(rows - q), axis=-1)
    return np.where(ok, d, np.inf)
