"""Fused distance + online top-k Pallas TPU kernel (the retrieval hot path).

TPU adaptation of the FAISS CPU scan (DESIGN.md §4): the database is tiled
into VMEM blocks; Q·Dᵀ runs on the MXU; the per-query running top-k lives in
VMEM scratch and is maintained with a *branchless iterative max-mask* pass
(k sweeps over the candidate tile — heaps don't vectorize, k max-reductions
do). Streaming across DB tiles mirrors FlashAttention's online softmax, but
the merged statistic is a top-k set instead of (m, l).

Grid: (Q/bq, N/bn) with the DB-tile axis innermost (TPU grids iterate
sequentially, so the scratch carry is valid across the N sweep).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import EXACT, NEG_INF


def _topk_update(run_v, run_i, cand_v, cand_i, k: int):
    """Merge [bq, k] running with [bq, bn] candidates -> new [bq, k].
    Branchless: k sweeps of (max, argmax, mask) over the concatenation."""
    allv = jnp.concatenate([run_v, cand_v], axis=1)  # [bq, k+bn]
    alli = jnp.concatenate([run_i, cand_i], axis=1)
    outv = jnp.zeros_like(run_v)
    outi = jnp.zeros_like(run_i)
    width = allv.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, allv.shape, 1)

    def body(j, carry):
        allv, outv, outi = carry
        m = jnp.max(allv, axis=1, keepdims=True)       # [bq, 1]
        # first column holding the max (argmax's tie rule); the id is then
        # read with a masked sum — Mosaic has no in-kernel gather
        am = jnp.min(jnp.where(allv == m, col, width), axis=1,
                     keepdims=True)
        hit = col == am
        gi = jnp.sum(jnp.where(hit, alli, 0), axis=1)
        outv = _set_col(outv, j, m[:, 0])
        outi = _set_col(outi, j, gi)
        allv = jnp.where(hit, NEG_INF, allv)           # mask the selected
        return allv, outv, outi

    allv, outv, outi = jax.lax.fori_loop(0, k, body, (allv, outv, outi))
    return outv, outi


def _set_col(x, j, v):
    """x[:, j] = v without scatter (TPU-friendly select on iota)."""
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col == j, v[:, None].astype(x.dtype), x)


def _kernel(q_ref, db_ref, d2_ref, vals_ref, idx_ref, acc_v, acc_i, *,
            k: int, bn: int, n_total: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_v[...] = jnp.full_like(acc_v, NEG_INF)
        acc_i[...] = jnp.full_like(acc_i, -1)

    q = q_ref[...]
    d = db_ref[...]
    # similarity = 2 q·d - ||d||^2 (the -||q||^2 constant is added by ops.py)
    s = 2.0 * jnp.dot(q, d.T, precision=EXACT,
                      preferred_element_type=jnp.float32) - d2_ref[...]
    base = j * bn
    cand_i = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    nv, ni = _topk_update(acc_v[...], acc_i[...], s, cand_i, k)
    acc_v[...] = nv
    acc_i[...] = ni

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        vals_ref[...] = acc_v[...]
        idx_ref[...] = acc_i[...]


def l2_topk_pallas(queries: jax.Array, db: jax.Array, db_sq: jax.Array,
                   k: int, *, bq: int = 128, bn: int = 512,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """queries [Q, d], db [N, d], db_sq [N] = ||d||^2 (precomputed once per
    corpus). Q % bq == 0 and N % bn == 0 (ops.py pads)."""
    qn, d = queries.shape
    n, _ = db.shape
    grid = (qn // bq, n // bn)
    kernel = functools.partial(_kernel, k=k, bn=bn, n_total=n)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, db, db_sq.reshape(1, n))
    return vals, idx
