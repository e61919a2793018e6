"""Fused gather + L2 + beam-merge Pallas TPU kernel (one HNSW hop).

The batched graph traversal expands one frontier node per live query per
hop; the work of a hop is "score W gathered neighbors against each query
and fold them into that query's running top-ef beam". Done naively that is
a [Q, W, d] gather materialized in HBM, a distance reduce, and a top-k —
three dispatches and triple traffic. This kernel fuses all of it using the
house idioms:

* *scalar-prefetch gather* (same trick as ``embedding_bag``): the neighbor
  ids are prefetched into SMEM and drive the DB BlockSpec index map, so
  each grid step DMAs the one sublane tile holding the wanted corpus row
  HBM->VMEM — the [Q, W, d] gather never exists;
* the squared-L2 score uses the same ``2 q.v - ||v||^2 - ||q||^2`` form as
  ``l2_topk``, with ``||v||^2`` prefetch-gathered from the packed graph's
  precomputed norms;
* the beam merge reuses ``l2_topk``'s branchless iterative max-mask
  ``_topk_update`` — masked slots (id -1: pad links, already-visited
  nodes) score ``NEG_INF`` and keep their -1 id, so the merged beam stays
  sorted descending with pads at the tail.

Tiling: Mosaic DMAs whole (8, 128) tiles, so a block's last two dims must
be tile multiples or span the array. The gathered operands are laid out
for that by :func:`gather_layout` — corpus rows padded to a whole sublane
tile and fetched as ``(rows, d)`` blocks, per-node scalars packed
lane-dense as ``[*, 128]`` and fetched as one ``(8, 128)`` block — and the
wanted row / scalar is picked in VMEM with an iota mask. Queries and beams
ride as full-array blocks (a few KiB), resident for the whole grid.

Grid (Q, W), neighbor-slot axis innermost: TPU grids iterate sequentially,
so the per-query candidate scratch accumulates across the W sweep and the
merge runs once per query on the last slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF
from ..l2_topk.kernel import _topk_update

_LANES = 128
_SUBLANES = 8


def tile_rows(dtype) -> int:
    """Sublane rows of one HBM tile of ``dtype`` (8 for 32-bit, 16 for
    16-bit): the row granularity a gathered block must have."""
    return _SUBLANES * max(1, 4 // jnp.dtype(dtype).itemsize)


def gather_layout(rows: jax.Array, per_node: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Lay out a hop's gathered operands for the chip: ``rows`` [N, C]
    padded to a whole sublane tile of rows, ``per_node`` [N] packed
    lane-dense as [ceil(N / 1024) * 8, 128]. Pads are never selected (ids
    stay < N). A no-op copy-free reshape when N is already tile-aligned,
    so a traversal calls it once per search, outside its hop loop."""
    n = rows.shape[0]
    rpad = (-n) % tile_rows(rows.dtype)
    if rpad:
        rows = jnp.pad(rows, ((0, rpad), (0, 0)))
    tile = _SUBLANES * _LANES
    spad = (-n) % tile
    if spad:
        per_node = jnp.pad(per_node, (0, spad))
    return rows, per_node.reshape(-1, _LANES)


def pick_row(block, r):
    """Row ``r`` of a VMEM block as a [1, C] slice (masked sum: exact,
    since every other row contributes zero)."""
    row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(row == r, block, 0), axis=0, keepdims=True)


def pick_lane(block, p):
    """Element ``p`` (row-major) of an (8, 128) VMEM block, as [1, 1]."""
    row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    x = jnp.where(row * _LANES + col == p, block, 0)
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def merge_into_row(i, bv_ref, bi_ref, cv, ci, vout_ref, iout_ref, ef: int):
    """Merge query ``i``'s candidates (cv/ci [1, W]) into its beam row and
    write that row of the resident [Q, ef] outputs."""
    nv, ni = _topk_update(pick_row(bv_ref[...], i).astype(jnp.float32),
                          pick_row(bi_ref[...], i), cv, ci, ef)
    # once every remaining entry ties at NEG_INF the iterative argmax
    # re-picks the first exhausted slot; those slots are pads, so
    # canonicalize them to (NEG_INF, -1) exactly like the ref
    ni = jnp.where(nv <= NEG_INF, -1, ni)
    nv = jnp.where(ni >= 0, nv, NEG_INF)
    row = jax.lax.broadcasted_iota(jnp.int32, vout_ref.shape, 0)
    vout_ref[...] = jnp.where(row == i, nv, vout_ref[...])
    iout_ref[...] = jnp.where(row == i, ni, iout_ref[...])


def _kernel(ids_ref, q_ref, row_ref, rsq_ref, bv_ref, bi_ref,
            vout_ref, iout_ref, cv_ref, ci_ref, *, w_slots: int, ef: int,
            rows: int):
    i = pl.program_id(0)
    w = pl.program_id(1)
    raw = ids_ref[i * w_slots + w]
    safe = jnp.maximum(raw, 0)
    q = pick_row(q_ref[...], i).astype(jnp.float32)                # [1, d]
    r = pick_row(row_ref[...], safe % rows).astype(jnp.float32)    # [1, d]
    rsq = pick_lane(rsq_ref[...], safe % (_SUBLANES * _LANES))     # [1, 1]
    s = (2.0 * jnp.sum(q * r, axis=1, keepdims=True) - rsq
         - jnp.sum(q * q, axis=1, keepdims=True))                  # -||q-v||^2
    s = jnp.where(raw < 0, NEG_INF, s)
    col = jax.lax.broadcasted_iota(jnp.int32, cv_ref.shape, 1)
    cv_ref[...] = jnp.where(col == w, s, cv_ref[...])
    ci_ref[...] = jnp.where(col == w, raw, ci_ref[...])

    @pl.when(w == w_slots - 1)
    def _():
        merge_into_row(i, bv_ref, bi_ref, cv_ref[...], ci_ref[...],
                       vout_ref, iout_ref, ef)


def graph_beam_pallas(queries: jax.Array, db: jax.Array, db_sq: jax.Array,
                      nbr_ids: jax.Array, beam_v: jax.Array,
                      beam_i: jax.Array, *,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """queries [Q, d], db [N', d] and db_sq [R, 128] as laid out by
    :func:`gather_layout` from the corpus and its norms ``||v||^2``,
    nbr_ids [Q, W] int32 (-1 = masked), beam_v/beam_i [Q, ef]. Returns the
    merged beam, sorted descending."""
    qn, d = queries.shape
    w_slots = nbr_ids.shape[1]
    ef = beam_v.shape[1]
    rows = tile_rows(db.dtype)
    ids = nbr_ids.reshape(-1)
    kernel = functools.partial(_kernel, w_slots=w_slots, ef=ef, rows=rows)

    def node(i, w, ids):
        return jnp.maximum(ids[i * w_slots + w], 0)

    def whole(i, w, ids):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # neighbor ids: drive the row DMAs
        grid=(qn, w_slots),
        in_specs=[
            pl.BlockSpec((qn, d), whole),
            # the sublane tile holding the neighbor's row, and the
            # (8, 128) tile holding its norm, id-selected
            pl.BlockSpec((rows, d), lambda i, w, ids: (node(i, w, ids)
                                                       // rows, 0)),
            pl.BlockSpec((_SUBLANES, _LANES),
                         lambda i, w, ids: (node(i, w, ids)
                                            // (_SUBLANES * _LANES), 0)),
            pl.BlockSpec((qn, ef), whole),
            pl.BlockSpec((qn, ef), whole),
        ],
        out_specs=[
            pl.BlockSpec((qn, ef), whole),
            pl.BlockSpec((qn, ef), whole),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, w_slots), jnp.float32),
            pltpu.VMEM((1, w_slots), jnp.int32),
        ],
    )
    vals, idx = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qn, ef), jnp.float32),
            jax.ShapeDtypeStruct((qn, ef), jnp.int32),
        ],
        interpret=interpret,
    )(ids, queries, db, db_sq, beam_v, beam_i)
    return vals, idx
