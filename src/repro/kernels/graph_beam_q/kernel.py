"""Quantized gather + asymmetric-score + beam-merge Pallas TPU kernel.

The quantized twin of ``graph_beam/kernel.py``: one HNSW hop whose
neighbor gather reads stored *codes* (SQ8 or PQ payloads) instead of f32
corpus rows — at d=64 that is 68 gathered bytes per neighbor for SQ8 and
12 for PQ8x8 versus 260 for the f32 row+norm, which is the whole point:
at million-vector scale the hop is bandwidth-bound on exactly this DMA.

Same house idioms as the f32 hop, plus the codec algebra:

* *scalar-prefetch gather*: neighbor ids prefetched into SMEM drive the
  code-row BlockSpec index map, so each grid step DMAs the one sublane
  tile holding the wanted code row HBM->VMEM — the [Q, W, C] gather never
  exists (same tile layout as the f32 hop, ``gather_layout``);
* scoring is the unified affine form ``contract(q_op, code_row) +
  q_bias - node_bias`` (see ``ref.py``): SQ8 contracts the pre-scaled
  query against the raw codes (dequant-free asymmetric L2, the
  ``sq8_scan`` rearrangement); PQ contracts the per-query negated ADC
  LUT against a one-hot expansion of the code row — the same
  iota-compare one-hot-matmul gather as ``pq_adc`` (TPUs have no fast
  arbitrary gather; they do have an MXU);
* the beam merge reuses ``l2_topk``'s branchless ``_topk_update``;
  masked slots (id -1) score ``NEG_INF`` and keep their -1 id.

``mode``/``ksub`` are static: the sq8/pq branch is resolved at trace
time, so each compiled kernel contains exactly one scoring form.

Grid (Q, W), neighbor-slot axis innermost: TPU grids iterate
sequentially, so the per-query candidate scratch accumulates across the
W sweep and the merge runs once per query on the last slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF
from ..graph_beam.kernel import (_LANES, _SUBLANES, merge_into_row,
                                 pick_lane, pick_row, tile_rows)


def _kernel(ids_ref, qop_ref, qb_ref, code_ref, nb_ref, bv_ref, bi_ref,
            vout_ref, iout_ref, cv_ref, ci_ref, *, w_slots: int, ef: int,
            mode: str, ksub: int, rows: int):
    i = pl.program_id(0)
    w = pl.program_id(1)
    raw = ids_ref[i * w_slots + w]
    safe = jnp.maximum(raw, 0)
    qop = pick_row(qop_ref[...], i).astype(jnp.float32)           # [1, Dop]
    c = pick_row(code_ref[...], safe % rows)                       # [1, C]
    if mode == "sq8":
        contrib = jnp.sum(qop * c.astype(jnp.float32), axis=1,
                          keepdims=True)
    else:
        # one-hot row [1, m*ksub]: oh[0, mm*ksub + j] = (codes[mm] == j);
        # contracting it against the flat LUT operand IS the per-subspace
        # LUT gather
        m = c.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (m, m * ksub), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (m, m * ksub), 0)
        want = jnp.transpose(c) + sub * ksub                       # [m, 1]
        oh = jnp.sum(jnp.where(lane == want, 1.0, 0.0), axis=0,
                     keepdims=True)                                # [1, m*ksub]
        contrib = jnp.sum(qop * oh, axis=1, keepdims=True)
    qb = pick_row(qb_ref[...], i)                                  # [1, 1]
    nb = pick_lane(nb_ref[...], safe % (_SUBLANES * _LANES))       # [1, 1]
    s = contrib + qb - nb
    s = jnp.where(raw < 0, NEG_INF, s)
    col = jax.lax.broadcasted_iota(jnp.int32, cv_ref.shape, 1)
    cv_ref[...] = jnp.where(col == w, s, cv_ref[...])
    ci_ref[...] = jnp.where(col == w, raw, ci_ref[...])

    @pl.when(w == w_slots - 1)
    def _():
        merge_into_row(i, bv_ref, bi_ref, cv_ref[...], ci_ref[...],
                       vout_ref, iout_ref, ef)


def graph_beam_q_pallas(q_op: jax.Array, q_bias: jax.Array, codes: jax.Array,
                        node_bias: jax.Array, nbr_ids: jax.Array,
                        beam_v: jax.Array, beam_i: jax.Array, *, mode: str,
                        ksub: int = 0, interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """q_op [Q, Dop] f32, q_bias [Q] f32, codes [N', C] int32 and
    node_bias [R, 128] f32 as laid out by
    :func:`repro.kernels.graph_beam.kernel.gather_layout` from the int32
    codes (``ops.py`` widens the stored uint8 — TPU tiling) and the [N]
    per-node bias, nbr_ids [Q, W] int32 (-1 = masked), beam_v/beam_i
    [Q, ef]. Returns the merged beam, sorted descending."""
    qn, dop = q_op.shape
    cw = codes.shape[1]
    w_slots = nbr_ids.shape[1]
    ef = beam_v.shape[1]
    rows = tile_rows(codes.dtype)
    ids = nbr_ids.reshape(-1)
    kernel = functools.partial(_kernel, w_slots=w_slots, ef=ef, mode=mode,
                               ksub=ksub, rows=rows)

    def node(i, w, ids):
        return jnp.maximum(ids[i * w_slots + w], 0)

    def whole(i, w, ids):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # neighbor ids: drive the code-row DMAs
        grid=(qn, w_slots),
        in_specs=[
            pl.BlockSpec((qn, dop), whole),
            pl.BlockSpec((qn, 1), whole),
            # the sublane tile holding the neighbor's code row, and the
            # (8, 128) tile holding its bias, id-selected
            pl.BlockSpec((rows, cw), lambda i, w, ids: (node(i, w, ids)
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
    )(ids, q_op, q_bias.reshape(qn, 1), codes, node_bias, beam_v, beam_i)
    return vals, idx
