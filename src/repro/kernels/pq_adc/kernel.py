"""Fused PQ ADC Pallas TPU kernel: LUT build + code gather + online top-k.

The ADC scan is the hot path of a PQ index: per query build an [m, ksub]
LUT of exact query-to-centroid distances, then for every stored code sum m
LUT entries and keep the running top-k. Three fusions keep it on-chip:

* the LUT is built ONCE per query block (first DB-tile step) from the query
  tile and the full codebooks — both resident in VMEM — and parked in VMEM
  scratch for the whole N sweep;
* the gather is reformulated as a one-hot matmul: a [bn, m*ksub] 0/1 matrix
  built from the code tile by iota-compare, contracted against the flat LUT
  on the MXU — TPUs have no fast arbitrary gather, but they do have a
  128x128 systolic array (same trick as embedding lookups via one-hot);
* the per-query running top-k reuses the branchless iterative max-mask
  merge of ``l2_topk`` (heaps don't vectorize; k max-reductions do).

Grid (Q/bq, N/bn), DB-tile axis innermost — TPU grids iterate sequentially,
so LUT + top-k scratch carry across the N sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import EXACT, NEG_INF
from ..l2_topk.kernel import _topk_update


def _kernel(q_ref, cb_ref, codes_ref, pen_ref, vals_ref, idx_ref,
            lut_ref, acc_v, acc_i, *, k: int, m: int, ksub: int, dsub: int,
            bn: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_v[...] = jnp.full_like(acc_v, NEG_INF)
        acc_i[...] = jnp.full_like(acc_i, -1)
        q = q_ref[...]                                  # [bq, m*dsub]
        for mm in range(m):                             # m is small + static
            qs = q[:, mm * dsub:(mm + 1) * dsub]        # [bq, dsub]
            cbm = cb_ref[mm * ksub:(mm + 1) * ksub, :]  # [ksub, dsub]
            lut_m = (jnp.sum(qs * qs, 1)[:, None]
                     - 2.0 * jnp.dot(qs, cbm.T, precision=EXACT,
                                     preferred_element_type=jnp.float32)
                     + jnp.sum(cbm * cbm, 1)[None, :])
            lut_ref[:, mm * ksub:(mm + 1) * ksub] = lut_m

    codes = codes_ref[...]                              # [bn, m] int32
    # one-hot [bn, m*ksub]: oh[n, mm*ksub + c] = (codes[n, mm] == c)
    oh = (codes[:, :, None]
          == jax.lax.broadcasted_iota(jnp.int32, (bn, m, ksub), 2))
    oh = oh.astype(jnp.float32).reshape(bn, m * ksub)
    dist = jnp.dot(lut_ref[...], oh.T, precision=EXACT,
                   preferred_element_type=jnp.float32)  # [bq, bn]
    s = -dist - pen_ref[...]
    cand_i = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    nv, ni = _topk_update(acc_v[...], acc_i[...], s, cand_i, k)
    acc_v[...] = nv
    acc_i[...] = ni

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        vals_ref[...] = acc_v[...]
        idx_ref[...] = acc_i[...]


def pq_adc_pallas(queries: jax.Array, codebooks_flat: jax.Array,
                  codes: jax.Array, penalty: jax.Array, k: int, *,
                  m: int, ksub: int, dsub: int, bq: int = 128, bn: int = 512,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """queries [Q, m*dsub] f32, codebooks_flat [m*ksub, dsub] f32, codes
    [N, m] int32, penalty [N] f32 (1e30 on padded rows so they never win).
    Q % bq == 0 and N % bn == 0 (ops.py pads)."""
    qn, d = queries.shape
    n = codes.shape[0]
    grid = (qn // bq, n // bn)
    kernel = functools.partial(_kernel, k=k, m=m, ksub=ksub, dsub=dsub, bn=bn)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((m * ksub, dsub), lambda i, j: (0, 0)),
            pl.BlockSpec((bn, m), lambda i, j: (j, 0)),
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
            pltpu.VMEM((bq, m * ksub), jnp.float32),
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, codebooks_flat, codes, penalty.reshape(1, n))
    return vals, idx
