"""Public wrapper: platform dispatch + row padding for the quantized hop.

Same shape as ``graph_beam/ops.py``: the off-TPU path is *pure numpy*
(the batched traversal calls this once per hop from a host-driven loop;
a jit dispatch per hop would dominate), the pallas path is jitted and
pads the query-row count to a power of two (ids -1, beams -inf) so the
shrinking live-row count hits a handful of compile-cache entries.

One codec-specific chore lives here: stored codes are uint8 (that is the
payload whose size the whole tier exists to shrink), but the TPU kernel
gathers (1, C) blocks and sub-byte/int8 tiling is not worth fighting for
a C-wide row — the pallas path widens codes to int32 on device (same
convention as ``pq_adc``, whose kernel also takes int32 codes). The
numpy ref reads the uint8 array directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..graph_beam.kernel import gather_layout
from .kernel import NEG_INF, graph_beam_q_pallas
from .ref import graph_beam_q_ref


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("mode", "ksub", "interpret"))
def _pallas_padded(q_op, q_bias, codes, node_bias, nbr_ids, beam_v, beam_i,
                   mode, ksub, interpret):
    codes, node_bias = gather_layout(codes.astype(jnp.int32), node_bias)
    return graph_beam_q_pallas(q_op, q_bias, codes, node_bias, nbr_ids,
                               beam_v, beam_i,
                               mode=mode, ksub=ksub, interpret=interpret)


def graph_beam_q(q_op, q_bias, codes, node_bias, nbr_ids, beam_v, beam_i,
                 db_mask=None, mode: str = "sq8", ksub: int = 0,
                 impl: str = "auto", interpret: bool = False
                 ) -> tuple[np.ndarray, np.ndarray]:
    """One fused quantized traversal hop: gather ``nbr_ids`` rows of the
    stored ``codes``, score them via the unified affine form
    ``contract(q_op, code_row) + q_bias - node_bias`` (SQ8 dequant-free
    asymmetric L2 / PQ negated-ADC-LUT — see ``ref.py`` for the operand
    contracts), and merge into the running ``(beam_v, beam_i)`` top-ef
    beam.

    q_op [Q, Dop] f32; q_bias [Q] f32; codes [N, C] uint8; node_bias [N]
    f32; nbr_ids [Q, W] int32, -1 = masked; beam_v/beam_i [Q, ef] sorted
    descending. ``mode`` = "sq8" | "pq" (``ksub`` = LUT stride, pq only).
    ``db_mask`` (bool [N]) tombstones code rows: masked candidate ids are
    demoted to -1 before the hop so a deleted row never enters the beam.
    Returns the merged beam (numpy), sorted descending, pads at the tail
    — byte-compatible with ``graph_beam``'s output, so the traversal
    drivers swap the two hops freely.
    """
    if mode not in ("sq8", "pq"):
        raise ValueError(f"graph_beam_q: mode must be 'sq8' or 'pq', "
                         f"got {mode!r}")
    if mode == "pq" and ksub < 1:
        raise ValueError("graph_beam_q: pq mode needs ksub >= 1 (the LUT "
                         "stride)")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "np"
    if impl == "np":
        return graph_beam_q_ref(q_op, q_bias, codes, node_bias, nbr_ids,
                                beam_v, beam_i, db_mask, mode, ksub)
    if db_mask is not None:
        # demote tombstoned candidates to pad slots pre-kernel (same
        # convention as graph_beam): no mask operand inside the kernel
        ids_np = np.asarray(nbr_ids, np.int32)
        safe = np.where(ids_np >= 0, ids_np, 0)
        nbr_ids = np.where((ids_np >= 0) & np.asarray(db_mask, bool)[safe],
                           ids_np, -1)
    qo = jnp.asarray(q_op, jnp.float32)
    qb = jnp.asarray(q_bias, jnp.float32)
    nq = qo.shape[0]
    pad = _next_pow2(nq) - nq
    ids = jnp.asarray(nbr_ids, jnp.int32)
    bv = jnp.asarray(beam_v, jnp.float32)
    bi = jnp.asarray(beam_i, jnp.int32)
    if pad:
        qo = jnp.pad(qo, ((0, pad), (0, 0)))
        qb = jnp.pad(qb, ((0, pad),))
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
        bv = jnp.pad(bv, ((0, pad), (0, 0)), constant_values=NEG_INF)
        bi = jnp.pad(bi, ((0, pad), (0, 0)), constant_values=-1)
    vals, idx = _pallas_padded(qo, qb, jnp.asarray(codes),
                               jnp.asarray(node_bias, jnp.float32), ids, bv,
                               bi, mode, ksub, interpret)
    return np.asarray(vals[:nq]), np.asarray(idx[:nq])
