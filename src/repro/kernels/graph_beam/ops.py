"""Public wrapper: platform dispatch + row padding for the beam-hop kernel.

Unlike the scan kernels, the off-TPU path here is *pure numpy*, not a
jitted jnp ref: the batched HNSW traversal calls this once per hop from a
host-driven loop, and on CPU a jit dispatch per hop would cost more than
the hop itself. The pallas path IS jitted and pads the query-row count up
to a power of two (ids -1, beams -inf) so the per-hop live-row count —
which shrinks as queries finish — hits a handful of compile-cache entries
instead of one per distinct batch size; ``SearchEngine.warmup`` visits the
same pow2 buckets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import NEG_INF, gather_layout, graph_beam_pallas
from .ref import graph_beam_ref


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_padded(queries, db, db_sq, nbr_ids, beam_v, beam_i, interpret):
    db, db_sq = gather_layout(db, db_sq)
    return graph_beam_pallas(queries, db, db_sq, nbr_ids, beam_v, beam_i,
                             interpret=interpret)


def graph_beam(queries, db, nbr_ids, beam_v, beam_i, db_sq=None, q_sq=None,
               db_mask=None, impl: str = "auto", interpret: bool = False
               ) -> tuple[np.ndarray, np.ndarray]:
    """One fused traversal hop: gather ``nbr_ids`` rows of ``db``, score
    them against ``queries`` (-squared-L2), and merge into the running
    ``(beam_v, beam_i)`` top-ef beam.

    queries [Q, d]; db [N, d]; nbr_ids [Q, W] int32, -1 = masked (pad link
    or visited node — scores ``NEG_INF``, keeps id -1); beam_v/beam_i
    [Q, ef] sorted descending. Returns the merged beam (numpy), sorted
    descending, pads at the tail. ``db_sq``/``q_sq`` = optional
    precomputed squared norms (the packed graph supplies the former, the
    hop loop hoists the latter; the pallas kernel computes ``q_sq``
    on-chip and ignores the hint). ``db_mask`` (bool [N]) tombstones db
    rows: masked candidate ids are demoted to -1 before the hop, so a
    deleted row can never enter the beam on either impl.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "np"
    if impl == "np":
        return graph_beam_ref(queries, db, nbr_ids, beam_v, beam_i, db_sq,
                              q_sq, db_mask)
    if db_mask is not None:
        # demote tombstoned candidates to pad slots pre-kernel: the pallas
        # hop then needs no mask operand of its own
        ids_np = np.asarray(nbr_ids, np.int32)
        safe = np.where(ids_np >= 0, ids_np, 0)
        nbr_ids = np.where((ids_np >= 0) & np.asarray(db_mask, bool)[safe],
                           ids_np, -1)
    q = jnp.asarray(queries, jnp.float32)
    if db_sq is None:
        db_sq = jnp.sum(jnp.asarray(db, jnp.float32) ** 2, axis=-1)
    nq = q.shape[0]
    pad = _next_pow2(nq) - nq
    ids = jnp.asarray(nbr_ids, jnp.int32)
    bv = jnp.asarray(beam_v, jnp.float32)
    bi = jnp.asarray(beam_i, jnp.int32)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
        bv = jnp.pad(bv, ((0, pad), (0, 0)), constant_values=NEG_INF)
        bi = jnp.pad(bi, ((0, pad), (0, 0)), constant_values=-1)
    vals, idx = _pallas_padded(q, jnp.asarray(db), jnp.asarray(db_sq,
                                                              jnp.float32),
                               ids, bv, bi, interpret)
    return np.asarray(vals[:nq]), np.asarray(idx[:nq])
