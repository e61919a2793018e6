"""Two-stage retrieval with RAE (beyond-paper integration, DESIGN.md §2).

Stage 1 scans the *reduced* corpus (R^m, m << n) with the fused
distance+top-k engine for k * rerank_factor candidates — this is where the
paper's compression pays: scan FLOPs and bytes both shrink by n/m.
Stage 2 reranks only the candidates in the original space, recovering the
exact-metric ordering on the shortlist. The paper's k-NN preservation bound
(kappa(W), Eq. 16) governs stage-1 recall, which ``recall_vs_exact``
measures directly.

:func:`rerank_candidates` is the stage-2 engine shared by every two-stage
path (this module and ``api.TwoStageIndex``): it takes the PADDED
candidate matrix any stage-1 tier emits — IVF probes and the batched HNSW
beam both pad short rows with id -1 — gathers the candidate vectors
INSIDE the jit (XLA fuses the gather with the distance compute; the
serving path pays one dispatch, not two), pins pad slots to -inf, and
returns the exact top-k in the original space.

:func:`twostage_search` runs a whole two-stage search as one device
program: encode, stage 1 and rerank, each handed over as a
:class:`DevicePart` by the reducer, the base index and the rerank.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..core import rae as rae_lib
from ..models.common import MeshCtx
from . import distributed as ds


def rerank_candidates(queries: jax.Array, db_full: jax.Array,
                      cand: jax.Array, k: int,
                      metric: str = "euclidean"
                      ) -> tuple[jax.Array, jax.Array]:
    """Exact full-space rerank of a padded candidate matrix.

    ``queries`` [Q, n], ``db_full`` [N, n], ``cand`` [Q, k1] int (id -1 =
    pad from a short stage-1 row). Returns (scores [Q, k], indices [Q, k])
    — scores follow the engine convention (higher = closer). Jit-safe with
    ``k`` static; pads keep their -1 id but score -inf so they can never
    outrank a real candidate.
    """
    # gather INSIDE the jit: XLA fuses it with the distance compute (one
    # dispatch per search, and the [Q, k1, n] gather never round-trips)
    cand_vecs = jnp.take(db_full, cand, axis=0)  # [Q, k1, n]
    q32 = queries.astype(jnp.float32)
    c32 = cand_vecs.astype(jnp.float32)
    if metric == "cosine":
        qn = q32 / jnp.maximum(
            jnp.linalg.norm(q32, axis=-1, keepdims=True), 1e-12)
        cn = c32 / jnp.maximum(
            jnp.linalg.norm(c32, axis=-1, keepdims=True), 1e-12)
        s = jnp.einsum("qd,qcd->qc", qn, cn)
    else:
        s = -jnp.sum(jnp.square(c32 - q32[:, None, :]), -1)
    # a padded id (-1, wrapped to the LAST corpus row by jnp.take above)
    # keeps its -1 id but is pinned to -inf so it can never win
    s = jnp.where(cand >= 0, s, -jnp.inf)
    v, sel = jax.lax.top_k(s, k)
    return v, jnp.take_along_axis(cand, sel, axis=1)


def rerank_stage(db_full: jax.Array, queries: jax.Array, cand: jax.Array,
                 *, k: int, metric: str) -> tuple[jax.Array, jax.Array]:
    """:func:`rerank_candidates` in the form of a :class:`DevicePart`'s
    function: its operand, the full-space corpus, first."""
    return rerank_candidates(queries, db_full, cand, k, metric)


@dataclass(frozen=True, eq=False)
class DevicePart:
    """One stage of a search as a pure device function and its operands,
    for :func:`twostage_search` to run inside its one program.

    ``fn(operands, *inputs, **dict(static))`` reads nothing but its
    arguments. ``operands`` are the stage's arrays (a pytree): arguments
    of the program, never closed over, so a corpus or a store is neither
    baked into the program nor copied. ``static`` holds the stage's knobs
    as hashable ``(name, value)`` pairs; the program compiles once per
    ``spec``. ``name`` is the stage's ``jax.named_scope`` in the program
    (the name of its own program elsewhere). A stage-1 part returns
    ``(scores, ids, aux)``, and ``stats(aux)``, on the host, turns the
    read-back ``aux`` into its ``SearchResult.stats``."""

    name: str
    fn: Callable
    operands: Any
    static: tuple = ()
    stats: Callable[[Any], dict] = lambda aux: {}

    @property
    def spec(self) -> tuple:
        """Everything of the part but its operands."""
        return (self.name, self.fn, self.static)


def _run_stage(spec: tuple, operands: Any, *inputs):
    name, fn, static = spec
    with jax.named_scope(name):
        return fn(operands, *inputs, **dict(static))


@functools.partial(jax.jit, static_argnames=("encode", "stage1", "rerank"))
def twostage_search(queries: jax.Array, encode_ops: Any, stage1_ops: Any,
                    rerank_ops: Any, *, encode: tuple, stage1: tuple,
                    rerank: tuple) -> tuple[jax.Array, jax.Array, Any]:
    """A two-stage search as one program: queries [Q, n] -> reduced
    queries -> stage-1 candidates [Q, k1] -> full-space rerank. ``encode``,
    ``stage1`` and ``rerank`` are the parts' specs, the ``*_ops`` their
    operands. Returns (scores [Q, k], ids [Q, k], stage 1's aux)."""
    z = _run_stage(encode, encode_ops, queries)
    _, cand, aux = _run_stage(stage1, stage1_ops, z)
    scores, ids = _run_stage(rerank, rerank_ops, queries, cand)
    return scores, ids, aux


def encode_corpus(rae_params, db: jax.Array, ctx: MeshCtx,
                  chunk: int = 65536) -> jax.Array:
    """Encode a (possibly huge) corpus through W_e, preserving row sharding."""
    db = ctx.constrain(db, "db_rows", None)
    z = rae_lib.encode(rae_params, db.astype(jnp.float32))
    return ctx.constrain(z, "db_rows", None)


def two_stage_search(
    queries: jax.Array,       # [Q, n]
    db_full: jax.Array,       # [N, n] row-sharded
    db_reduced: jax.Array,    # [N, m] row-sharded (encode_corpus output)
    rae_params,
    k: int,
    ctx: MeshCtx,
    rerank_factor: int = 4,
    metric: str = "euclidean",
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores [Q, k], indices [Q, k]) in the ORIGINAL space."""
    zq = rae_lib.encode(rae_params, queries.astype(jnp.float32))
    k1 = min(k * rerank_factor, db_reduced.shape[0])
    _, cand = ds.search(zq, db_reduced, k1, ctx, metric=metric)  # [Q, k1]
    return rerank_candidates(queries, db_full, cand, k, metric)


def recall_vs_exact(queries, db_full, db_reduced, rae_params, k, ctx,
                    rerank_factor: int = 4, metric: str = "euclidean") -> float:
    """Recall@k of two-stage search against the exact full-space scan."""
    _, exact_idx = ds.search(queries, db_full, k, ctx, metric=metric)
    _, ts_idx = two_stage_search(queries, db_full, db_reduced, rae_params, k,
                                 ctx, rerank_factor, metric)
    inter = (exact_idx[:, :, None] == ts_idx[:, None, :]).any(-1)
    return float(jnp.mean(inter.astype(jnp.float32)))
