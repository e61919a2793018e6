"""Distributed exact vector search: sharded scan + global top-k merge.

The corpus is row-sharded over every mesh axis ("db_rows"). Each shard scores
its slab with one XLA matmul at ``EXACT`` precision and takes its local
``lax.top_k``; the global merge all-gathers only the per-shard (k values,
k global indices) — k * n_shards scalars — and reduces them with the
deterministic ``topk_merge`` kernel. The euclidean score needs each row's
``‖x‖²``: a caller that holds the corpus (``FlatIndex``) computes it once
with :func:`row_norms` where the rows are placed and passes it in, sharded
like the rows, so the scan reads its slab once per batch; without it the
scan sums the slab's squares again on every call.

Three invariants (regression-tested in tests/test_sharded.py) that the
original version of this module violated:

* **ragged corpora** — when ``n % n_shards != 0`` the corpus is padded up
  to ``n_shards * ceil(n / n_shards)`` rows and pad rows are pinned to
  ``NEG_INF`` / ``PAD_ID`` before they can reach the merge; global ids are
  mapped with the padded slab size, so no tail row is dropped or mislabeled.
* **small shards** — per-shard ``top_k`` is clamped to the slab size and
  padded back to ``k`` with ``(NEG_INF, PAD_ID)`` (the ``l2_topk``
  convention), so ``k > n_loc`` cannot crash ``lax.top_k``.
* **deterministic merge** — score ties break by the smaller global index
  (``topk_merge``), never by gather order, so the result is bitwise
  invariant to the shard count.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..distributed.partitioning import _flat_axes
from ..kernels.common import EXACT, NEG_INF, PAD_ID
from ..kernels.topk_merge.ops import topk_merge
from ..models.common import MeshCtx


def _padded_topk(s: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k`` along the last axis, clamped to the axis size and
    padded back to ``k`` with ``(NEG_INF, PAD_ID)`` when k overflows it."""
    n = s.shape[-1]
    kl = min(k, n)
    v, i = jax.lax.top_k(s, kl)
    if kl < k:
        pad = k - kl
        v = jnp.concatenate(
            [v, jnp.full((*v.shape[:-1], pad), NEG_INF, v.dtype)], -1)
        i = jnp.concatenate(
            [i, jnp.full((*i.shape[:-1], pad), PAD_ID, i.dtype)], -1)
    return v, i


def local_topk_scores(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    return _padded_topk(scores, k)


def _shard_axes(ctx: MeshCtx, logical: str) -> tuple[tuple[str, ...], int]:
    """Mesh axes a logical name shards over, WITHOUT the divisibility
    filter of ``usable_axes`` — ragged sizes are handled by padding the
    slab, not by silently degrading to replication."""
    if ctx.mesh is None:
        return (), 1
    axes = tuple(a for a in _flat_axes(ctx.rules.get(logical))
                 if a in ctx.mesh.shape and ctx.mesh.shape[a] > 1)
    return axes, math.prod(ctx.mesh.shape[a] for a in axes) if axes else 1


def _linear_shard_index(mesh, axes) -> jax.Array:
    shard = jnp.zeros((), jnp.int32)
    for a in axes:
        shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
    return shard


def distributed_topk(scores: jax.Array, k: int, ctx: MeshCtx,
                     logical: str = "db_rows") -> tuple[jax.Array, jax.Array]:
    """scores [N] (higher=better), row-sharded -> (vals [k], global idx [k])."""
    n = scores.shape[0]
    axes, n_shards = _shard_axes(ctx, logical)
    if n_shards == 1:
        return _padded_topk(scores, k)

    mesh = ctx.mesh
    n_loc = -(-n // n_shards)           # ceil: last shard may be ragged
    n_pad = n_loc * n_shards
    if n_pad > n:
        scores = jnp.pad(scores, (0, n_pad - n), constant_values=NEG_INF)
    kl = min(k, n_loc)
    s_spec = ctx.pspec((n_pad,), logical)
    r_spec = ctx.pspec((k,))

    def f(s_l):
        v, i = jax.lax.top_k(s_l, kl)
        shard = _linear_shard_index(mesh, axes)
        gi = i + shard * n_loc
        v = jnp.where(gi < n, v, NEG_INF)       # pad rows never win
        gi = jnp.where(gi < n, gi, PAD_ID)
        if kl < k:
            v = jnp.concatenate(
                [v, jnp.full((k - kl,), NEG_INF, v.dtype)])
            gi = jnp.concatenate(
                [gi, jnp.full((k - kl,), PAD_ID, gi.dtype)])
        vs = jax.lax.all_gather(v, axes, axis=0, tiled=True)   # [k*n_shards]
        gis = jax.lax.all_gather(gi, axes, axis=0, tiled=True)
        vg, ig = topk_merge(vs[None, :], gis[None, :], k)
        return vg[0], ig[0]

    fn = jax.shard_map(f, mesh=mesh, in_specs=(s_spec,),
                       out_specs=(r_spec, r_spec), check_vma=False)
    return fn(scores)


def _sq_norms(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return jnp.sum(x32 * x32, -1)


#: ``‖x‖²`` of every row of a placed corpus [N, d] -> [N] float32, the
#: expression the euclidean scan computes when it is given none. Jitted, so
#: the norms of a row-sharded corpus are summed on the devices and come out
#: sharded like its rows.
row_norms = jax.jit(_sq_norms)


def sharded_scores(queries: jax.Array, db: jax.Array, metric: str,
                   ctx: MeshCtx, norms: jax.Array | None = None
                   ) -> jax.Array:
    """[Q, N] similarity scores (higher = closer) with db row-sharded.

    ``norms`` [N] are the rows' held ``row_norms(db)``, read by the
    euclidean score in place of a pass over ``db``; ``None`` computes
    them here. The cosine score normalises ``db`` on every call."""
    q32 = queries.astype(jnp.float32)
    db = ctx.constrain(db, "db_rows", None)
    d32 = db.astype(jnp.float32)
    if metric == "cosine":
        qn = q32 / jnp.maximum(jnp.linalg.norm(q32, -1, keepdims=True), 1e-12)
        dn = d32 / jnp.maximum(jnp.linalg.norm(d32, -1, keepdims=True), 1e-12)
        s = jnp.matmul(qn, dn.T, precision=EXACT)
    elif metric == "euclidean":
        q2 = _sq_norms(q32)[:, None]
        d2 = (_sq_norms(d32) if norms is None else norms)[None, :]
        s = -(q2 - 2.0 * jnp.matmul(q32, d32.T, precision=EXACT)
              + d2)  # negative squared distance
    else:
        raise ValueError(metric)
    return ctx.constrain(s, None, "db_rows")


def place_rows(x: np.ndarray, ctx: MeshCtx) -> jax.Array:
    """Put host rows [N, d] on the device(s). With a mesh the rows go
    row-sharded over "db_rows" straight from the host — each device
    receives only its slab, and the corpus is never staged on one device.
    A sharded array cannot be ragged, so the last slab is zero-padded to
    ``n_shards * ceil(N / n_shards)`` rows; :func:`search` takes the real
    row count ``n`` and pins the pad rows."""
    axes, n_shards = _shard_axes(ctx, "db_rows")
    if n_shards == 1:
        return jnp.asarray(x, jnp.float32)
    n, d = x.shape
    n_loc = -(-n // n_shards)

    def slab(idx):
        rows = x[idx[0].start:min(idx[0].stop, n)]
        return np.pad(rows, ((0, n_loc - rows.shape[0]), (0, 0)))

    return jax.make_array_from_callback(
        (n_loc * n_shards, d), NamedSharding(ctx.mesh, P(axes, None)), slab)


def search(queries: jax.Array, db: jax.Array, k: int, ctx: MeshCtx,
           metric: str = "euclidean", alive: jax.Array | None = None,
           n: int | None = None, norms: jax.Array | None = None
           ) -> tuple[jax.Array, jax.Array]:
    """Exact k-NN: returns (scores [Q, k], indices [Q, k]).

    ``alive`` (bool [N]) tombstones db rows: a dead row is pinned to
    ``(NEG_INF, PAD_ID)`` before the local top-k on every shard, so it can
    never surface — same contract as ``l2_topk``'s ``db_mask`` operand.
    ``alive=None`` leaves the static path bitwise untouched. ``n`` is the
    real row count of a ``db`` that :func:`place_rows` padded (default:
    every row is real). ``norms`` (float32 [N]) are ``row_norms(db)``,
    row-sharded like ``db``: the euclidean scan reads them instead of
    summing every row's squares again (``None``: it does)."""
    n = db.shape[0] if n is None else n
    axes, n_shards = _shard_axes(ctx, "db_rows")
    if n_shards == 1:
        s = sharded_scores(queries, db, metric, ctx, norms)
        if alive is None:
            return _padded_topk(s, k)
        s = jnp.where(alive[None, :], s, NEG_INF)
        v, i = _padded_topk(s, k)
        i = jnp.where(v <= NEG_INF / 2, PAD_ID, i)
        return jnp.where(i == PAD_ID, NEG_INF, v), i

    mesh = ctx.mesh
    n_loc = -(-n // n_shards)           # ceil: last shard may be ragged
    n_pad = n_loc * n_shards
    if n_pad > db.shape[0]:
        db = jnp.pad(db, ((0, n_pad - db.shape[0]), (0, 0)))
    rows = {}  # the optional per-row operands, row-sharded like db
    for name, x in (("norms", norms), ("alive", alive)):
        if x is not None:
            rows[name] = (x if x.shape[0] == n_pad else
                          jnp.pad(x, (0, n_pad - x.shape[0])))
    kl = min(k, n_loc)
    q_spec = ctx.pspec(queries.shape)          # queries replicated
    db_spec = ctx.pspec((n_pad, db.shape[1]), "db_rows", None)
    row_spec = ctx.pspec((n_pad,), "db_rows")
    out_spec = ctx.pspec((queries.shape[0], k))

    def f(q_l, db_l, rows_l):
        with jax.named_scope("shard_scan"):
            s = sharded_scores(q_l, db_l, metric, MeshCtx(mesh=None),
                               rows_l.get("norms"))
            shard = _linear_shard_index(mesh, axes)
            # pin pad rows BEFORE the local top-k: a padded (zero) row must
            # not displace a real candidate inside the shard
            grow = shard * n_loc + jnp.arange(s.shape[1], dtype=jnp.int32)
            keep = grow[None, :] < n
            if "alive" in rows_l:  # tombstones: the never-wins lane of pads
                keep = keep & rows_l["alive"][None, :]
            s = jnp.where(keep, s, NEG_INF)
            v, i = jax.lax.top_k(s, kl)             # [Q, kl] local
            gi = shard * n_loc + i
            dead = (gi >= n) | (v <= NEG_INF / 2)
            v = jnp.where(dead, NEG_INF, v)
            gi = jnp.where(dead, PAD_ID, gi)
            if kl < k:
                pad = k - kl
                v = jnp.concatenate(
                    [v, jnp.full((v.shape[0], pad), NEG_INF, v.dtype)], 1)
                gi = jnp.concatenate(
                    [gi, jnp.full((gi.shape[0], pad), PAD_ID, gi.dtype)], 1)
        with jax.named_scope("topk_merge"):
            vs = jax.lax.all_gather(v, axes, axis=1, tiled=True)  # [Q, k*S]
            gis = jax.lax.all_gather(gi, axes, axis=1, tiled=True)
            return topk_merge(vs, gis, k)

    fn = jax.shard_map(f, mesh=mesh,
                       in_specs=(q_spec, db_spec,
                                 {name: row_spec for name in rows}),
                       out_specs=(out_spec, out_spec), check_vma=False)
    return fn(queries, db, rows)
