"""Shared model primitives: norms, sharded embedding lookup, mesh context.

``MeshCtx`` carries (mesh, rules) through model code. When ``mesh is None``
(unit tests, single-device smoke runs) every collective helper degrades to
its local pure-jnp equivalent — same math, no shard_map — so correctness
tests never depend on device topology.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..distributed.partitioning import MeshAxes, default_rules, spec_for, usable_axes


@dataclass(frozen=True)
class MeshCtx:
    mesh: Optional[Mesh] = None
    rules: dict[str, MeshAxes] = field(default_factory=default_rules)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        r = self.rules.get("batch", ())
        if r is None:
            return ()
        return (r,) if isinstance(r, str) else tuple(r)

    def axis_size(self, logical: str) -> int:
        """Product of mesh-axis sizes a logical name maps to (1 if unmapped)."""
        if self.mesh is None:
            return 1
        r = self.rules.get(logical)
        if r is None:
            return 1
        axes = (r,) if isinstance(r, str) else r
        out = 1
        for a in axes:
            out *= self.mesh.shape.get(a, 1)
        return out

    def used_axes(self, dim: int, logical: str) -> tuple[str, ...]:
        """Mesh axes that actually shard a dim of this size (after fallback)."""
        if self.mesh is None:
            return ()
        return usable_axes(dim, logical, self.rules, self.mesh)

    def shards_for(self, dim: int, logical: str) -> int:
        out = 1
        for a in self.used_axes(dim, logical):
            out *= self.mesh.shape[a]
        return out

    def constrain(self, x: jax.Array, *logical: Optional[str]) -> jax.Array:
        """with_sharding_constraint by logical axis names (no-op without mesh)."""
        if self.mesh is None:
            return x
        spec = spec_for(x.shape, tuple(logical), self.rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def pspec(self, shape: tuple[int, ...], *logical: Optional[str]) -> P:
        if self.mesh is None:
            return P()
        return spec_for(shape, tuple(logical), self.rules, self.mesh)


NULL_CTX = MeshCtx(mesh=None)


# ---------------------------------------------------------------------------
# Explicit Megatron-SP boundaries (hillclimb A5, EXPERIMENTS.md §Perf):
# GSPMD resolves the seq-parallel <-> tensor-parallel transitions as fp32
# all-reduce + slice (observed 16x the minimal traffic); these shard_map
# helpers pin the exact collective (bf16 all-gather / psum_scatter on the
# sequence dim) and transpose correctly under AD.
# ---------------------------------------------------------------------------
def sp_all_gather(x: jax.Array, ctx: "MeshCtx") -> jax.Array:
    """[B, S(seq_sp-sharded), d] -> [B, S, d] gathered, in x.dtype."""
    if ctx.mesh is None or ctx.axis_size("seq_sp") == 1:
        return x
    mesh = ctx.mesh
    in_spec = ctx.pspec(x.shape, "batch", "seq_sp", None)
    out_spec = ctx.pspec(x.shape, "batch", None, None)

    def f(xl):
        return jax.lax.all_gather(xl, "model", axis=1, tiled=True)

    return jax.shard_map(f, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(x)


def row_parallel_out_proj(x: jax.Array, w: jax.Array, ctx: "MeshCtx",
                          in_logical: str = "qkv_out") -> jax.Array:
    """y = x @ w with the contraction dim sharded over ``model``: partial
    products psum_scatter (bf16) straight into the seq-sharded layout.

    x: [B, S, K] (K sharded over model); w: [K(model), d(data-FSDP)].
    Returns [B, S(seq_sp), d].
    """
    if ctx.mesh is None or ctx.axis_size("seq_sp") == 1:
        return x @ w
    mesh = ctx.mesh
    b, s, k = x.shape
    d = w.shape[1]
    x_spec = ctx.pspec(x.shape, "batch", None, in_logical)
    w_spec = ctx.pspec(w.shape, in_logical, "embed_fsdp")
    out_spec = ctx.pspec((b, s, d), "batch", "seq_sp", None)
    fsdp_axes = ctx.used_axes(d, "embed_fsdp")

    def f(xl, wl):
        if fsdp_axes:
            wl = jax.lax.all_gather(wl, fsdp_axes, axis=1, tiled=True)
        part = jnp.einsum("bsk,kd->bsd", xl, wl,
                          preferred_element_type=jnp.float32)
        return jax.lax.psum_scatter(part.astype(xl.dtype), "model",
                                    scatter_dimension=1, tiled=True)

    return jax.shard_map(f, mesh=mesh, in_specs=(x_spec, w_spec),
                         out_specs=out_spec, check_vma=False)(x, w)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def pad_to_multiple(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# Vocab/row-sharded embedding lookup (JAX has no EmbeddingBag / sharded gather
# primitive — this masked-psum lookup IS the system's embedding engine, used
# by the LM input embedding and every recsys table.)
# ---------------------------------------------------------------------------
def sharded_embedding_lookup(
    table: jax.Array,          # [V, d], rows sharded over `row_axes`
    ids: jax.Array,            # int32 [...], sharded over batch axes on dim 0
    ctx: MeshCtx,
    row_logical: str = "vocab",
    ids_logical: tuple[Optional[str], ...] = ("batch",),
    compute_dtype: Any = jnp.bfloat16,
    scatter_dim_logical: Optional[str] = None,
) -> jax.Array:
    """out[..., :] = table[ids] with the table row-sharded.

    Every row shard looks up the ids that fall in its range (clipped take +
    validity mask) and the partial results are psum'd over the row axes —
    the standard TPU vocab-parallel embedding pattern. Row axes must be
    disjoint from the ids' batch axes (enforced by the "table_rows"/"vocab"
    rules mapping to "model" only).

    ``scatter_dim_logical`` (hillclimb A1): when the consumer wants dim 1 of
    the output sharded over the SAME axes (e.g. the LM residual stream is
    seq-sharded over "model" = the vocab axes), a psum_scatter delivers it
    directly — 16x less reduce traffic than psum + slice.
    """
    if ctx.mesh is None or ctx.axis_size(row_logical) == 1:
        # clip like production embedding engines (hash collisions fold into
        # the last row rather than poisoning the batch with NaN fills)
        return jnp.take(table, ids, axis=0, mode="clip").astype(compute_dtype)

    mesh = ctx.mesh
    row_rule = ctx.rules[row_logical]
    row_axes = (row_rule,) if isinstance(row_rule, str) else tuple(row_rule)
    row_axes = tuple(a for a in row_axes if a in mesh.shape)
    n_shards = 1
    for a in row_axes:
        n_shards *= mesh.shape[a]
    assert table.shape[0] % n_shards == 0, (table.shape, n_shards)

    scatter = (scatter_dim_logical is not None and ids.ndim >= 2
               and ids.shape[1] % n_shards == 0
               and ctx.used_axes(ids.shape[1], scatter_dim_logical) == row_axes)

    table_spec = ctx.pspec(table.shape, row_logical, *([None] * (table.ndim - 1)))
    ids_spec = ctx.pspec(ids.shape, *ids_logical, *([None] * (ids.ndim - len(ids_logical))))
    out_shape = ids.shape + table.shape[1:]
    out_logical = list(ids_logical) + [None] * (len(out_shape) - len(ids_logical))
    if scatter:
        out_logical[1] = scatter_dim_logical
    out_spec = ctx.pspec(out_shape, *out_logical)

    def local(tbl, ids_l):
        vloc = tbl.shape[0]
        # linear shard index over the row axes
        shard = jnp.zeros((), jnp.int32)
        for a in row_axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        start = shard * vloc
        rel = ids_l - start
        valid = (rel >= 0) & (rel < vloc)
        rel = jnp.clip(rel, 0, vloc - 1)
        out = jnp.take(tbl.astype(compute_dtype), rel, axis=0, mode="clip")
        out = jnp.where(valid[..., None], out, 0)
        if scatter:
            return jax.lax.psum_scatter(out, row_axes, scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(out, row_axes)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(table_spec, ids_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(table, ids)


def embedding_bag(
    table: jax.Array,           # [V, d]
    ids: jax.Array,             # [B, L] int32 multi-hot bags (padded)
    lengths: jax.Array,         # [B] valid prefix length per bag
    ctx: MeshCtx,
    mode: str = "mean",
    row_logical: str = "table_rows",
    compute_dtype: Any = jnp.bfloat16,
) -> jax.Array:
    """torch.nn.EmbeddingBag equivalent: gather + masked segment reduce.

    Returns [B, d]. The bag reduction commutes with the cross-shard psum,
    so it runs INSIDE the lookup shard_map: the collective moves [B, d]
    instead of [B, L, d] — an L-fold traffic cut (hillclimb B1, measured
    ~15x on two-tower serve_bulk; EXPERIMENTS.md §Perf)."""
    b, l = ids.shape
    if ctx.mesh is None or ctx.axis_size(row_logical) == 1:
        e = jnp.take(table, ids, axis=0, mode="clip").astype(compute_dtype)
        mask = (jnp.arange(l)[None, :] < lengths[:, None]).astype(e.dtype)
        s = jnp.sum(e * mask[..., None], axis=1)
    else:
        mesh = ctx.mesh
        row_rule = ctx.rules[row_logical]
        row_axes = (row_rule,) if isinstance(row_rule, str) else tuple(row_rule)
        row_axes = tuple(a for a in row_axes if a in mesh.shape)
        table_spec = ctx.pspec(table.shape, row_logical, None)
        ids_spec = ctx.pspec(ids.shape, "batch", None)
        len_spec = ctx.pspec(lengths.shape, "batch")
        out_spec = ctx.pspec((b, table.shape[1]), "batch", None)

        def local(tbl, ids_l, len_l):
            vloc = tbl.shape[0]
            shard = jnp.zeros((), jnp.int32)
            for a in row_axes:
                shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
            rel = ids_l - shard * vloc
            valid = (rel >= 0) & (rel < vloc)
            rel = jnp.clip(rel, 0, vloc - 1)
            e = jnp.take(tbl.astype(compute_dtype), rel, axis=0, mode="clip")
            mask = valid & (jnp.arange(ids_l.shape[1])[None, :]
                            < len_l[:, None])
            partial = jnp.einsum("bld,bl->bd", e,
                                 mask.astype(e.dtype))
            return jax.lax.psum(partial, row_axes)  # [B_loc, d] only

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(table_spec, ids_spec, len_spec),
                           out_specs=out_spec, check_vma=False)
        s = fn(table, ids, lengths)
    if mode == "sum":
        return s
    if mode == "mean":
        return s / jnp.maximum(lengths[:, None].astype(s.dtype), 1)
    raise ValueError(mode)
