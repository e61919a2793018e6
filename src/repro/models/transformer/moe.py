"""Mixture-of-Experts: capacity-based scatter dispatch + expert parallelism.

TPU-native design (DESIGN.md §5): the O(T·E·C) one-hot dispatch einsum of
GShard is memory/FLOP-infeasible at 128 experts, so dispatch is a *local*
sort-free scatter (argsort by expert id -> rank-in-expert -> scatter into a
[E, C_dev, d] buffer with capacity drops), followed by an explicit
all-to-all over the ``model`` axis (expert parallelism). Expert weights are
additionally FSDP-sharded on d_model over ``data`` and gathered by GSPMD at
use. The grouped GEMMs run as plain einsums over the expert-sharded buffer.

Everything is differentiable: gates flow through take_along_axis on the
router probs; scatter/gather transpose to gather/scatter-add; all_to_all
transposes to all_to_all.

Routing: softmax router, top-k with renormalized gates (Qwen3-style),
Switch-style load-balance aux loss + router z-loss.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...configs.base import TransformerConfig
from ..common import MeshCtx


class RouteResult(NamedTuple):
    slot: jax.Array    # [T, k] int32 flat slot in the (E*C_dev [+overflow]) buffer
    gates: jax.Array   # [T, k] float32 renormalized top-k gates
    aux: dict[str, jax.Array]


def _route_and_slot(x, router_w, n_experts: int, top_k: int, capacity: int):
    """Local routing + slot assignment for a shard's tokens. x: [t, d]."""
    t = x.shape[0]
    # routing logits accumulate in f32 on the MXU without materializing an
    # f32 copy of the token stream (which the outer scan would then save)
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, top_k)  # [t, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)  # [t*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    ranks = jnp.arange(t * top_k) - jnp.searchsorted(sorted_e, sorted_e,
                                                     side="left")
    pos = jnp.zeros_like(ranks).at[order].set(ranks)  # rank within expert
    pos = pos.reshape(t, top_k)
    dropped = pos >= capacity
    slot = jnp.where(dropped, n_experts * capacity, eidx * capacity + pos)

    # aux losses (Switch LB + z-loss), per-token so the caller can mean() them
    me = probs.mean(0)  # [E] mean router prob
    assign = jnp.zeros((n_experts,), jnp.float32).at[flat_e].add(1.0)
    ce = assign / (t * top_k)  # fraction of assignments per expert
    lb = n_experts * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    frac_dropped = dropped.mean()
    aux = {"load_balance": lb, "router_z": z, "frac_dropped": frac_dropped}
    return RouteResult(slot=slot, gates=gates, aux=aux)


def _dispatch_local(x, slot, capacity: int, n_experts: int):
    """Scatter tokens into the [E*C (+1 overflow), d] buffer. x: [t, d]."""
    t, d = x.shape
    k = slot.shape[1]
    token_of = jnp.arange(t * k) // k
    x_rep = jnp.take(x, token_of, axis=0)  # [t*k, d]
    buf = jnp.zeros((n_experts * capacity + 1, d), x.dtype)
    buf = buf.at[slot.reshape(-1)].set(x_rep, mode="drop")
    return buf[: n_experts * capacity].reshape(n_experts, capacity, d)


def _combine_local(y_buf, slot, gates, t: int):
    """Gather expert outputs back to tokens. y_buf: [E, C, d] -> [t, d]."""
    e, c, d = y_buf.shape
    flat = jnp.concatenate(
        [y_buf.reshape(e * c, d), jnp.zeros((1, d), y_buf.dtype)], 0)
    yk = jnp.take(flat, slot.reshape(-1), axis=0).reshape(t, -1, d)
    return jnp.einsum("tkd,tk->td", yk, gates.astype(y_buf.dtype))


def _expert_ffn(buf, wg, wu, wd, compute_dtype):
    """Grouped SwiGLU over the expert dim: buf [E, R, d]; w* [E, d, f]/[E, f, d]."""
    h = jnp.einsum("erd,edf->erf", buf, wg.astype(compute_dtype))
    u = jnp.einsum("erd,edf->erf", buf, wu.astype(compute_dtype))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(compute_dtype) * u
    return jnp.einsum("erf,efd->erd", h, wd.astype(compute_dtype))


def moe_block(
    x: jax.Array,                 # [T, d] tokens (flattened batch*seq)
    router_w: jax.Array,          # [d, E]
    wg: jax.Array, wu: jax.Array, wd: jax.Array,  # [E, d, f] / [E, f, d]
    cfg: TransformerConfig,
    ctx: MeshCtx,
    capacity_override: Optional[int] = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Returns (y [T, d], aux losses)."""
    e, k = cfg.n_experts, cfg.moe_top_k
    t_global, d = x.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = x.astype(compute_dtype)

    tok_axes = ctx.used_axes(t_global, "tokens") if ctx.mesh is not None else ()
    n_tok_shards = 1
    for a in tok_axes:
        n_tok_shards *= ctx.mesh.shape[a]
    ep = ctx.axis_size("experts")  # expert-parallel degree (model axis)
    t_loc = t_global // max(n_tok_shards, 1)
    if capacity_override is not None:
        cap = capacity_override
    else:
        cap = max(int(t_loc * k / e * cfg.capacity_factor), 1)
        cap = min(cap, t_loc)  # an expert can get at most t_loc local tokens

    if ctx.mesh is None or (n_tok_shards == 1 and ep == 1):
        route = _route_and_slot(x, router_w, e, k, cap)
        buf = _dispatch_local(x, route.slot, cap, e)
        y_buf = _expert_ffn(buf, wg, wu, wd, compute_dtype)
        y = _combine_local(y_buf, route.slot, route.gates, t_global)
        return y.astype(compute_dtype), {k_: v for k_, v in route.aux.items()}

    mesh = ctx.mesh
    assert e % ep == 0, (e, ep)
    e_loc = e // ep
    tokens_on_model = "model" in tok_axes
    tok_spec = ctx.pspec(x.shape, "tokens", None)
    slot_spec = ctx.pspec((t_global, k), "tokens", None)
    # global buffer: [E, rows, d]; rows dim carries the (pod,data) shards,
    # E carries the model (expert-parallel) shards.
    n_pd = max(n_tok_shards // (ep if tokens_on_model else 1), 1)
    rows_per_shard = (ep * cap) if tokens_on_model else cap
    buf_shape = (e, n_pd * rows_per_shard, d)
    buf_spec = ctx.pspec(buf_shape, "experts", "batch", None)
    aux_keys = ("load_balance", "router_z", "frac_dropped")

    def dispatch(x_l, rw):
        route = _route_and_slot(x_l, rw, e, k, cap)
        buf = _dispatch_local(x_l, route.slot, cap, e)  # [E, cap, d]
        if tokens_on_model:
            # expert-parallel all-to-all: send expert block j to model-peer j
            buf = buf.reshape(ep, e_loc, cap, d)
            recv = jax.lax.all_to_all(buf, "model", split_axis=0,
                                      concat_axis=0, tiled=True)
            recv = jnp.moveaxis(recv, 0, 1).reshape(e_loc, ep * cap, d)
        else:
            # tokens replicated over model: each shard just takes its block
            me = jax.lax.axis_index("model")
            recv = jax.lax.dynamic_slice_in_dim(buf, me * e_loc, e_loc, 0)
        # per-token aux values, broadcast so the outer mean is global
        aux_tok = {k_: jnp.full((x_l.shape[0],), v)
                   for k_, v in route.aux.items()}
        return recv, route.slot, route.gates, aux_tok

    aux_spec = ctx.pspec((t_global,), "tokens")
    disp = jax.shard_map(
        dispatch, mesh=mesh,
        in_specs=(tok_spec, ctx.pspec(router_w.shape, None, None)),
        out_specs=(buf_spec, slot_spec, slot_spec,
                   {k_: aux_spec for k_ in aux_keys}),
        check_vma=False)
    buf, slot, gates, aux_tok = disp(x, router_w)

    # expert GEMMs under GSPMD: E sharded over model; FSDP d gathered on use
    buf = jax.lax.with_sharding_constraint(
        buf, jax.sharding.NamedSharding(mesh, buf_spec))
    y_buf = _expert_ffn(buf, wg, wu, wd, compute_dtype)
    y_buf = jax.lax.with_sharding_constraint(
        y_buf, jax.sharding.NamedSharding(mesh, buf_spec))

    def combine(yb, slot_l, gates_l):
        if tokens_on_model:
            yb = yb.reshape(e_loc, ep, cap, d)
            back = jax.lax.all_to_all(jnp.moveaxis(yb, 1, 0), "model",
                                      split_axis=0, concat_axis=0, tiled=True)
            # back: [ep(expert-shard), e_loc, cap, d] -> [E, cap, d]
            y_full = back.reshape(e, cap, d)
        else:
            # every shard holds outputs for its e_loc experts; sum the rest
            y_full = jax.lax.all_gather(yb, "model", axis=0,
                                        tiled=True)  # [E, cap, d]
        return _combine_local(y_full, slot_l, gates_l, slot_l.shape[0])

    comb = jax.shard_map(
        combine, mesh=mesh,
        in_specs=(buf_spec, slot_spec, slot_spec),
        out_specs=tok_spec, check_vma=False)
    y = comb(y_buf, slot, gates)
    aux = {k_: v.mean() for k_, v in aux_tok.items()}
    return y.astype(compute_dtype), aux
