"""GraphSAGE (Hamilton et al., arXiv:1706.02216) in JAX.

JAX has no sparse-adjacency SpMM (BCOO only) — message passing is built from
``jnp.take`` (gather along the edge list) + ``jax.ops.segment_sum`` (scatter
by destination), which IS the system's GNN kernel (kernel_taxonomy §GNN).

Three execution regimes, matching the assigned shape cells:
  * full-batch (cora / ogbn-products): node features row-sharded over the
    whole mesh; per-shard edge gather + segment-sum partials; explicit
    all-gather(h) -> local scatter -> reduce-scatter(out) via shard_map so
    GSPMD can never fall back to gathering the edge tensors.
  * sampled minibatch (reddit, fanout 15-10): the *host-side* CSR uniform
    sampler (sampler.py) emits fixed-shape [B, f1, (f2), d] feature tensors;
    the device program is dense (GSPMD batch-shards it).
  * batched small graphs (molecule): graphs flattened with node-index
    offsets so one segment_sum serves the whole batch.

Layer: h' = relu(W · [h_v ; agg_{u in N(v)} h_u]) (concat form, mean agg),
followed by L2 normalization as in the paper.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ...configs.base import GNNConfig, ShapeCell
from ...distributed.partitioning import ParamDef, init_from_schema
from ..common import MeshCtx, NULL_CTX


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------
def schema(cfg: GNNConfig, d_feat: int, n_classes: int) -> dict:
    pdt = jnp.dtype(cfg.param_dtype)
    dims = [d_feat] + [cfg.d_hidden] * cfg.n_layers
    out: dict[str, Any] = {}
    for i in range(cfg.n_layers):
        out[f"w{i}"] = ParamDef((2 * dims[i], dims[i + 1]), (None, None), pdt)
        out[f"b{i}"] = ParamDef((dims[i + 1],), (None,), pdt, init="zeros")
    out["w_out"] = ParamDef((cfg.d_hidden, n_classes), (None, None), pdt)
    out["b_out"] = ParamDef((n_classes,), (None,), pdt, init="zeros")
    return out


def init(cfg: GNNConfig, d_feat: int, n_classes: int, key: jax.Array):
    return init_from_schema(schema(cfg, d_feat, n_classes), key)


def _l2norm(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def _sage_combine(h_self, h_agg, w, b, aggregator: str, last: bool):
    z = jnp.concatenate([h_self, h_agg], -1) @ w + b
    z = jax.nn.relu(z)
    return z if last else _l2norm(z)


# ---------------------------------------------------------------------------
# Full-batch message passing (sharded)
# ---------------------------------------------------------------------------
def mean_aggregate(h: jax.Array, src: jax.Array, dst: jax.Array,
                   n_nodes: int, ctx: MeshCtx, aggregator: str = "mean"
                   ) -> jax.Array:
    """agg[v] = reduce_{(u,v) in E} h[u]. h row-sharded, edges sharded."""
    if ctx.mesh is None or ctx.shards_for(n_nodes, "db_rows") == 1:
        msg = jnp.take(h, src, axis=0)
        s = jax.ops.segment_sum(msg, dst, num_segments=n_nodes)
        deg = jax.ops.segment_sum(jnp.ones_like(dst, h.dtype), dst,
                                  num_segments=n_nodes)
        if aggregator == "sum":
            return s
        return s / jnp.maximum(deg, 1.0)[:, None]

    mesh = ctx.mesh
    axes = ctx.used_axes(n_nodes, "db_rows")
    h_spec = ctx.pspec(h.shape, "db_rows", None)
    e_spec = ctx.pspec(src.shape, "db_rows")

    def f(h_l, src_l, dst_l):
        h_full = jax.lax.all_gather(h_l, axes, axis=0, tiled=True)  # [N, d]
        msg = jnp.take(h_full, src_l, axis=0)
        partial = jax.ops.segment_sum(msg, dst_l, num_segments=n_nodes)
        deg = jax.ops.segment_sum(jnp.ones_like(dst_l, h_l.dtype), dst_l,
                                  num_segments=n_nodes)
        out = jax.lax.psum_scatter(partial, axes, scatter_dimension=0,
                                   tiled=True)
        deg = jax.lax.psum_scatter(deg, axes, scatter_dimension=0, tiled=True)
        if aggregator == "sum":
            return out
        return out / jnp.maximum(deg, 1.0)[:, None]

    fn = jax.shard_map(f, mesh=mesh, in_specs=(h_spec, e_spec, e_spec),
                       out_specs=h_spec, check_vma=False)
    return fn(h, src, dst)


def full_batch_logits(params, feats, src, dst, cfg: GNNConfig, ctx: MeshCtx):
    n = feats.shape[0]
    h = ctx.constrain(feats, "db_rows", None)
    for i in range(cfg.n_layers):
        agg = mean_aggregate(h, src, dst, n, ctx, cfg.aggregator)
        h = _sage_combine(h, agg, params[f"w{i}"], params[f"b{i}"],
                          cfg.aggregator, last=(i == cfg.n_layers - 1))
        h = ctx.constrain(h, "db_rows", None)
    return h @ params["w_out"] + params["b_out"], h


def full_batch_loss(params, batch, cfg: GNNConfig, ctx: MeshCtx):
    logits, _ = full_batch_logits(params, batch["features"], batch["src"],
                                  batch["dst"], cfg, ctx)
    labels = batch["labels"]
    # node_mask excludes rows added by padding N to a mesh multiple
    mask = batch.get("node_mask", jnp.ones_like(labels, jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = jnp.sum((lse - gold) * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom
    return loss, {"acc": acc}


# ---------------------------------------------------------------------------
# Sampled minibatch (fixed fanout tensors from the host sampler)
# ---------------------------------------------------------------------------
def minibatch_logits(params, batch, cfg: GNNConfig, ctx: MeshCtx):
    """batch: x_seed [B,d], x_n1 [B,f1,d], x_n2 [B,f1,f2,d] (2-layer case)."""
    assert cfg.n_layers == 2, "fanout pipeline built for the 2-layer config"
    x0, x1, x2 = batch["x_seed"], batch["x_n1"], batch["x_n2"]
    x0 = ctx.constrain(x0, "batch", None)
    # layer 1 applied at depth-1 nodes (aggregate their depth-2 samples)...
    agg1 = x2.mean(axis=2)
    h1_n1 = _sage_combine(x1, agg1, params["w0"], params["b0"],
                          cfg.aggregator, last=False)
    # ...and at the seeds (aggregate depth-1 samples)
    h1_seed = _sage_combine(x0, x1.mean(axis=1), params["w0"], params["b0"],
                            cfg.aggregator, last=False)
    # layer 2 at the seeds
    h2 = _sage_combine(h1_seed, h1_n1.mean(axis=1), params["w1"], params["b1"],
                       cfg.aggregator, last=True)
    return h2 @ params["w_out"] + params["b_out"], h2


def minibatch_loss(params, batch, cfg: GNNConfig, ctx: MeshCtx):
    logits, _ = minibatch_logits(params, batch, cfg, ctx)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    loss = jnp.mean(lse - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"acc": acc}


# ---------------------------------------------------------------------------
# Batched small graphs (molecule): flatten + offset segment ids
# ---------------------------------------------------------------------------
def batched_graphs_logits(params, batch, cfg: GNNConfig, ctx: MeshCtx):
    """features [G, n, d]; edges [G, e, 2] (+ edge_mask [G, e]); per-graph
    classification via mean readout."""
    feats, edges = batch["features"], batch["edges"]
    emask = batch["edge_mask"]
    g, n, d = feats.shape
    _, e, _ = edges.shape
    h = ctx.constrain(feats, "batch", None, None).reshape(g * n, d)
    offs = (jnp.arange(g) * n)[:, None]
    src = (edges[..., 0] + offs).reshape(-1)
    dst = (edges[..., 1] + offs).reshape(-1)
    # masked edges scatter to a dummy segment
    dst = jnp.where(emask.reshape(-1) > 0, dst, g * n)
    for i in range(cfg.n_layers):
        msg = jnp.take(h, src, axis=0)
        s = jax.ops.segment_sum(msg, dst, num_segments=g * n + 1)[: g * n]
        deg = jax.ops.segment_sum(emask.reshape(-1).astype(h.dtype), dst,
                                  num_segments=g * n + 1)[: g * n]
        agg = s / jnp.maximum(deg, 1.0)[:, None]
        h = _sage_combine(h, agg, params[f"w{i}"], params[f"b{i}"],
                          cfg.aggregator, last=(i == cfg.n_layers - 1))
    readout = h.reshape(g, n, -1).mean(axis=1)
    return readout @ params["w_out"] + params["b_out"], readout


def batched_graphs_loss(params, batch, cfg: GNNConfig, ctx: MeshCtx):
    logits, _ = batched_graphs_logits(params, batch, cfg, ctx)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    loss = jnp.mean(lse - gold)
    return loss, {}


def make_train_step(cfg: GNNConfig, ctx: MeshCtx, opt, kind: str):
    loss_map = {"full_graph": full_batch_loss, "minibatch": minibatch_loss,
                "batched_graphs": batched_graphs_loss}
    lf = loss_map[kind]

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            params, batch, cfg, ctx)
        params, opt_state, om = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step
