"""Decoder-only transformer LM: dense + MoE, GQA, RoPE, SwiGLU, KV-cache decode.

Sharding (DESIGN.md §5, Megatron-SP style under GSPMD):
  * residual stream between blocks is sequence-sharded over ``model``
    ("seq_sp") — required for qwen3-235B activation memory to fit;
  * projections are TP-sharded on their qkv/mlp feature dims; FSDP shards
    every weight's d_model dim over ``data``; GSPMD inserts the AG/RS pairs;
  * attention runs head-TP or context-parallel (``resolve_scheme``);
  * decode uses the sequence-sharded KV cache (attention.decode_attention);
  * MoE uses scatter dispatch + expert-parallel all-to-all (moe.moe_block).

Layers are scanned (94-layer qwen3 compiles in seconds, not hours); remat
policy is full recompute per layer, so only the per-layer residual stream
(seq-sharded) is retained for backward.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...configs.base import TransformerConfig
from ...distributed.partitioning import (ParamDef, abstract_from_schema,
                                         init_from_schema)
from ..common import (MeshCtx, NULL_CTX, pad_to_multiple,
                      rms_norm, row_parallel_out_proj,
                      sharded_embedding_lookup, sp_all_gather)
from . import attention as attn_lib
from . import moe as moe_lib

VOCAB_PAD = 256


def padded_vocab(cfg: TransformerConfig) -> int:
    return pad_to_multiple(cfg.vocab_size, VOCAB_PAD)


def effective_heads(cfg: TransformerConfig, ctx: MeshCtx) -> tuple[int, int]:
    tp = ctx.axis_size("heads")
    h, kh = cfg.n_heads, cfg.n_kv_heads
    if cfg.pad_heads_to_tp and tp > 1 and h % tp != 0:
        return attn_lib.padded_head_layout(h, kh, tp)
    return h, kh


def resolve_scheme(cfg: TransformerConfig, ctx: MeshCtx) -> str:
    if cfg.attention_scheme != "auto":
        return cfg.attention_scheme
    tp = ctx.axis_size("heads")
    h, _ = effective_heads(cfg, ctx)
    return "tp" if (tp <= 1 or h % tp == 0) else "cp"


# ---------------------------------------------------------------------------
# Schema / init
# ---------------------------------------------------------------------------
def schema(cfg: TransformerConfig, ctx: MeshCtx = NULL_CTX) -> dict:
    L, d, dh = cfg.n_layers, cfg.d_model, cfg.d_head
    h, kh = effective_heads(cfg, ctx)
    pdt = jnp.dtype(cfg.param_dtype)
    v = padded_vocab(cfg)
    layers: dict[str, ParamDef] = {
        "ln1": ParamDef((L, d), ("stack", None), pdt, init="ones"),
        "wq": ParamDef((L, d, h * dh), ("stack", "embed_fsdp", "qkv_out"), pdt),
        "wk": ParamDef((L, d, kh * dh), ("stack", "embed_fsdp", "qkv_out"), pdt),
        "wv": ParamDef((L, d, kh * dh), ("stack", "embed_fsdp", "qkv_out"), pdt),
        "wo": ParamDef((L, h * dh, d), ("stack", "qkv_out", "embed_fsdp"), pdt),
        "ln2": ParamDef((L, d), ("stack", None), pdt, init="ones"),
    }
    if cfg.qkv_bias:
        layers["bq"] = ParamDef((L, h * dh), ("stack", "qkv_out"), pdt, init="zeros")
        layers["bk"] = ParamDef((L, kh * dh), ("stack", "qkv_out"), pdt, init="zeros")
        layers["bv"] = ParamDef((L, kh * dh), ("stack", "qkv_out"), pdt, init="zeros")
    if cfg.qk_norm:
        layers["q_norm"] = ParamDef((L, dh), ("stack", None), pdt, init="ones")
        layers["k_norm"] = ParamDef((L, dh), ("stack", None), pdt, init="ones")
    if cfg.family == "moe":
        e, f = cfg.n_experts, cfg.d_ff
        layers["router"] = ParamDef((L, d, e), ("stack", None, None), pdt)
        layers["wg_e"] = ParamDef((L, e, d, f), ("stack", "experts", "embed_fsdp", None), pdt)
        layers["wu_e"] = ParamDef((L, e, d, f), ("stack", "experts", "embed_fsdp", None), pdt)
        layers["wd_e"] = ParamDef((L, e, f, d), ("stack", "experts", None, "embed_fsdp"), pdt)
    else:
        f = cfg.d_ff
        layers["wg"] = ParamDef((L, d, f), ("stack", "embed_fsdp", "mlp"), pdt)
        layers["wu"] = ParamDef((L, d, f), ("stack", "embed_fsdp", "mlp"), pdt)
        layers["wd"] = ParamDef((L, f, d), ("stack", "mlp", "embed_fsdp"), pdt)
    out = {
        "layers": layers,
        "embed": ParamDef((v, d), ("vocab", None), pdt, init="embed"),
        "final_ln": ParamDef((d,), (None,), pdt, init="ones"),
    }
    if not cfg.tie_embeddings:
        # same scale as the tied path (embed.T, std 0.02): init logits stay
        # O(0.02*sqrt(d)) so init xent ~ log(vocab_size) either way.
        out["head"] = ParamDef((d, v), ("embed_fsdp", "vocab"), pdt,
                               init="normal")
    return out


def init(cfg: TransformerConfig, key: jax.Array, ctx: MeshCtx = NULL_CTX):
    return init_from_schema(schema(cfg, ctx), key)


def abstract_params(cfg: TransformerConfig, ctx: MeshCtx = NULL_CTX):
    return abstract_from_schema(schema(cfg, ctx))


# ---------------------------------------------------------------------------
# Layer
# ---------------------------------------------------------------------------
def _project_qkv(h_ln, lp, cfg, ctx, scheme, cdt, h, kh, dh):
    """QKV projections + per-scheme activation sharding constraints."""
    b, s, _ = h_ln.shape
    q = h_ln @ lp["wq"].astype(cdt)
    k = h_ln @ lp["wk"].astype(cdt)
    v = h_ln @ lp["wv"].astype(cdt)
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(cdt)
        k = k + lp["bk"].astype(cdt)
        v = v + lp["bv"].astype(cdt)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kh, dh)
    v = v.reshape(b, s, kh, dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    return q, k, v


def decoder_layer(x, lp, cfg: TransformerConfig, ctx: MeshCtx, scheme: str,
                  positions, *, emit_cache: bool = False):
    """One pre-norm block. x: [B, S, d] (seq-sharded between blocks)."""
    # Barrier: without it XLA hoists the rms_norm bf16->f32 convert of the
    # *saved residual stack* out of the backward while loop, materializing a
    # full-precision [L, B, S, d] copy (+6 GiB/dev on qwen3-235B).
    x = jax.lax.optimization_barrier(x)
    b, s, d = x.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    h, kh = effective_heads(cfg, ctx)
    dh = cfg.d_head

    # Megatron-SP boundary (hillclimb A5): norm in the sequence-sharded
    # region, then an EXPLICIT bf16 all-gather into the TP region. Leaving
    # this to GSPMD resolved the boundary as fp32 all-reduce + slice
    # (~16x the minimal traffic; A4 restructuring was refuted — the fix is
    # pinning the collectives via shard_map).
    h_ln = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if scheme == "tp":
        h_ln = sp_all_gather(h_ln, ctx)
    else:
        h_ln = ctx.constrain(h_ln, "batch", "seq_sp", None)
    q, k, v = _project_qkv(h_ln, lp, cfg, ctx, scheme, cdt, h, kh, dh)
    q = attn_lib.apply_rope(q, positions[None, :], cfg.rope_theta)
    k = attn_lib.apply_rope(k, positions[None, :], cfg.rope_theta)
    o = attn_lib.flash_attention(q, k, v, causal=True, kv_chunk=cfg.kv_chunk,
                                 ctx=ctx, scheme=scheme)
    o = o.reshape(b, s, h * dh)
    if scheme == "tp":
        # row-parallel wo with explicit bf16 psum_scatter into seq_sp
        o = row_parallel_out_proj(o, lp["wo"].astype(cdt), ctx, "qkv_out")
    else:
        o = o @ lp["wo"].astype(cdt)
        o = ctx.constrain(o, "batch", "seq_sp", None)
    x = x + o

    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        t = ctx.constrain(h2, "batch", "seq_sp", None).reshape(b * s, d)
        t = ctx.constrain(t, "tokens", None)
        y, aux = moe_lib.moe_block(
            t, lp["router"], lp["wg_e"], lp["wu_e"], lp["wd_e"], cfg, ctx)
        y = ctx.constrain(y, "tokens", None).reshape(b, s, d)
        y = ctx.constrain(y, "batch", "seq_sp", None)
    else:
        if scheme == "tp":
            h2 = sp_all_gather(h2, ctx)
        else:
            h2 = ctx.constrain(h2, "batch", None, None)
        g = h2 @ lp["wg"].astype(cdt)
        u = h2 @ lp["wu"].astype(cdt)
        g = ctx.constrain(g, "batch", None, "mlp")
        u = ctx.constrain(u, "batch", None, "mlp")
        hmid = jax.nn.silu(g.astype(jnp.float32)).astype(cdt) * u
        if scheme == "tp":
            y = row_parallel_out_proj(hmid, lp["wd"].astype(cdt), ctx, "mlp")
        else:
            y = hmid @ lp["wd"].astype(cdt)
            y = ctx.constrain(y, "batch", "seq_sp", None)
        aux = {}
    x = x + y
    if emit_cache:
        kc = ctx.constrain(k, "batch", "kv_seq", None, None)
        vc = ctx.constrain(v, "batch", "kv_seq", None, None)
        return x, aux, (kc.astype(cdt), vc.astype(cdt))
    return x, aux, None


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------
def _cast_layer_stack(layers: dict, cfg: TransformerConfig) -> dict:
    """One-time bf16 cast of the stacked layer weights before the scan: the
    per-layer FSDP all-gathers then move bf16 instead of fp32 (halves the
    dominant collective traffic + gather transients). The router stays fp32
    for routing stability; fp32 masters are untouched (grads flow back
    through the cast)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if cdt == jnp.float32:
        return layers
    keep = {"router"}
    return {k: (v if (k in keep or v.dtype != jnp.float32) else v.astype(cdt))
            for k, v in layers.items()}


def _aux_zero():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32),
            "frac_dropped": jnp.zeros((), jnp.float32)}


def forward_hidden(params, tokens, cfg: TransformerConfig, ctx: MeshCtx,
                   *, emit_cache: bool = False):
    """tokens [B, S] -> hidden [B, S, d] (+ per-layer aux means, + cache)."""
    b, s = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    x = sharded_embedding_lookup(params["embed"], tokens, ctx,
                                 row_logical="vocab",
                                 ids_logical=("batch", None),
                                 compute_dtype=cdt,
                                 scatter_dim_logical="seq_sp")
    x = ctx.constrain(x, "batch", "seq_sp", None)
    positions = jnp.arange(s)
    scheme = resolve_scheme(cfg, ctx)
    layers = _cast_layer_stack(params["layers"], cfg)

    def body(xc, lp):
        y, aux, cache = decoder_layer(xc, lp, cfg, ctx, scheme, positions,
                                      emit_cache=emit_cache)
        if not aux:
            aux = _aux_zero()
        return y, (aux, cache)

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.scan_layers:
        x, (aux_l, cache) = jax.lax.scan(body, x, layers)
        aux = {k_: v.mean() for k_, v in aux_l.items()}
    else:
        auxes, caches_k, caches_v = [], [], []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], layers)
            x, (aux_i, cache_i) = body(x, lp)
            auxes.append(aux_i)
            if emit_cache:
                caches_k.append(cache_i[0])
                caches_v.append(cache_i[1])
        aux = {k_: jnp.mean(jnp.stack([a[k_] for a in auxes]))
               for k_ in auxes[0]}
        cache = (jnp.stack(caches_k), jnp.stack(caches_v)) if emit_cache else None

    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return x, aux, cache


def _head_matrix(params, cfg, cdt):
    if cfg.tie_embeddings:
        return params["embed"].astype(cdt).T  # [d, Vp]
    return params["head"].astype(cdt)


def loss_fn(params, batch, cfg: TransformerConfig, ctx: MeshCtx):
    """Token-chunked causal-LM cross entropy (+ MoE aux losses)."""
    tokens, targets = batch["tokens"], batch["targets"]
    b, s = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    hidden, aux, _ = forward_hidden(params, tokens, cfg, ctx)
    hidden = ctx.constrain(hidden, "batch", None, None)
    w = _head_matrix(params, cfg, cdt)  # [d, Vp]
    vp = w.shape[1]
    vr = cfg.vocab_size

    c = cfg.xent_chunk or min(s, 512)
    nc = s // c
    assert nc * c == s, (s, c)
    hs = jnp.moveaxis(hidden.reshape(b, nc, c, -1), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, nc, c), 1, 0)

    def body(tot, inp):
        h_c, t_c = inp
        logits = (h_c @ w).astype(jnp.float32)  # [B, C, Vp]
        logits = ctx.constrain(logits, "batch", None, "vocab")
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        logits = jnp.where(col < vr, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.sum(jnp.where(col == t_c[..., None], logits, 0.0), axis=-1)
        return tot + jnp.sum(lse - gold), None

    # remat: recompute each chunk's logits in backward instead of saving
    # [B, C, V/16] blocks per chunk
    total, _ = jax.lax.scan(
        jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable),
        jnp.zeros((), jnp.float32), (hs, ts))
    xent = total / (b * s)
    loss = xent
    if cfg.family == "moe":
        loss = (loss + cfg.router_aux_weight * aux["load_balance"]
                + cfg.router_z_weight * aux["router_z"])
    metrics = {"xent": xent, **aux}
    return loss, metrics


def make_train_step(cfg: TransformerConfig, ctx: MeshCtx, opt):
    ga = max(cfg.grad_accum, 1)

    if ga == 1:
        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, cfg, ctx)
            params, opt_state, om = opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss, **metrics, **om}

        return train_step

    def train_step(params, opt_state, batch):
        """Gradient accumulation over ga microbatches (hillclimb A2):
        activation stacks shrink by ga; grads accumulate in bf16."""
        micro = jax.tree.map(
            lambda x: x.reshape((ga, x.shape[0] // ga) + x.shape[1:]), batch)

        def body(carry, mb):
            gsum, lsum = carry
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb, cfg, ctx)
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), gsum, g)
            return (gsum, lsum + loss), metrics

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
        (gsum, lsum), metrics_l = jax.lax.scan(
            body, (g0, jnp.zeros((), jnp.float32)), micro)
        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) / ga), gsum)
        params, opt_state, om = opt.update(grads, opt_state, params)
        metrics = {k: v.mean() for k, v in metrics_l.items()}
        return params, opt_state, {"loss": lsum / ga, **metrics, **om}

    return train_step


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
class DecodeState(NamedTuple):
    k: jax.Array  # [L, B, Smax, kh, dh]
    v: jax.Array
    length: jax.Array  # scalar int32


def prefill(params, tokens, cfg: TransformerConfig, ctx: MeshCtx):
    """Returns (last-token logits, pooled embedding, DecodeState)."""
    hidden, _, cache = forward_hidden(params, tokens, cfg, ctx,
                                      emit_cache=True)
    cdt = jnp.dtype(cfg.compute_dtype)
    last = hidden[:, -1, :]
    logits = (last @ _head_matrix(params, cfg, cdt)).astype(jnp.float32)
    pooled = hidden.mean(axis=1).astype(jnp.float32)
    embed = pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6)
    ks, vs = cache
    state = DecodeState(k=ks, v=vs,
                        length=jnp.asarray(tokens.shape[1], jnp.int32))
    return logits, embed, state


def decode_layer(x, lp, k_cache, v_cache, cur_len, cfg, ctx, seq_logical):
    """Single-token decode block. x: [B, d]."""
    b, d = x.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    h, kh = effective_heads(cfg, ctx)
    dh = cfg.d_head

    h_ln = rms_norm(x, lp["ln1"], cfg.norm_eps)[:, None, :]  # [B, 1, d]
    q, k, v = _project_qkv(h_ln, lp, cfg, ctx, "decode", cdt, h, kh, dh)
    pos = jnp.full((1, 1), cur_len, jnp.int32)
    q = attn_lib.apply_rope(q, pos, cfg.rope_theta)
    k = attn_lib.apply_rope(k, pos, cfg.rope_theta)
    q, k_new, v_new = q[:, 0], k[:, 0].astype(cdt), v[:, 0].astype(cdt)

    o, k2, v2 = attn_lib.decode_attention(
        q, k_cache, v_cache, k_new, v_new, cur_len, ctx, seq_logical)
    o = o.reshape(b, h * dh) @ lp["wo"].astype(cdt)
    x = x + ctx.constrain(o, "batch", None)

    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        t = ctx.constrain(h2, "tokens", None)
        t_shards = max(ctx.shards_for(b, "tokens"), 1)
        y, aux = moe_lib.moe_block(
            t, lp["router"], lp["wg_e"], lp["wu_e"], lp["wd_e"], cfg, ctx,
            capacity_override=max(b // t_shards, 1))  # drop-free at decode
    else:
        g = h2 @ lp["wg"].astype(cdt)
        u = h2 @ lp["wu"].astype(cdt)
        g = ctx.constrain(g, "batch", "mlp")
        u = ctx.constrain(u, "batch", "mlp")
        y = (jax.nn.silu(g.astype(jnp.float32)).astype(cdt) * u) @ lp["wd"].astype(cdt)
        aux = {}
    x = x + ctx.constrain(y, "batch", None)
    return x, (k2, v2)


def decode_step(params, state: DecodeState, tokens, cfg: TransformerConfig,
                ctx: MeshCtx, seq_logical: str = "kv_seq"):
    """One decode step: tokens [B] -> (logits [B, Vp], embed, new state)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = sharded_embedding_lookup(params["embed"], tokens, ctx,
                                 row_logical="vocab", ids_logical=("batch",),
                                 compute_dtype=cdt)
    x = ctx.constrain(x, "batch", None)
    cur_len = state.length
    layers = _cast_layer_stack(params["layers"], cfg)

    def body(xc, inp):
        lp, kc, vc = inp
        y, (k2, v2) = decode_layer(xc, lp, kc, vc, cur_len, cfg, ctx,
                                   seq_logical)
        return y, (k2, v2)

    if cfg.scan_layers:
        x, (ks, vs) = jax.lax.scan(body, x, (layers, state.k, state.v))
    else:
        ks_l, vs_l = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], layers)
            x, (k2, v2) = body(x, (lp, state.k[i], state.v[i]))
            ks_l.append(k2)
            vs_l.append(v2)
        ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)

    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = (x @ _head_matrix(params, cfg, cdt)).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    embed = xf / jnp.maximum(jnp.linalg.norm(xf, axis=-1, keepdims=True), 1e-6)
    return logits, embed, DecodeState(k=ks, v=vs, length=cur_len + 1)


def abstract_decode_state(cfg: TransformerConfig, batch: int, max_len: int,
                          ctx: MeshCtx = NULL_CTX) -> DecodeState:
    _, kh = effective_heads(cfg, ctx)
    cdt = jnp.dtype(cfg.compute_dtype)
    shape = (cfg.n_layers, batch, max_len, kh, cfg.d_head)
    return DecodeState(
        k=jax.ShapeDtypeStruct(shape, cdt),
        v=jax.ShapeDtypeStruct(shape, cdt),
        length=jax.ShapeDtypeStruct((), jnp.int32),
    )
