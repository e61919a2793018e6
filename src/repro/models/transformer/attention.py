"""Attention: RoPE, flash-style blockwise attention, seq-sharded decode.

Three schemes (DESIGN.md §5):

* head-TP (``tp``)       — heads sharded over ``model`` (train/prefill when
                           divisible); KV heads broadcast-repeated to the
                           full head count so every tensor in the attention
                           core stays 4D with a clean (batch, _, heads, _)
                           sharding. (A 5D (g, kh) grouping is NOT
                           PartitionSpec-expressible when one mesh axis must
                           split across both dims — GSPMD then falls back to
                           "involuntary full rematerialization" reshards,
                           observed as +GBs of collectives in the dry-run.)
* context-parallel (``cp``) — q-sequence sharded over ``model``, K/V
                           gathered (phi3 40H / qwen2 28H: 16 ∤ H).
* decode               — KV cache *sequence*-sharded across chips; grouped
                           (kh-major) per-shard partial softmax stats
                           (m, l, o) merged with pmax/psum inside shard_map.
                           Works for any head count and keeps a 500k-token
                           cache at ~GB/chip.

Flat head index convention (weights are initialized, never imported, so we
define it): h = k_idx * g + g_idx (kh-major) — jnp.repeat(kv, g, axis=2)
produces exactly this order, and the decode path's (kh, g) reshape matches.
The train/prefill kernel is an online-softmax scan over KV chunks — the
scanned dim is always unsharded under either scheme. Softmax stats are fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import MeshCtx, NULL_CTX

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    half = d_head // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, d_head]; positions: broadcastable to [..., S]."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Train / prefill attention: online-softmax scan over KV chunks
# ---------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,  # [B, S, H, dh]
    k: jax.Array,  # [B, T, K, dh]
    v: jax.Array,  # [B, T, K, dh]
    *,
    causal: bool = True,
    q_offset: int | jax.Array = 0,
    kv_chunk: int = 256,
    ctx: MeshCtx = NULL_CTX,
    scheme: str = "tp",
) -> jax.Array:
    b, s, h, dh = q.shape
    _, t, kh, _ = k.shape
    g = h // kh
    assert g * kh == h, (h, kh)
    scale = dh ** -0.5

    if scheme == "tp":
        sp3 = ("batch", None, "heads")          # [B, S, H]
        sp4 = ("batch", None, "heads", None)    # [B, S, H, dh]
        spk = (None, "batch", None, "heads", None)  # chunked KV
    else:  # context parallel: q-seq sharded, kv replicated
        sp3 = ("batch", "seq_sp", None)
        sp4 = ("batch", "seq_sp", None, None)
        spk = (None, "batch", None, None, None)

    if g > 1:  # broadcast KV heads to kh-major full head count
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qs = ctx.constrain((q * scale), *sp4)

    ck = min(kv_chunk, t)
    t_real = t
    if t % ck:  # pad KV to a chunk multiple; padding masked below
        pad = ck - t % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        t = t + pad
    nkv = t // ck
    # [nkv, B, ck, H, dh] so scan slices chunks along dim 0
    ks = ctx.constrain(jnp.moveaxis(k.reshape(b, nkv, ck, h, dh), 1, 0), *spk)
    vs = ctx.constrain(jnp.moveaxis(v.reshape(b, nkv, ck, h, dh), 1, 0), *spk)

    q_pos = q_offset + jnp.arange(s)

    def body(carry, inputs):
        m, l, o = carry
        i, kc, vc = inputs
        sblk = jnp.einsum("bshd,bchd->bshc", qs, kc,
                          preferred_element_type=jnp.float32)
        sblk = ctx.constrain(sblk, *sp3, None)
        kv_pos = i * ck + jnp.arange(ck)
        if causal:
            mask = (q_pos[:, None] >= kv_pos[None, :])  # [S, ck]
            if t_real < t:
                mask = mask & (kv_pos[None, :] < t_real)
            sblk = jnp.where(mask[None, :, None, :], sblk, NEG_INF)
        elif t_real < t:
            mask = jnp.broadcast_to(kv_pos[None, :] < t_real, (s, ck))
            sblk = jnp.where(mask[None, :, None, :], sblk, NEG_INF)
        m_new = ctx.constrain(jnp.maximum(m, sblk.max(-1)), *sp3)
        p = jnp.exp(sblk - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = ctx.constrain(l * alpha + p.sum(-1), *sp3)
        o = o * alpha[..., None] + jnp.einsum(
            "bshc,bchd->bshd", p, vc, preferred_element_type=jnp.float32)
        o = ctx.constrain(o, *sp4)
        return (m_new, l, o), None

    m0 = ctx.constrain(jnp.full((b, s, h), NEG_INF, jnp.float32), *sp3)
    l0 = ctx.constrain(jnp.zeros((b, s, h), jnp.float32), *sp3)
    o0 = ctx.constrain(jnp.zeros((b, s, h, dh), jnp.float32), *sp4)
    # remat the chunk body: without it the scan's backward saves every
    # chunk's [S, ck] score block — O(S*T) memory, defeating flash entirely
    # (observed +4 GiB/dev on llama train_4k).
    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable),
        (m0, l0, o0), (jnp.arange(nkv), ks, vs))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return ctx.constrain(out.astype(q.dtype), *sp4)


# ---------------------------------------------------------------------------
# Decode attention: sequence-sharded KV cache, partial-softmax merge
# (grouped kh-major: q [B, kh, g, dh] so the unrepeated cache is reused)
# ---------------------------------------------------------------------------
def _decode_local(q, k_loc, v_loc, k_new, v_new, cur_len, pos_base, s_loc):
    """Per-shard decode attention. Returns (o, l, m) partial stats and the
    locally-updated cache slabs. ``pos_base`` is this shard's first global
    cache position. q: [B, kh, g, dh]."""
    b, kh, g, dh = q.shape
    scale = dh ** -0.5
    qs = q.astype(jnp.float32) * scale

    # -- masked local scores over the cache slab
    gpos = pos_base + jnp.arange(s_loc)  # [s_loc] global positions
    mask = gpos[None, :] < cur_len  # [1, s_loc]
    s_blk = jnp.einsum("bkgd,bskd->bkgs", qs, k_loc.astype(jnp.float32))
    s_blk = jnp.where(mask[:, None, None, :], s_blk, NEG_INF)
    m = jnp.maximum(s_blk.max(-1), NEG_INF)  # [b, kh, g]
    p = jnp.exp(s_blk - m[..., None]) * mask[:, None, None, :]
    l = p.sum(-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_loc.astype(jnp.float32))

    # -- write the new token's KV into the owning shard's slab
    wpos = cur_len - pos_base  # local write index (may be out of range)
    owner = (wpos >= 0) & (wpos < s_loc)
    wclip = jnp.clip(wpos, 0, s_loc - 1)
    cur_k = jax.lax.dynamic_slice_in_dim(k_loc, wclip, 1, axis=1)
    cur_v = jax.lax.dynamic_slice_in_dim(v_loc, wclip, 1, axis=1)
    sel_k = jnp.where(owner, k_new[:, None].astype(k_loc.dtype), cur_k)
    sel_v = jnp.where(owner, v_new[:, None].astype(v_loc.dtype), cur_v)
    k_loc = jax.lax.dynamic_update_slice_in_dim(k_loc, sel_k, wclip, axis=1)
    v_loc = jax.lax.dynamic_update_slice_in_dim(v_loc, sel_v, wclip, axis=1)
    return (o, l, m), (k_loc, v_loc)


def _merge_with_new_token(o, l, m, q, k_new, v_new):
    """Fold the new token's self-attention into merged (o, l, m)."""
    b, kh, g, dh = q.shape
    scale = dh ** -0.5
    qs = q.astype(jnp.float32) * scale
    s_self = jnp.einsum("bkgd,bkd->bkg", qs, k_new.astype(jnp.float32))
    m2 = jnp.maximum(m, s_self)
    alpha = jnp.exp(m - m2)
    beta = jnp.exp(s_self - m2)
    l2 = l * alpha + beta
    # v_new [b, kh, dh] broadcasts over the g dim of o [b, kh, g, dh]
    o2 = o * alpha[..., None] + beta[..., None] * v_new[:, :, None].astype(jnp.float32)
    return o2 / jnp.maximum(l2[..., None], 1e-30)


def decode_attention(
    q: jax.Array,        # [B, H, dh] current-token queries (kh-major heads)
    k_cache: jax.Array,  # [B, Smax, K, dh]
    v_cache: jax.Array,
    k_new: jax.Array,    # [B, K, dh]
    v_new: jax.Array,
    cur_len: jax.Array,  # scalar int32: number of tokens already cached
    ctx: MeshCtx,
    seq_logical: str = "kv_seq",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out [B, H, dh], new_k_cache, new_v_cache)."""
    b, h, dh = q.shape
    _, smax, kh, _ = k_cache.shape
    g = h // kh
    qg = q.reshape(b, kh, g, dh)  # kh-major, matching the repeat() layout

    n_shards = ctx.axis_size(seq_logical) if ctx.mesh is not None else 1
    if ctx.mesh is None or n_shards == 1:
        (o, l, m), (k2, v2) = _decode_local(
            qg, k_cache, v_cache, k_new, v_new, cur_len, 0, smax)
        out = _merge_with_new_token(o, l, m, qg, k_new, v_new)
        return out.reshape(b, h, dh).astype(q.dtype), k2, v2

    mesh = ctx.mesh
    rule = ctx.rules[seq_logical]
    seq_axes = (rule,) if isinstance(rule, str) else tuple(rule)
    seq_axes = tuple(a for a in seq_axes if a in mesh.shape)
    s_loc = smax
    for a in seq_axes:
        s_loc //= mesh.shape[a]

    c_spec = ctx.pspec(k_cache.shape, "batch", seq_logical, None, None)
    n_spec = ctx.pspec(k_new.shape, "batch", None, None)

    def fn(qg_l, kc, vc, kn, vn, clen):
        shard = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        pos_base = shard * s_loc
        (o, l, m), (k2, v2) = _decode_local(
            qg_l, kc, vc, kn, vn, clen, pos_base, s_loc)
        # merge partial stats across the sequence shards
        m_g = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axes)
        o_g = jax.lax.psum(o * corr[..., None], seq_axes)
        out = _merge_with_new_token(o_g, l_g, m_g, qg_l, kn, vn)
        return out, k2, v2

    qg_spec = ctx.pspec(qg.shape, "batch", None, None, None)
    fn_sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(qg_spec, c_spec, c_spec, n_spec, n_spec, ctx.pspec(())),
        out_specs=(qg_spec, c_spec, c_spec), check_vma=False)
    out, k2, v2 = fn_sm(qg, k_cache, v_cache, k_new, v_new, cur_len)
    return out.reshape(b, h, dh).astype(q.dtype), k2, v2


# ---------------------------------------------------------------------------
# Head-count padding solver (beyond-paper hillclimb: switch cp -> tp)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def padded_head_layout(n_heads: int, n_kv: int, tp: int) -> tuple[int, int]:
    """Smallest (H', K') with K' >= n_kv, H'/K' >= n_heads/n_kv integral,
    and H' % tp == 0 — makes head-TP legal for awkward head counts."""
    g = n_heads // n_kv
    best: Optional[tuple[int, int]] = None
    for kp in range(n_kv, 4 * n_kv + 1):
        for gp in range(g, 4 * g + 1):
            hp = kp * gp
            if hp >= n_heads and hp % tp == 0:
                if best is None or hp < best[0]:
                    best = (hp, kp)
    assert best is not None
    return best
