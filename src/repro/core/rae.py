"""RAE — Regularized Auto-Encoder (the paper's core contribution, Section 3.2).

A *linear* autoencoder:  x_hat = W_d @ W_e @ x  with W_e in R^{m x n},
W_d in R^{n x m}, trained on

    L = ||W_d W_e x - x||_2^2 + lambda * (||W_e||_F^2 + ||W_d||_F^2)   (Eq. 7)

The paper realises lambda as AdamW decoupled weight decay (Section 4.1);
``explicit_frobenius=True`` instead adds the Frobenius term to the loss
(mathematically the plain-SGD-equivalent form of Eq. 7). The trained encoder
is the dimensionality-reduction map f(x) = W_e x.

Parameters live in a plain dict so they compose with the framework's schema /
sharding / checkpoint machinery.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import RAEConfig
from ..distributed.partitioning import ParamDef
from ..kernels.common import EXACT

Params = dict[str, jax.Array]


def schema(cfg: RAEConfig) -> dict[str, ParamDef]:
    n, m = cfg.in_dim, cfg.out_dim
    dt = jnp.dtype(cfg.param_dtype)
    s: dict[str, ParamDef] = {
        # encoder rows are the learned (possibly non-orthogonal) basis; fan_in
        # init ~ N(0, 1/n) keeps ||W_e x|| ~ ||x|| at init (sigma ~ 1).
        "w_e": ParamDef((n, m), ("embed_fsdp", None), dt, init="fan_in"),
        "w_d": ParamDef((m, n), (None, "embed_fsdp"), dt, init="fan_in"),
    }
    if cfg.use_bias:
        s["b_e"] = ParamDef((m,), (None,), dt, init="zeros")
        s["b_d"] = ParamDef((n,), (None,), dt, init="zeros")
    return s


def init(cfg: RAEConfig, key: jax.Array) -> Params:
    from ..distributed.partitioning import init_from_schema

    return init_from_schema(schema(cfg), key)


def encode(params: Params, x: jax.Array) -> jax.Array:
    """f(x) = x @ W_e (+ b_e). x: [..., n] -> [..., m]."""
    y = jnp.matmul(x, params["w_e"], precision=EXACT)
    if "b_e" in params:
        y = y + params["b_e"]
    return y


def decode(params: Params, z: jax.Array) -> jax.Array:
    y = jnp.matmul(z, params["w_d"], precision=EXACT)
    if "b_d" in params:
        y = y + params["b_d"]
    return y


def reconstruct(params: Params, x: jax.Array) -> jax.Array:
    return decode(params, encode(params, x))


def loss_fn(params: Params, x: jax.Array, cfg: RAEConfig
            ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mean-over-batch squared reconstruction error (+ optional Frobenius term)."""
    x = x.astype(jnp.float32)
    x_hat = reconstruct(params, x).astype(jnp.float32)
    recon = jnp.mean(jnp.sum(jnp.square(x_hat - x), axis=-1))
    loss = recon
    frob = frobenius_sq(params)
    if cfg.explicit_frobenius:
        loss = loss + cfg.weight_decay * frob
    return loss, {"recon": recon, "frobenius_sq": frob}


def frobenius_sq(params: Params) -> jax.Array:
    """||W_e||_F^2 + ||W_d||_F^2 (biases excluded, matching Eq. 7)."""
    tot = jnp.zeros((), jnp.float32)
    for k in ("w_e", "w_d"):
        if k in params:
            tot = tot + jnp.sum(jnp.square(params[k].astype(jnp.float32)))
    return tot


def encoder_matrix(params: Params) -> jax.Array:
    """W_e as the paper writes it: [m, n] (maps R^n -> R^m)."""
    return params["w_e"].T
