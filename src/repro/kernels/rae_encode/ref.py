"""Pure-jnp oracle: RAE encode (x @ W_e) with fused L2-normalize epilogue."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import EXACT


def rae_encode_ref(x: jax.Array, w_e: jax.Array,
                   normalize: bool = True) -> jax.Array:
    z = jnp.matmul(x.astype(jnp.float32), w_e.astype(jnp.float32),
                   precision=EXACT)
    if normalize:
        z = z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-12)
    return z
