"""RAE encoder Pallas kernel: tiled GEMM + fused L2-normalize epilogue.

Encoding a billion-row corpus through W_e [n, m] is a skinny GEMM whose
output is immediately re-read for normalization (cosine retrieval). Fusing
the row-norm into the GEMM epilogue removes one full HBM round trip of the
reduced corpus — at m=128..512 the op is output-bandwidth-bound, so this is
a ~2x bytes saving on the encode pass.

Grid (rows/br, n/bk): the contraction axis is innermost; the [br, m]
accumulator lives in VMEM scratch; the epilogue normalizes on the last step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import EXACT


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, normalize: bool):
    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], precision=EXACT,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        z = acc_ref[...]
        if normalize:
            norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
            z = z / jnp.maximum(norm, 1e-12)
        o_ref[...] = z.astype(o_ref.dtype)


def rae_encode_pallas(x: jax.Array, w_e: jax.Array, *, normalize: bool = True,
                      br: int = 256, bk: int = 512,
                      interpret: bool = False) -> jax.Array:
    rows, n = x.shape
    _, m = w_e.shape
    assert rows % br == 0 and n % bk == 0, (rows, n, br, bk)
    kernel = functools.partial(_kernel, normalize=normalize)
    return pl.pallas_call(
        kernel,
        grid=(rows // br, n // bk),
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bk, m), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, m), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, m), jnp.float32)],
        interpret=interpret,
    )(x, w_e)
