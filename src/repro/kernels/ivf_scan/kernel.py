"""IVF probe scan Pallas TPU kernel: gather-and-score of the probed cells.

The IVF store is padded dense and feature-major, ``list_vecs [C, D, L]``:
cell ``c``'s member ``j`` is the column ``list_vecs[c, :, j]``, and each
cell's real members are its first ``extent[c]`` columns. ``D`` is the
dimension rounded up to whole sublanes and ``L`` the cell capacity rounded
up to whole lanes, so the TPU's default layout of the store is row-major:
a cell's columns are contiguous, and the kernel reads the store in place
(a store whose default layout is not row-major would be copied whole by
XLA before every call). Gathering ``list_vecs[cells]`` in XLA materialises
the whole padded ``[Q, P, D, L]`` gather in HBM and reads it back for the
dot and the norms. This kernel streams the probed cells' member blocks
straight from the store into VMEM and writes only scores:

* *scalar-prefetch gather* (as ``graph_beam``): the probed cell ids and
  the per-cell extents are prefetched into SMEM and drive the store's
  BlockSpec index map, so grid step ``(q, p, r)`` DMAs column block ``r``
  of cell ``cells[q, p]``;
* *padding is never read*: the index map clamps ``r`` to the cell's last
  real block. Consecutive steps then name the same block, and the
  pipeline issues no DMA for it; the body writes ``-inf`` for every column
  at or past the extent;
* the score ``2 q.x - ||x||^2 - ||q||^2`` is ``x * (2q - x)`` summed over
  the feature (sublane) axis in f32 on the VPU, which leaves the block's
  scores lane-major, ready to store.

Tiling: a block is ``(D, B)``, ``B`` a multiple of 128 sized by
:func:`block_cols`. The queries ride as the whole ``[D, Q]`` array (the
column picked in VMEM: a one-query block fails Mosaic's tiling check). The
scores are laid out ``[Q * P, 1, nb * B]`` so each grid step writes its
own ``(1, B)`` block (the unit dim spans the array): VMEM holds one store
block and one score block, whatever ``P``. The grid iterates
sequentially, ``r`` innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
#: VMEM bytes of one store block: large enough that a grid step's fixed
#: cost is small next to its DMA, small enough that the double-buffered
#: block and its f32 temporaries stay well inside the default scoped VMEM
_BLOCK_BYTES = 1 << 20


def store_shape(n_cells: int, cap: int, d: int) -> tuple[int, int, int]:
    """``[C, D, L]`` of the store of ``n_cells`` cells of ``cap`` rows of
    ``d`` features: whole sublanes of features, whole lanes of members."""
    return (n_cells, -(-d // _SUBLANES) * _SUBLANES,
            -(-cap // _LANES) * _LANES)


def block_cols(width: int, depth: int) -> int:
    """Members (lanes) of one store block of a ``[C, depth, width]`` store:
    ``_BLOCK_BYTES`` of f32 columns in whole lanes, or the whole ``width``
    where it fits one block."""
    cols = max(_LANES, _BLOCK_BYTES // (depth * 4) // _LANES * _LANES)
    return min(cols, width)


def _kernel(cells_ref, ext_ref, q_ref, x_ref, o_ref, *, n_probe: int,
            block: int):
    qi = pl.program_id(0)
    p = pl.program_id(1)
    start = pl.multiple_of(pl.program_id(2) * block, _LANES)
    ext = ext_ref[cells_ref[qi * n_probe + p]]

    @pl.when(start < ext)
    def _():
        qs = q_ref[...].astype(jnp.float32)                          # [D, Q]
        col = jax.lax.broadcasted_iota(jnp.int32, qs.shape, 1)
        q = jnp.sum(jnp.where(col == qi, qs, 0.0), axis=1,
                    keepdims=True)                                   # [D, 1]
        x = x_ref[...].astype(jnp.float32)                           # [D, B]
        # column sums of x (2q - x) = 2 q.x - ||x||^2, lane-major [1, B]
        s = (jnp.sum(x * (2.0 * q - x), axis=0, keepdims=True)
             - jnp.sum(q * q, axis=0, keepdims=True))
        member = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        o_ref[...] = jnp.where(member < ext, s, -jnp.inf)

    @pl.when(start >= ext)
    def _():
        o_ref[...] = jnp.full((1, block), -jnp.inf, jnp.float32)


def ivf_scan_pallas(queries_t: jax.Array, cells: jax.Array,
                    extent: jax.Array, list_vecs: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """queries_t [D, Q] (the queries as columns, zero-padded to ``D``);
    cells [Q, P] int32 probed cell ids; extent [C] int32 real members per
    cell (its prefix length); list_vecs [C, D, L]. Returns scores
    [Q * P, 1, nb * B] f32 (row ``q * P + p`` is query ``q``'s ``p``-th
    probed cell), ``-inf`` at and past each cell's extent."""
    depth, qn = queries_t.shape
    n_probe = cells.shape[1]
    width = list_vecs.shape[2]
    block = block_cols(width, depth)
    nb = pl.cdiv(width, block)
    kernel = functools.partial(_kernel, n_probe=n_probe, block=block)

    def store_block(q, p, r, cells, ext):
        cell = cells[q * n_probe + p]
        last = jnp.maximum(pl.cdiv(ext[cell], block) - 1, 0)
        return cell, 0, jnp.minimum(r, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # probed cells and extents drive the DMAs
        grid=(qn, n_probe, nb),
        in_specs=[
            pl.BlockSpec((depth, qn), lambda q, p, r, cells, ext: (0, 0)),
            pl.BlockSpec((None, depth, block), store_block),
        ],
        out_specs=pl.BlockSpec(
            (None, 1, block),
            lambda q, p, r, cells, ext: (q * n_probe + p, 0, r)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((qn * n_probe, 1, nb * block),
                                       jnp.float32),
        interpret=interpret,
    )(cells.reshape(-1).astype(jnp.int32), extent.astype(jnp.int32),
      queries_t, list_vecs)
