"""Quantized storage codecs for the search tiers: SQ8 + PQ (with ADC).

Two codecs, both trading bytes-per-vector for a small, bounded recall hit —
the memory-axis complement of the paper's dimensionality reduction (RAE
shrinks d, quantization shrinks bytes/dim; Zouhar et al. 2022 show the two
compressions stack almost independently):

* **SQ8** — per-dim min/max scalar quantization to uint8. Reconstruction
  ``x_hat = vmin + code * step`` is never materialized on the scan path:
  ``||q - x_hat||^2 = ||q||^2 - 2 q.vmin - 2 (q*step).codes + ||x_hat||^2``
  needs only a dot of the *pre-scaled* query against the raw uint8 codes
  plus the per-row ``||x_hat||^2`` term precomputed at encode time
  (dequant-free asymmetric L2). 4x smaller than f32, error <= step/2 per
  dim.

* **PQ{m}x{bits}** — product quantization: split d into m subspaces, run
  k-means (2^bits centroids) per subspace, store one code per subspace.
  Search uses ADC (asymmetric distance computation): a per-query LUT of
  exact query-to-centroid distances, summed via code gather. m bytes per
  vector at bits=8 — 32x smaller than f32 at d=8m.

IVF composition: the coarse layer is unchanged (``search.ivf`` k-means
cells); the padded-dense list payload stores *codes* instead of f32
vectors, and the probe scan runs the same dequant-free forms over the
gathered codes. The flat PQ hot path has a fused Pallas kernel
(``repro.kernels.pq_adc``); everything here is the pure-JAX engine.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import EXACT
from .ivf import kmeans


# ---------------------------------------------------------------------------
# SQ8: per-dim min/max scalar quantization
# ---------------------------------------------------------------------------
@dataclass
class ScalarQuantizer:
    """Per-dim affine codebook: ``decode(c) = vmin + c * step``, c in 0..255."""

    vmin: jax.Array   # [d]
    step: jax.Array   # [d], >= tiny eps so constant dims round-trip


def sq8_train(x: jax.Array) -> ScalarQuantizer:
    """Fit per-dim [min, max] on the corpus; 256 uniform levels per dim."""
    x = jnp.asarray(x, jnp.float32)
    vmin = jnp.min(x, axis=0)
    vmax = jnp.max(x, axis=0)
    step = jnp.maximum((vmax - vmin) / 255.0, 1e-12)
    return ScalarQuantizer(vmin=vmin, step=step)


def sq8_encode(sq: ScalarQuantizer, x: jax.Array) -> jax.Array:
    """f32 [N, d] -> uint8 codes [N, d]; round-to-nearest, clipped to range."""
    x = jnp.asarray(x, jnp.float32)
    c = jnp.round((x - sq.vmin[None, :]) / sq.step[None, :])
    return jnp.clip(c, 0, 255).astype(jnp.uint8)


def sq8_decode(sq: ScalarQuantizer, codes: jax.Array) -> jax.Array:
    return sq.vmin[None, :] + codes.astype(jnp.float32) * sq.step[None, :]


def sq8_recon_sq_norms(sq: ScalarQuantizer, codes: jax.Array) -> jax.Array:
    """``||decode(codes)||^2`` per row — the scan-time constant term."""
    return jnp.sum(jnp.square(sq8_decode(sq, codes)), axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def sq8_scan(vmin: jax.Array, step: jax.Array, q: jax.Array,
             codes: jax.Array, recon_sq: jax.Array, k: int
             ) -> tuple[jax.Array, jax.Array]:
    """Dequant-free exact asymmetric top-k over SQ8 codes.

    Returns (scores [Q, k], indices [Q, k]); scores = -||q - decode(c)||^2
    (higher = closer, same convention as the flat scan).
    """
    q = jnp.asarray(q, jnp.float32)
    cf = codes.astype(jnp.float32)                     # [N, d]
    # q . x_hat = q . vmin + (q * step) . codes
    qdotmin = jnp.matmul(q, vmin, precision=EXACT)     # [Q]
    qdotc = jnp.matmul(q * step[None, :], cf.T,
                       precision=EXACT)                # [Q, N]
    s = (2.0 * (qdotmin[:, None] + qdotc)
         - recon_sq[None, :]
         - jnp.sum(q * q, axis=-1, keepdims=True))
    return jax.lax.top_k(s, k)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_sq8_search(centroids: jax.Array, lists: jax.Array, codes: jax.Array,
                   recon_sq: jax.Array, mask: jax.Array, vmin: jax.Array,
                   step: jax.Array, q: jax.Array, k: int, nprobe: int
                   ) -> tuple[jax.Array, jax.Array]:
    """IVF probe scan over SQ8 list payloads (padded-dense layout).

    ``codes`` [C, cap, d] uint8, ``recon_sq`` [C, cap], ``lists``/``mask``
    as in :class:`repro.search.ivf.IVFIndex`. Same -1/-inf pad semantics.
    """
    q = jnp.asarray(q, jnp.float32)
    d2c = (jnp.sum(q * q, 1)[:, None]
           - 2 * jnp.matmul(q, centroids.T, precision=EXACT)
           + jnp.sum(centroids * centroids, 1)[None, :])
    _, cells = jax.lax.top_k(-d2c, nprobe)             # [Q, P]
    cf = codes[cells].astype(jnp.float32)              # [Q, P, cap, d]
    ids = lists[cells]                                 # [Q, P, cap]
    m = mask[cells]
    r2 = recon_sq[cells]                               # [Q, P, cap]
    qdotmin = jnp.matmul(q, vmin, precision=EXACT)     # [Q]
    qdotc = jnp.einsum("qd,qpcd->qpc", q * step[None, :], cf,
                       precision=EXACT)
    s = (2.0 * (qdotmin[:, None, None] + qdotc)
         - r2 - jnp.sum(q * q, -1)[:, None, None])
    s = jnp.where(m, s, -jnp.inf)
    qn, p, cap = s.shape
    v, flat = jax.lax.top_k(s.reshape(qn, p * cap), k)
    idx = jnp.take_along_axis(ids.reshape(qn, p * cap), flat, axis=1)
    return v, jnp.where(jnp.isfinite(v), idx, -1)


# ---------------------------------------------------------------------------
# PQ: product quantization with ADC
# ---------------------------------------------------------------------------
@dataclass
class ProductQuantizer:
    """``m`` subspace codebooks of ``ksub`` centroids each (dsub = d // m)."""

    codebooks: jax.Array   # [m, ksub, dsub] f32

    @property
    def m(self) -> int:
        return int(self.codebooks.shape[0])

    @property
    def ksub(self) -> int:
        return int(self.codebooks.shape[1])

    @property
    def dsub(self) -> int:
        return int(self.codebooks.shape[2])


def pq_train(x: jax.Array, m: int, bits: int = 8, iters: int = 15,
             seed: int = 0) -> ProductQuantizer:
    """Independent k-means per subspace. ``d % m == 0`` required; the
    centroid count is ``min(2**bits, n)`` so tiny corpora still train."""
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    if d % m:
        raise ValueError(f"PQ: dim {d} not divisible by m={m}")
    if not 1 <= bits <= 8:
        raise ValueError(f"PQ: bits must be in 1..8, got {bits}")
    ksub = min(2 ** bits, n)
    dsub = d // m
    books = []
    for mm in range(m):
        sub = x[:, mm * dsub:(mm + 1) * dsub]
        cent, _ = kmeans(sub, ksub, iters=iters, seed=seed + mm)
        books.append(cent)
    return ProductQuantizer(codebooks=jnp.stack(books))


def pq_encode(pq: ProductQuantizer, x: jax.Array) -> jax.Array:
    """f32 [N, d] -> uint8 codes [N, m] (nearest centroid per subspace)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    xs = x.reshape(n, pq.m, pq.dsub)
    # d2[n, m, j] = ||x_sub - cb||^2 ; argmin over j
    cb = pq.codebooks
    d2 = (jnp.sum(xs * xs, -1)[:, :, None]
          - 2 * jnp.einsum("nms,mjs->nmj", xs, cb, precision=EXACT)
          + jnp.sum(cb * cb, -1)[None, :, :])
    return jnp.argmin(d2, axis=-1).astype(jnp.uint8)


def pq_decode(pq: ProductQuantizer, codes: jax.Array) -> jax.Array:
    """codes [N, m] -> reconstructed f32 [N, d]."""
    gathered = jnp.take_along_axis(
        pq.codebooks[None], codes.astype(jnp.int32)[:, :, None, None],
        axis=2)[:, :, 0, :]                            # [N, m, dsub]
    return gathered.reshape(codes.shape[0], pq.m * pq.dsub)


def adc_lut(codebooks: jax.Array, q: jax.Array) -> jax.Array:
    """Exact query-to-centroid distance LUT [Q, m, ksub] from raw arrays:
    ``lut[q, m, j] = ||q_sub_m - codebooks[m, j]||^2``. The ONE place the
    ADC LUT formula lives (the flat scan, the IVF probe scan and the
    public wrapper all call this; kernels/pq_adc/ref.py is a deliberate
    independent oracle)."""
    q = jnp.asarray(q, jnp.float32)
    m, _, dsub = codebooks.shape
    qs = q.reshape(q.shape[0], m, dsub)
    return (jnp.sum(qs * qs, -1)[:, :, None]
            - 2 * jnp.einsum("qms,mjs->qmj", qs, codebooks,
                             precision=EXACT)
            + jnp.sum(codebooks * codebooks, -1)[None, :, :])


def _code_offsets(codes: jax.Array, ksub: int) -> jax.Array:
    """codes [..., m] -> offsets into a [m*ksub]-flattened LUT row."""
    m = codes.shape[-1]
    return (codes.astype(jnp.int32)
            + jnp.arange(m, dtype=jnp.int32) * ksub)


def pq_adc_lut(pq: ProductQuantizer, q: jax.Array) -> jax.Array:
    """:func:`adc_lut` over a :class:`ProductQuantizer`."""
    return adc_lut(pq.codebooks, q)


def pq_adc_gather(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Sum the LUT over each row's codes: dist [Q, N] = sum_m lut[q, m, c]."""
    qn, m, ksub = lut.shape
    lut_flat = lut.reshape(qn, m * ksub)
    flat = _code_offsets(codes, ksub).reshape(-1)
    g = jnp.take(lut_flat, flat, axis=1)               # [Q, N*m]
    return g.reshape(qn, codes.shape[0], m).sum(-1)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_pq_search(centroids: jax.Array, lists: jax.Array, codes: jax.Array,
                  mask: jax.Array, codebooks: jax.Array, q: jax.Array,
                  k: int, nprobe: int) -> tuple[jax.Array, jax.Array]:
    """IVF probe scan over PQ list payloads via per-query ADC LUT.

    ``codes`` [C, cap, m] uint8; LUT built once per query, gathered per
    probed row. Same -1/-inf pad semantics as the flat IVF scan.
    """
    q = jnp.asarray(q, jnp.float32)
    m, ksub, _ = codebooks.shape
    d2c = (jnp.sum(q * q, 1)[:, None]
           - 2 * jnp.matmul(q, centroids.T, precision=EXACT)
           + jnp.sum(centroids * centroids, 1)[None, :])
    _, cells = jax.lax.top_k(-d2c, nprobe)             # [Q, P]
    ids = lists[cells]                                 # [Q, P, cap]
    msk = mask[cells]
    lut_flat = adc_lut(codebooks, q).reshape(q.shape[0], m * ksub)
    offs = _code_offsets(codes[cells], ksub)           # [Q, P, cap, m]
    qn, p, cap, _ = offs.shape
    g = jnp.take_along_axis(lut_flat, offs.reshape(qn, p * cap * m), axis=1)
    dist = g.reshape(qn, p, cap, m).sum(-1)
    s = jnp.where(msk, -dist, -jnp.inf)
    v, flat = jax.lax.top_k(s.reshape(qn, p * cap), k)
    idx = jnp.take_along_axis(ids.reshape(qn, p * cap), flat, axis=1)
    return v, jnp.where(jnp.isfinite(v), idx, -1)


def bytes_per_code(m: int, bits: int) -> int:
    """Stored PQ code size in bytes: one uint8 per subspace. bits < 8
    narrows the codebook (2^bits centroids) but codes are NOT bit-packed —
    report what is actually stored, not the ceil(m*bits/8) a packed layout
    would reach."""
    del bits  # kept in the signature so a future packed layout is non-breaking
    return max(1, m)
