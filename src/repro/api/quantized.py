"""Quantized ``VectorIndex`` tiers: SQ8 and PQ codes, flat or IVF-sharded.

The first index family where *memory*, not FLOPs, is the scaled resource:
every class here stores codes instead of f32 vectors and searches them
asymmetrically (exact f32 query vs quantized corpus), so the recall hit is
bounded by the reconstruction error alone.

=============  =======================================  ==================
factory stage  class                                    bytes / vector
=============  =======================================  ==================
``SQ8``        :class:`SQ8Index` (flat ADC scan)        d + 4
``PQ{m}x{b}``  :class:`PQIndex` (fused ADC kernel)      m (uint8/subspace)
``IVF{c},SQ8`` :class:`IVFSQ8Index` (probe + ADC)       d + 8
``IVF{c},PQ…`` :class:`IVFPQIndex` (probe + LUT ADC)    m + 4
=============  =======================================  ==================

All compose with any reducer through ``TwoStageIndex`` — e.g.
``"RAE64,IVF256,PQ8x8,Rerank4"`` = RAE 256->64, IVF over reduced space, PQ
codes in the lists, full-space rerank. Persistence follows the house
layout (``meta.json`` + ``arrays.npz``); codes round-trip as uint8.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import pq_adc
from ..search import ivf as ivf_lib
from ..search import quantize as qz
from .index import (SearchParams, VectorIndex, _load_arrays, _pad_result,
                    _probed_sizes, _save_dir, _timed, register_index)


def _drop_tombstones(vals, idx, alive: np.ndarray, k_req: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Strip tombstoned ids out of an over-fetched top-k.

    The flat quantized scans (``sq8_scan`` / ``pq_adc``) have no mask
    operand, so callers over-fetch ``k + n_dead`` rows — enough that the
    dead rows can never crowd out k alive ones — and this filters them,
    shifting survivors left (stable, so relative order is preserved) and
    padding the tail with the house (-inf, -1) convention."""
    v = np.asarray(vals, np.float32)
    i = np.asarray(idx)
    keep = (i >= 0) & alive[np.where(i >= 0, i, 0)]
    # stable sort on "dead?" moves survivors left without reordering them
    order = np.argsort(~keep, axis=1, kind="stable")[:, :k_req]
    rr = np.arange(v.shape[0])[:, None]
    kept = keep[rr, order]
    out_v = np.where(kept, v[rr, order], -np.inf).astype(np.float32)
    out_i = np.where(kept, i[rr, order], -1)
    return out_v, out_i


def _fold_alive_into_lists(lists, mask, alive):
    """Fold a row-level tombstone mask into IVF list slots: a dead row's
    slot is masked AND its id nulled to -1 — the probe scans keep real ids
    on masked slots (at -inf), which could surface when a probe holds
    fewer than k alive members."""
    al = jnp.asarray(np.asarray(alive, bool))
    mask = mask & al[jnp.where(lists >= 0, lists, 0)]
    return jnp.where(mask, lists, -1), mask


# ---------------------------------------------------------------------------
# SQ8 flat
# ---------------------------------------------------------------------------
@register_index("sq8_flat")
class SQ8Index(VectorIndex):
    """Flat exact-order ADC scan over SQ8 codes (4x smaller than f32).

    ``build`` fits the per-dim [min, max] codebook on the corpus and stores
    uint8 codes + per-row ``||x_hat||^2``; ``search`` never dequantizes —
    the scan is one f32xuint8 matmul (see ``search.quantize``)."""

    # SQ8 ordering is near-exact (error <= step/2/dim); a light oversample
    # under a rerank recovers the borderline swaps.
    stage1_oversample = 2

    _fp_exempt = {
        "_recon_sq": "derived: recomputable from _sq + _codes (both "
                     "hashed)",
    }

    def __init__(self):
        self._sq: Optional[qz.ScalarQuantizer] = None
        self._codes: Optional[jax.Array] = None
        self._recon_sq: Optional[jax.Array] = None

    @property
    def ntotal(self) -> int:
        return 0 if self._codes is None else int(self._codes.shape[0])

    @property
    def built(self) -> bool:
        return self._codes is not None

    @property
    def bytes_per_vector(self) -> float:
        """uint8 per dim + f32 reconstruction norm."""
        self._require_built()
        return float(self._codes.shape[1] + 4)

    @property
    def dim(self) -> int:
        self._require_built()
        return int(self._codes.shape[1])

    def _fingerprint_state(self) -> list:
        return [self._sq.vmin, self._sq.step, self._codes]

    def build(self, corpus: np.ndarray) -> "SQ8Index":
        corpus = jnp.asarray(corpus, jnp.float32)
        self._sq = qz.sq8_train(corpus)
        self._codes = qz.sq8_encode(self._sq, corpus)
        self._recon_sq = qz.sq8_recon_sq_norms(self._sq, self._codes)
        return self

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> "SearchResult":
        del params  # flat code scan has no knobs: every row is scored
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        k_eff = min(k, self.ntotal)
        if alive is None:
            return _timed(
                lambda: qz.sq8_scan(self._sq.vmin, self._sq.step, q,
                                    self._codes, self._recon_sq, k_eff),
                stats={"distance_evals": float(self.ntotal)})
        al = np.asarray(alive, bool)
        k_fetch = min(self.ntotal, k_eff + int((~al).sum()))

        def run():
            v, i = qz.sq8_scan(self._sq.vmin, self._sq.step, q, self._codes,
                               self._recon_sq, k_fetch)
            return _drop_tombstones(v, i, al, k_eff)

        return _timed(run, stats={"distance_evals": float(self.ntotal)})

    def save(self, directory: str) -> None:
        self._require_built()
        _save_dir(directory, {"kind": self.kind}, {
            "vmin": np.asarray(self._sq.vmin),
            "step": np.asarray(self._sq.step),
            "codes": np.asarray(self._codes),
            "recon_sq": np.asarray(self._recon_sq),
        })

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "SQ8Index":
        a = _load_arrays(directory)
        self = cls()
        self._sq = qz.ScalarQuantizer(vmin=jnp.asarray(a["vmin"]),
                                      step=jnp.asarray(a["step"]))
        self._codes = jnp.asarray(a["codes"])
        self._recon_sq = jnp.asarray(a["recon_sq"])
        return self


# ---------------------------------------------------------------------------
# PQ flat
# ---------------------------------------------------------------------------
@register_index("pq_flat")
class PQIndex(VectorIndex):
    """Flat ADC scan over PQ codes via the fused ``pq_adc`` kernel
    (Pallas on TPU, jnp oracle elsewhere). ``m`` bytes per vector (one
    uint8 code per subspace; bits < 8 narrows the codebook, not the
    storage) — 32x smaller than f32 at d=8m."""

    # ADC ordering is noisy at PQ compression rates: a true neighbor often
    # sits in the ADC top-few-hundred but not the top-k*rerank. Candidate
    # lists cost one LUT gather per row, so over-fetch aggressively and let
    # the exact rerank (TwoStageIndex) sort it out — FAISS refine / SCANN
    # reorder do the same.
    stage1_oversample = 8

    _fp_exempt = {
        "m": "build-time hyperparam; materialized in the hashed "
             "codebooks/codes shapes",
        "bits": "build-time hyperparam; materialized in the hashed "
                "codebooks shape",
        "kmeans_iters": "build-time hyperparam; materialized in the "
                        "hashed codebooks",
        "seed": "build-time hyperparam; materialized in the hashed "
                "codebooks",
    }

    def __init__(self, m: int = 8, bits: int = 8, kmeans_iters: int = 15,
                 seed: int = 0):
        self.m = m
        self.bits = bits
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self._pq: Optional[qz.ProductQuantizer] = None
        self._codes: Optional[jax.Array] = None

    @property
    def ntotal(self) -> int:
        return 0 if self._codes is None else int(self._codes.shape[0])

    @property
    def built(self) -> bool:
        return self._codes is not None

    @property
    def bytes_per_vector(self) -> float:
        return float(qz.bytes_per_code(self.m, self.bits))

    @property
    def dim(self) -> int:
        self._require_built()
        # codebooks are [m, 2^bits, d/m]
        return int(self._pq.codebooks.shape[0] * self._pq.codebooks.shape[2])

    def _fingerprint_state(self) -> list:
        return [self._pq.codebooks, self._codes]

    def build(self, corpus: np.ndarray) -> "PQIndex":
        corpus = jnp.asarray(corpus, jnp.float32)
        self._pq = qz.pq_train(corpus, self.m, self.bits,
                               iters=self.kmeans_iters, seed=self.seed)
        self._codes = qz.pq_encode(self._pq, corpus)
        return self

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> "SearchResult":
        del params  # flat ADC scan has no knobs: every row is scored
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        k_eff = min(k, self.ntotal)
        if alive is None:
            return _timed(lambda: pq_adc(q, self._pq.codebooks, self._codes,
                                         k_eff),
                          stats={"distance_evals": float(self.ntotal)})
        al = np.asarray(alive, bool)
        k_fetch = min(self.ntotal, k_eff + int((~al).sum()))

        def run():
            v, i = pq_adc(q, self._pq.codebooks, self._codes, k_fetch)
            return _drop_tombstones(v, i, al, k_eff)

        return _timed(run, stats={"distance_evals": float(self.ntotal)})

    def save(self, directory: str) -> None:
        self._require_built()
        _save_dir(directory, {"kind": self.kind, "m": self.m,
                              "bits": self.bits,
                              "kmeans_iters": self.kmeans_iters,
                              "seed": self.seed},
                  {"codebooks": np.asarray(self._pq.codebooks),
                   "codes": np.asarray(self._codes)})

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "PQIndex":
        a = _load_arrays(directory)
        self = cls(m=meta["m"], bits=meta["bits"],
                   kmeans_iters=meta["kmeans_iters"], seed=meta["seed"])
        self._pq = qz.ProductQuantizer(codebooks=jnp.asarray(a["codebooks"]))
        self._codes = jnp.asarray(a["codes"])
        return self


# ---------------------------------------------------------------------------
# IVF + quantized list payloads (shared coarse layer)
# ---------------------------------------------------------------------------
class _IVFQuantBase(VectorIndex):
    """Shared coarse layer: k-means cells from ``search.ivf`` whose padded
    dense lists store *codes* instead of f32 vectors."""

    _fp_exempt = {
        "n_cells": "build-time hyperparam; materialized in the hashed "
                   "centroids/lists arrays",
        "cell_cap": "build-time hyperparam; materialized in the hashed "
                    "lists shape",
        "kmeans_iters": "build-time hyperparam; materialized in the "
                        "hashed centroids",
        "seed": "build-time hyperparam; materialized in the hashed "
                "centroids/lists",
        "_mask": "derived: exactly (_lists >= 0), and _lists is hashed",
        "_cell_sizes": "derived from _mask; feeds host-side stats only",
        "spill": "build diagnostic; spilled membership is materialized "
                 "in the hashed _lists",
    }

    def __init__(self, n_cells: int = 256, nprobe: int = 0,
                 cell_cap: Optional[int] = None, kmeans_iters: int = 10,
                 seed: int = 0):
        self.n_cells = n_cells
        # ADC scans are cheap, so default to probing 2x the IVF-flat share
        self.nprobe = nprobe or max(8, n_cells // 8)
        self.cell_cap = cell_cap
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self._centroids: Optional[jax.Array] = None
        self._lists: Optional[jax.Array] = None
        self._mask: Optional[jax.Array] = None
        self._cell_sizes: Optional[np.ndarray] = None  # fixed at build
        self._ntotal = 0
        self.spill = 0

    @property
    def ntotal(self) -> int:
        return self._ntotal

    @property
    def built(self) -> bool:
        return self._lists is not None

    def _build_coarse(self, corpus: jax.Array) -> ivf_lib.IVFIndex:
        n_cells = min(self.n_cells, corpus.shape[0])
        coarse = ivf_lib.build(corpus, n_cells, cell_cap=self.cell_cap,
                               kmeans_iters=self.kmeans_iters, seed=self.seed)
        self._centroids = coarse.centroids
        self._lists = coarse.lists
        self._mask = coarse.list_mask
        self._cell_sizes = np.asarray(coarse.list_mask).sum(axis=1)
        self._ntotal = int(corpus.shape[0])
        self.spill = int(coarse.spill)
        return coarse

    @property
    def dim(self) -> int:
        self._require_built()
        return int(self._centroids.shape[1])

    def _fingerprint_state(self) -> list:
        # coarse layer; subclasses append their code payloads
        return [f"nprobe={self.nprobe}", self._centroids, self._lists]

    def set_params(self, params: SearchParams) -> None:
        """Adopt a tuned ``nprobe`` default (fingerprint state, same as
        :class:`~repro.api.index.IVFFlatIndex`)."""
        if params.nprobe is not None:
            self.nprobe = params.nprobe

    def _probe_budget(self, k: int,
                      params: Optional[SearchParams] = None
                      ) -> tuple[int, int, int]:
        """(k requested, k servable by the probe scan, nprobe).
        ``params.nprobe`` overrides ``self.nprobe`` for this call —
        ladder-snapped, so the static-arg jit caches stay bounded."""
        nprobe = (self.nprobe if params is None or params.nprobe is None
                  else params.nprobe)
        nprobe = min(nprobe, int(self._centroids.shape[0]))
        k_req = min(k, self.ntotal)
        k_eff = min(k_req, nprobe * int(self._lists.shape[1]))
        return k_req, k_eff, nprobe

    def _probe_stats(self, queries: np.ndarray,
                     nprobe: int) -> dict[str, float]:
        return {"distance_evals": _probed_sizes(queries, self._centroids,
                                                self._cell_sizes, nprobe),
                "centroid_evals": float(self._centroids.shape[0])}

    def _coarse_meta(self) -> dict[str, Any]:
        return {"kind": self.kind, "n_cells": self.n_cells,
                "nprobe": self.nprobe, "kmeans_iters": self.kmeans_iters,
                "seed": self.seed, "ntotal": self._ntotal,
                "spill": self.spill}

    def _coarse_arrays(self) -> dict[str, np.ndarray]:
        return {"centroids": np.asarray(self._centroids),
                "lists": np.asarray(self._lists),
                "mask": np.asarray(self._mask)}

    def _load_coarse(self, meta: dict[str, Any],
                     a: dict[str, np.ndarray]) -> None:
        self._centroids = jnp.asarray(a["centroids"])
        self._lists = jnp.asarray(a["lists"])
        self._mask = jnp.asarray(a["mask"])
        self._cell_sizes = a["mask"].sum(axis=1)
        self._ntotal = int(meta["ntotal"])
        self.spill = int(meta.get("spill", 0))


@register_index("ivf_sq8")
class IVFSQ8Index(_IVFQuantBase):
    """IVF cells whose lists hold SQ8 codes: probe ``nprobe`` cells, scan
    their codes dequant-free. Short results pad with -1/-inf like
    ``IVFFlatIndex``."""

    stage1_oversample = 2  # same near-exact ordering as SQ8Index

    _fp_exempt = {
        "_recon_sq": "derived: recomputable from _sq + _codes (both "
                     "hashed)",
    }

    def __init__(self, n_cells: int = 256, nprobe: int = 0,
                 cell_cap: Optional[int] = None, kmeans_iters: int = 10,
                 seed: int = 0):
        super().__init__(n_cells, nprobe, cell_cap, kmeans_iters, seed)
        self._sq: Optional[qz.ScalarQuantizer] = None
        self._codes: Optional[jax.Array] = None      # [C, cap, d] uint8
        self._recon_sq: Optional[jax.Array] = None   # [C, cap]

    @property
    def bytes_per_vector(self) -> float:
        """uint8 per dim + f32 recon norm + int32 row id."""
        self._require_built()
        return float(self._codes.shape[2] + 4 + 4)

    def _fingerprint_state(self) -> list:
        return super()._fingerprint_state() + [self._sq.vmin, self._sq.step,
                                               self._codes]

    def build(self, corpus: np.ndarray) -> "IVFSQ8Index":
        corpus = jnp.asarray(corpus, jnp.float32)
        coarse = self._build_coarse(corpus)
        self._sq = qz.sq8_train(corpus)
        rows = ivf_lib.store_rows(coarse)
        c, cap, d = rows.shape
        flat = qz.sq8_encode(self._sq, jnp.asarray(rows.reshape(c * cap, d)))
        self._codes = flat.reshape(c, cap, d)
        self._recon_sq = qz.sq8_recon_sq_norms(
            self._sq, flat).reshape(c, cap)
        return self

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> "SearchResult":
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        k_req, k_eff, nprobe = self._probe_budget(k, params)
        lists, mask = self._lists, self._mask
        if alive is not None:
            lists, mask = _fold_alive_into_lists(lists, mask, alive)

        def run():
            v, i = qz.ivf_sq8_search(self._centroids, lists,
                                     self._codes, self._recon_sq, mask,
                                     self._sq.vmin, self._sq.step, q,
                                     k_eff, nprobe)
            return _pad_result(v, i, k_req)

        return _timed(run, stats=self._probe_stats(queries, nprobe))

    def save(self, directory: str) -> None:
        self._require_built()
        arrays = self._coarse_arrays()
        arrays.update({"vmin": np.asarray(self._sq.vmin),
                       "step": np.asarray(self._sq.step),
                       "codes": np.asarray(self._codes),
                       "recon_sq": np.asarray(self._recon_sq)})
        _save_dir(directory, self._coarse_meta(), arrays)

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "IVFSQ8Index":
        a = _load_arrays(directory)
        self = cls(n_cells=meta["n_cells"], nprobe=meta["nprobe"],
                   kmeans_iters=meta["kmeans_iters"], seed=meta["seed"])
        self._load_coarse(meta, a)
        self._sq = qz.ScalarQuantizer(vmin=jnp.asarray(a["vmin"]),
                                      step=jnp.asarray(a["step"]))
        self._codes = jnp.asarray(a["codes"])
        self._recon_sq = jnp.asarray(a["recon_sq"])
        return self


@register_index("ivf_pq")
class IVFPQIndex(_IVFQuantBase):
    """IVF cells whose lists hold PQ codes, scanned with a per-query ADC
    LUT — the classic FAISS ``IVFx,PQy`` tier. PQ codebooks are trained on
    the raw corpus (not residuals): one global LUT per query instead of one
    per probed cell, which keeps the scan a single gather."""

    stage1_oversample = 8  # same ADC ordering noise as PQIndex

    _fp_exempt = {
        "m": "build-time hyperparam; materialized in the hashed "
             "codebooks/codes shapes",
        "bits": "build-time hyperparam; materialized in the hashed "
                "codebooks shape",
        "pq_iters": "build-time hyperparam; materialized in the hashed "
                    "codebooks",
    }

    def __init__(self, n_cells: int = 256, m: int = 8, bits: int = 8,
                 nprobe: int = 0, cell_cap: Optional[int] = None,
                 kmeans_iters: int = 10, pq_iters: int = 15, seed: int = 0):
        super().__init__(n_cells, nprobe, cell_cap, kmeans_iters, seed)
        self.m = m
        self.bits = bits
        self.pq_iters = pq_iters
        self._pq: Optional[qz.ProductQuantizer] = None
        self._codes: Optional[jax.Array] = None      # [C, cap, m] uint8

    @property
    def bytes_per_vector(self) -> float:
        """packed code + int32 row id."""
        return float(qz.bytes_per_code(self.m, self.bits) + 4)

    def _fingerprint_state(self) -> list:
        return super()._fingerprint_state() + [self._pq.codebooks,
                                               self._codes]

    def build(self, corpus: np.ndarray) -> "IVFPQIndex":
        corpus = jnp.asarray(corpus, jnp.float32)
        coarse = self._build_coarse(corpus)
        self._pq = qz.pq_train(corpus, self.m, self.bits,
                               iters=self.pq_iters, seed=self.seed)
        rows = ivf_lib.store_rows(coarse)
        c, cap, d = rows.shape
        flat = qz.pq_encode(self._pq, jnp.asarray(rows.reshape(c * cap, d)))
        self._codes = flat.reshape(c, cap, self.m)
        return self

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> "SearchResult":
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        k_req, k_eff, nprobe = self._probe_budget(k, params)
        lists, mask = self._lists, self._mask
        if alive is not None:
            lists, mask = _fold_alive_into_lists(lists, mask, alive)

        def run():
            v, i = qz.ivf_pq_search(self._centroids, lists,
                                    self._codes, mask,
                                    self._pq.codebooks, q, k_eff, nprobe)
            return _pad_result(v, i, k_req)

        return _timed(run, stats=self._probe_stats(queries, nprobe))

    def save(self, directory: str) -> None:
        self._require_built()
        arrays = self._coarse_arrays()
        arrays.update({"codebooks": np.asarray(self._pq.codebooks),
                       "codes": np.asarray(self._codes)})
        meta = self._coarse_meta()
        meta.update({"m": self.m, "bits": self.bits,
                     "pq_iters": self.pq_iters})
        _save_dir(directory, meta, arrays)

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "IVFPQIndex":
        a = _load_arrays(directory)
        self = cls(n_cells=meta["n_cells"], m=meta["m"], bits=meta["bits"],
                   nprobe=meta["nprobe"], kmeans_iters=meta["kmeans_iters"],
                   pq_iters=meta["pq_iters"], seed=meta["seed"])
        self._load_coarse(meta, a)
        self._pq = qz.ProductQuantizer(codebooks=jnp.asarray(a["codebooks"]))
        self._codes = jnp.asarray(a["codes"])
        return self
