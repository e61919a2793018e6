"""Graph ``VectorIndex`` tier: HNSW beam search behind the factory.

The first index family where per-query work is *sublinear in N* — beam
search visits a few hundred nodes of a 20k corpus instead of scanning all
of it (``SearchResult.stats["distance_evals"]`` reports the visited
count). The engine lives in :mod:`repro.search.hnsw`; this class adapts it
to the ``build / search / save / load`` protocol and the factory grammar::

    index_factory("HNSW32")                  # graph over the raw space
    index_factory("RAE64,HNSW32,Rerank4")    # graph over the reduced space,
                                             # exact full-space rerank

``M`` (the factory numeral) caps per-node degree — ``M`` on upper layers,
``2M`` at layer 0; ``ef_construction`` is the insert-time beam width
(recall of the *graph*), ``ef_search`` the query-time beam width (the
recall/latency knob — search always uses ``max(ef_search, k)``).

Two traversal engines serve queries (same semantics, same ``ef``):
``build``/``load`` compile the packed dense adjacency
(:meth:`HNSWGraph.pack`), and ``search`` routes batches (q > 1) through
the array-native batched frontier loop — one fused ``graph_beam`` dispatch
per hop for the WHOLE batch — while lone queries (q = 1) keep the
sequential heapq beam, which wins when there is no batch to amortize
across. ``batched=True/False`` pins either engine. Within the batched
engine answers are bitwise-deterministic and independent of batch-mates;
ACROSS the two engines neighbor sets agree up to beam-boundary ties
(exactly at ``frontier=1``; >= 99% of queries at the serving default,
asserted in tests) and scores differ only in rounding — so a query served
lone vs coalesced can, rarely, swap its boundary neighbor. Under the
deployment ``Rerank`` stack the exact full-space rerank absorbs exactly
that noise; pin ``batched`` if strict cross-batch-size reproducibility
matters more than lone-query latency. The packed
arrays are persisted and fingerprinted, so a reloaded index serves the
fast path without repacking and the serving cache can never alias the two
forms.

Under a rerank the graph declares ``stage1_oversample=2``: beam search
returns exact reduced-space distances but can *miss* neighbors near the
beam boundary, so ``TwoStageIndex`` widens k1 (which also widens the beam)
and lets the full-space rerank absorb the ordering noise.

**Quantized payloads** (``quant="sq8"`` / ``"pq"``; the factory's
``"RAE64,HNSW32,SQ8,Rerank4"``): the graph is built in f32 as usual, then
a code payload (:func:`repro.search.hnsw.make_graph_codes`) is trained
over the same corpus and attached, and every batched hop gathers *codes*
instead of f32 rows — 68 bytes per gathered neighbor for SQ8 at d=64, 12
for PQ8x8, versus 260 for the f32 row+norm (the
``stats["gather_bytes_per_hop"]`` metric the benches gate). Quantized
scores are approximations, so a quantized index inherits its codec's
oversample (2 for SQ8, 8 for PQ) and leans on the ``Rerank`` stage to
recover exact ordering. All queries — including q=1 — are pinned to the
batched engine: the sequential heapq beam scores f32 and would answer
differently, which the serving cache's row-independence contract forbids.
The codec state is fingerprinted (a quantized graph can never alias its
f32 twin in the serve cache) and persisted, so a reloaded index serves
codes without re-training.

Persistence follows the house layout: ``meta.json`` + ``arrays.npz``
holding the corpus vectors, per-node levels, the padded-dense adjacency of
every layer, the packed form's precomputed norms, and the code payload.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Union

import numpy as np

from ..search import hnsw as hnsw_lib
from .index import (SearchParams, SearchResult, VectorIndex, _load_arrays,
                    _save_dir, register_index)


@register_index("hnsw")
class HNSWIndex(VectorIndex):
    """Hierarchical navigable small-world graph (euclidean only)."""

    stage1_oversample = 2

    _fp_exempt = {
        "m": "build-time degree cap; materialized in the hashed "
             "links0/links shapes",
        "ef_construction": "insert-time beam width; materialized in the "
                           "hashed adjacency",
        "seed": "build-time level draw; materialized in the hashed "
                "levels/adjacency",
        "pq_m": "codec train knob; materialized in the hashed codebook "
                "and code-payload shapes",
        "pq_bits": "codec train knob; materialized in the hashed "
                   "codebook width",
        "kmeans_iters": "codec train knob; materialized in the hashed "
                        "codebooks",
        "stage1_oversample": "stage-composition hint derived from quant "
                             "(hashed); not traversal state",
    }

    def __init__(self, m: int = 32, ef_construction: int = 100,
                 ef_search: int = 64, seed: int = 0,
                 batched: Union[str, bool] = "auto", frontier: int = 8,
                 quant: Optional[str] = None, pq_m: int = 8,
                 pq_bits: int = 8, kmeans_iters: int = 15):
        if m < 2:
            raise ValueError(f"HNSW needs M >= 2, got {m}")
        if batched not in ("auto", True, False):
            raise ValueError(f"batched must be 'auto', True or False, "
                             f"got {batched!r}")
        if frontier < 1:
            raise ValueError(f"frontier must be >= 1, got {frontier}")
        if quant not in (None, "sq8", "pq"):
            raise ValueError(f"quant must be None, 'sq8' or 'pq', "
                             f"got {quant!r}")
        if quant == "pq":
            if pq_m < 1:
                raise ValueError(f"PQ needs at least one subspace, "
                                 f"got pq_m={pq_m}")
            if not 1 <= pq_bits <= 8:
                raise ValueError(f"PQ bits must be in 1..8, got {pq_bits}")
            # approximate ADC hops miss more boundary neighbors than SQ8;
            # inherit the PQ codec's wider oversample so the Rerank stage
            # sees enough candidates (instance override — the class attr
            # stays 2 for f32/SQ8 graphs)
            self.stage1_oversample = 8
        self.m = m
        self.ef_construction = ef_construction
        self.ef_search = ef_search
        self.seed = seed
        self.batched = batched
        self.frontier = frontier
        self.quant = quant
        self.pq_m = pq_m
        self.pq_bits = pq_bits
        self.kmeans_iters = kmeans_iters
        self._g: Optional[hnsw_lib.HNSWGraph] = None

    @property
    def ntotal(self) -> int:
        return 0 if self._g is None else self._g.ntotal

    @property
    def built(self) -> bool:
        return self._g is not None

    @property
    def bytes_per_vector(self) -> float:
        """f32 vector + int32 link slots in every layer the node occupies
        (2M at layer 0, M per upper layer — averaged over the geometric
        level distribution) + int32 level; a quantized payload adds its
        per-node code row + f32 bias on top (the f32 vectors stay — they
        serve build, the sequential engine, and connectivity repair; the
        payload shrinks what the *hop gather* streams, not total RAM)."""
        self._require_built()
        g = self._g
        upper_slots = g.M * float(g.levels.mean())
        codec = 0.0 if g.codec is None else float(g.codec.gather_bytes)
        return float(g.vecs.shape[1] * 4
                     + 4 * (g.links0.shape[1] + upper_slots) + 4 + codec)

    @property
    def dim(self) -> int:
        self._require_built()
        return int(self._g.vecs.shape[1])

    def _fingerprint_state(self) -> list:
        # full traversal state: vectors, EVERY layer's adjacency, levels,
        # entry (upper layers steer the layer-0 beam entry, so two graphs
        # differing only above layer 0 answer differently); ef_search and
        # the engine routing are query-time knobs that change answers
        # (batched scores round differently), so they are part of
        # identity. This also covers the packed form without touching it:
        # its tables share links0/links' bytes and its norms derive from
        # vecs (all hashed here), while the batched/frontier flags make an
        # index serving the packed fast path never alias one pinned to
        # the ragged sequential engine — and packing later (load, save)
        # can't shift the hash.
        g = self._g
        state = [f"ef={self.ef_search}:entry={g.entry}"
                 f":batched={self.batched}:frontier={self.frontier}"
                 f":quant={self.quant}",
                 g.vecs, g.links0, g.links, g.levels]
        if g.codec is not None:
            # codec state is identity: two graphs differing only in their
            # code payload answer differently, so the serve cache must
            # never alias them (nor a quantized graph with its f32 twin)
            c = g.codec
            state += [c.codes, c.node_bias]
            state += [a for a in (c.vmin, c.step, c.codebooks)
                      if a is not None]
        return state

    def build(self, corpus: np.ndarray) -> "HNSWIndex":
        self._g = hnsw_lib.build(corpus, M=self.m,
                                 ef_construction=self.ef_construction,
                                 seed=self.seed)
        if self.quant is not None:
            # graph construction stays f32 (insertion quality); the code
            # payload is trained over the same corpus and swaps what the
            # batched hop gather reads. Raises at build for impossible
            # codecs (e.g. PQ with d % m != 0) — never a broken index.
            self._g.codec = hnsw_lib.make_graph_codes(
                self._g.vecs, self.quant, m=self.pq_m, bits=self.pq_bits,
                iters=self.kmeans_iters, seed=self.seed)
        if self.batched is not False or self.quant is not None:
            self._g.pack()  # compile the dense form once, at build time
        return self

    def _use_batched(self, nq: int) -> bool:
        if self.quant is not None:
            # code payloads only exist on the batched path; routing q=1 to
            # the f32 heapq beam would answer differently lone vs
            # coalesced, which the serving cache contract forbids
            return True
        if self.batched == "auto":
            # the batched frontier loop amortizes per-hop work across the
            # batch; with nothing to amortize (q=1) the heapq beam wins
            return nq > 1
        return bool(self.batched)

    def add(self, vecs: np.ndarray) -> np.ndarray:
        """Incremental insert: run HNSW Alg. 1 for each new row against the
        live graph (same code path as ``build``, so insert order — build
        then add — is the only divergence from a from-scratch build), extend
        the code payload with the already-trained codec, and re-pack so the
        batched drivers see the new rows. Returns the new row ids."""
        self._require_built()
        nv = np.asarray(vecs, np.float32)
        ids = hnsw_lib.insert_batch(self._g, nv,
                                    ef_construction=self.ef_construction,
                                    seed=self.seed)
        if self.batched is not False or self.quant is not None:
            self._g.pack()  # re-pack eagerly: serving must never stall
        return ids

    def set_params(self, params: SearchParams) -> None:
        """Adopt a tuned ``ef_search`` default. ``ef_search`` is
        fingerprint state, so the serving cache sees a new identity."""
        if params.ef_search is not None:
            self.ef_search = params.ef_search

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> SearchResult:
        """Beam search with ef = max(ef_search, k). Queries whose beam
        holds fewer than k nodes pad the tail with index -1 / score -inf
        (FAISS convention, same as the IVF tiers). ``alive`` (bool
        [ntotal]) tombstones rows out of BOTH engines — a dead node never
        enters a beam; the entry point must be alive (callers that delete
        it reassign via :func:`repro.search.hnsw.reassign_entry`, which
        ``MutableIndex.delete`` does automatically).

        ``params.ef_search`` overrides ``self.ef_search`` for this call;
        ladder-snapped values keep the ef-dependent trace set bounded, so
        laddered calls stay compile-budget-zero once warm."""
        self._require_built()
        q = np.asarray(queries, np.float32)
        k_req = min(k, self.ntotal)
        ef_base = (self.ef_search if params is None or params.ef_search is None
                   else params.ef_search)
        ef = max(ef_base, k_req)
        t0 = time.perf_counter()
        if self._use_batched(q.shape[0]):
            driver = hnsw_lib.batched_driver()
            scores, idx, evals, hops = hnsw_lib.search_batched(
                self._g, q, k_req, ef_search=ef, impl=driver,
                frontier=self.frontier, alive=alive)
            g = self._g
            row_bytes = (g.codec.gather_bytes if g.codec is not None
                         else 4 * g.vecs.shape[1] + 4)
            stats = {"distance_evals": float(evals.mean()),
                     "beam_hops": float(hops),
                     # payload bytes the traversal streamed per fused hop
                     # (each eval gathers one row: codes+bias when
                     # quantized, f32 row+norm otherwise) — the bandwidth
                     # axis the graph bench gates
                     "gather_bytes_per_hop":
                         float(evals.sum() * row_bytes) / max(hops, 1),
                     # 1.0 when the layer-0 hops ran the Pallas kernel
                     "pallas_hop": float(driver == "fused")}
        else:
            scores, idx, evals = hnsw_lib.search(self._g, q, k_req,
                                                 ef_search=ef, alive=alive)
            stats = {"distance_evals": float(evals.mean())}
        dt = time.perf_counter() - t0
        return SearchResult(scores=scores, indices=idx, latency_s=dt,
                            stats=stats)

    def save(self, directory: str) -> None:
        self._require_built()
        g = self._g
        p = g.pack()  # always persist the packed form alongside the graph
        arrays = {"vecs": g.vecs, "levels": g.levels,
                  "links0": g.links0, "links": g.links,
                  "packed_vecs_sq": p.vecs_sq}
        if g.codec is not None:
            # trained codec state rides along so a reloaded index serves
            # codes without re-training (k-means is seed-stable but slow)
            arrays["codec_codes"] = g.codec.codes
            arrays["codec_node_bias"] = g.codec.node_bias
            if g.codec.kind == "sq8":
                arrays["codec_vmin"] = g.codec.vmin
                arrays["codec_step"] = g.codec.step
            else:
                arrays["codec_codebooks"] = g.codec.codebooks
        _save_dir(directory,
                  {"kind": self.kind, "m": self.m,
                   "ef_construction": self.ef_construction,
                   "ef_search": self.ef_search, "seed": self.seed,
                   "entry": int(g.entry), "packed": True,
                   "batched": self.batched, "frontier": self.frontier,
                   "quant": self.quant, "pq_m": self.pq_m,
                   "pq_bits": self.pq_bits,
                   "kmeans_iters": self.kmeans_iters},
                  # the packed adjacency is byte-identical to links0/links
                  # (pack() only makes them contiguous), so persisting it
                  # "alongside" means sharing their bytes: only the
                  # packed-exclusive norms (and codec) are written extra
                  arrays)

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "HNSWIndex":
        self = cls(m=meta["m"], ef_construction=meta["ef_construction"],
                   ef_search=meta["ef_search"], seed=meta["seed"],
                   batched=meta.get("batched", "auto"),
                   frontier=int(meta.get("frontier", 8)),
                   quant=meta.get("quant"),
                   pq_m=int(meta.get("pq_m", 8)),
                   pq_bits=int(meta.get("pq_bits", 8)),
                   kmeans_iters=int(meta.get("kmeans_iters", 15)))
        a = _load_arrays(directory)
        links = a["links"]
        if links.size == 0:  # single-layer graph round-trips as [0, N, M]
            links = links.reshape(0, a["vecs"].shape[0], meta["m"])
        self._g = hnsw_lib.HNSWGraph(
            vecs=a["vecs"], levels=a["levels"], links0=a["links0"],
            links=links, entry=int(meta["entry"]), M=int(meta["m"]))
        if "packed_vecs_sq" in a:  # pre-PR-5 saves: pack() on first batch
            # zero repack work: npz loads are C-contiguous, so the packed
            # tables ARE the loaded adjacency; only the norms come from
            # the file
            self._g.packed = hnsw_lib.PackedHNSW(
                nbrs0=self._g.links0, upper=self._g.links,
                vecs_sq=a["packed_vecs_sq"])
        if self.quant is not None:
            self._g.codec = hnsw_lib.GraphCodes(
                kind=self.quant, codes=a["codec_codes"],
                node_bias=a["codec_node_bias"],
                vmin=a.get("codec_vmin"), step=a.get("codec_step"),
                codebooks=a.get("codec_codebooks"))
        return self
