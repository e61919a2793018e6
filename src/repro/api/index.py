"""``VectorIndex``: one build/search/save/load interface for every search tier.

``FlatIndex`` wraps the exact distributed scan (``search.distributed``),
``IVFFlatIndex`` the coarse-quantized probe scan (``search.ivf``), and
``TwoStageIndex`` composes ANY :class:`~repro.api.reducer.Reducer` with ANY
base index — reduced-space candidate generation, full-space rerank (the
paper's deployment story, previously hardwired to RAE + flat scan in
``search.twostage``).

``search`` returns a uniform :class:`SearchResult` with device-synchronized
wall latency. Scores follow the engine convention: higher = closer
(negative squared euclidean / cosine similarity).

Persistence layout mirrors the reducers: ``meta.json`` + ``arrays.npz``
per directory; ``TwoStageIndex`` nests ``reducer/`` and ``base/``
subdirectories. ``load_index(dir)`` dispatches on ``meta.json["kind"]``.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.common import NULL_CTX, MeshCtx
from ..search import distributed as ds
from ..search import ivf as ivf_lib
from ..search import twostage as ts_lib
from .reducer import Reducer, load_reducer

_META = "meta.json"
_ARRAYS = "arrays.npz"


#: Geometric ladder every per-call knob snaps to — each rung ~1.5x the
#: previous (8*2^i interleaved with 12*2^i). The knobs feed jit static
#: arguments (IVF ``nprobe``, HNSW ``ef``, the rerank ``k1``), so an
#: arbitrary integer per call would mint a fresh XLA compile per value;
#: snapping bounds every per-knob jit cache to at most ``len(KNOB_LADDER)``
#: entries, which is what keeps laddered serving compile-budget-zero under
#: ``analysis.runtime.no_retrace`` once each rung is warmed.
KNOB_LADDER = (8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512,
               768, 1024, 1536, 2048)


def snap_knob(value: int) -> int:
    """Round ``value`` UP to its :data:`KNOB_LADDER` rung. Rounding up
    (never down) means a snapped knob always does at least the work the
    caller asked for; values past the top rung clamp to it."""
    v = int(value)
    for rung in KNOB_LADDER:
        if rung >= v:
            return rung
    return KNOB_LADDER[-1]


def next_rung(value: int) -> int:
    """The ladder rung strictly above ``value``'s — the escalation step.
    The top rung escalates to itself (there is nowhere left to go)."""
    snapped = snap_knob(value)
    i = KNOB_LADDER.index(snapped)
    return KNOB_LADDER[min(i + 1, len(KNOB_LADDER) - 1)]


@dataclass(frozen=True)
class SearchParams:
    """Per-call search-knob overrides, threaded through every
    ``VectorIndex.search`` as ``params=``. ``None`` leaves that knob at
    the index's own default; each tier consumes the knobs it understands
    and forwards the rest down its stack (``TwoStageIndex`` applies
    ``rerank_k1`` and hands the whole object to its base; ``Sharded`` /
    ``Mutable`` forward verbatim; ``Flat`` and the flat quantized scans
    have no knobs and ignore it).

    Values are snapped UP to :data:`KNOB_LADDER` at construction, so two
    ``SearchParams`` resolving to the same operating point compare equal
    — the serving cache keys on :meth:`key` — and the jit caches stay
    bounded (see :data:`KNOB_LADDER`). ``set_params`` on an index applies
    the same knobs as its new *defaults*, moving the fingerprint (the
    knobs are fingerprint state), which is what lets the serving cache
    distinguish answers computed under different tuned points."""

    ef_search: Optional[int] = None
    nprobe: Optional[int] = None
    rerank_k1: Optional[int] = None

    def __post_init__(self):
        for name in ("ef_search", "nprobe", "rerank_k1"):
            v = getattr(self, name)
            if v is None:
                continue
            if int(v) < 1:
                raise ValueError(f"SearchParams.{name} must be >= 1, "
                                 f"got {v}")
            object.__setattr__(self, name, snap_knob(v))

    def key(self) -> tuple:
        """Hashable operating-point token (cache keys, curve JSON)."""
        return (self.ef_search, self.nprobe, self.rerank_k1)

    def merged(self, override: "SearchParams") -> "SearchParams":
        """This point with ``override``'s set knobs winning."""
        return SearchParams(
            ef_search=override.ef_search if override.ef_search is not None
            else self.ef_search,
            nprobe=override.nprobe if override.nprobe is not None
            else self.nprobe,
            rerank_k1=override.rerank_k1 if override.rerank_k1 is not None
            else self.rerank_k1)

    def escalated(self) -> "SearchParams":
        """One ladder rung up on every set knob — the pass-2 point of
        per-query adaptive escalation. Unset knobs stay unset."""
        return SearchParams(
            ef_search=None if self.ef_search is None
            else next_rung(self.ef_search),
            nprobe=None if self.nprobe is None else next_rung(self.nprobe),
            rerank_k1=None if self.rerank_k1 is None
            else next_rung(self.rerank_k1))

    def to_dict(self) -> dict[str, Optional[int]]:
        return {"ef_search": self.ef_search, "nprobe": self.nprobe,
                "rerank_k1": self.rerank_k1}

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "SearchParams":
        return cls(ef_search=d.get("ef_search"), nprobe=d.get("nprobe"),
                   rerank_k1=d.get("rerank_k1"))


@dataclass
class SearchResult:
    """Uniform k-NN result: ``scores``/``indices`` are [Q, k]; higher score
    = closer; ``latency_s`` is device-synchronized wall time of the query.

    ``stats`` carries per-query work counters; every built-in index reports
    ``distance_evals`` — the mean number of corpus vectors whose distance
    to the query was evaluated (flat scan = N, IVF = probed list sizes,
    HNSW = beam-visited count) — the sublinearity axis benchmarks report
    next to recall and QPS."""

    scores: np.ndarray
    indices: np.ndarray
    latency_s: float
    stats: dict[str, float] = field(default_factory=dict)

    @property
    def k(self) -> int:
        return self.indices.shape[1]

    @property
    def distance_evals(self) -> Optional[float]:
        """Mean distance evaluations per query (None if not reported)."""
        return self.stats.get("distance_evals")


# ---------------------------------------------------------------------------
# Registry / persistence plumbing
# ---------------------------------------------------------------------------
_INDEXES: dict[str, type] = {}


def register_index(name: str):
    def deco(cls):
        _INDEXES[name.lower()] = cls
        cls.kind = name.lower()
        return cls

    return deco


def load_index(directory: str) -> "VectorIndex":
    with open(os.path.join(directory, _META)) as f:
        meta = json.load(f)
    try:
        cls = _INDEXES[meta["kind"]]
    except KeyError:
        raise KeyError(f"unknown index kind {meta['kind']!r}; "
                       f"known: {sorted(_INDEXES)}") from None
    return cls._load(directory, meta)


def _save_dir(directory: str, meta: dict[str, Any],
              arrays: dict[str, np.ndarray]) -> None:
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, _META), "w") as f:
        json.dump(meta, f, indent=1)
    np.savez(os.path.join(directory, _ARRAYS), **arrays)


def _load_arrays(directory: str) -> dict[str, np.ndarray]:
    with np.load(os.path.join(directory, _ARRAYS)) as z:
        return {k: z[k] for k in z.files}


class VectorIndex:
    """Base class: ``build(corpus)`` then ``search(queries, k)``."""

    kind: str = "abstract"

    #: When this index serves as stage 1 under a rerank (``TwoStageIndex``),
    #: fetch this multiple of the rerank budget as candidates. Lossy-ranking
    #: tiers (PQ/ADC: candidate lists are cheap, ordering is noisy) override
    #: with > 1 so the exact rerank sees past the quantization noise.
    stage1_oversample: int = 1

    @property
    def ntotal(self) -> int:
        raise NotImplementedError

    @property
    def built(self) -> bool:
        raise NotImplementedError

    @property
    def bytes_per_vector(self) -> float:
        """Per-vector payload of the stored search structure (codes +
        per-vector auxiliaries), the memory axis benchmarks report next to
        recall/QPS. Composite indexes report their stage-1 payload."""
        raise NotImplementedError

    @property
    def dim(self) -> int:
        """Query dimensionality this index accepts (the ORIGINAL space for
        composite indexes — what a client hands ``search``)."""
        raise NotImplementedError

    def _fingerprint_state(self) -> list:
        """Arrays/strings that identify the searchable content. Subclasses
        list whatever distinguishes two builds: the stored vectors, codes,
        or (for composites) the children's fingerprints."""
        raise NotImplementedError

    def fingerprint(self) -> str:
        """Stable content hash of the built index. Two indexes answering
        queries identically hash equal; rebuilding over a different corpus
        (or swapping a stage) changes it — the serving cache keys results
        on it so a hot swap can never serve stale answers."""
        self._require_built()
        h = hashlib.sha1()
        h.update(f"{self.kind}:{self.ntotal}".encode())
        for item in self._fingerprint_state():
            if isinstance(item, str):
                h.update(item.encode())
            else:
                a = np.asarray(item)
                h.update(f"{a.shape}:{a.dtype}".encode())
                h.update(a.tobytes())
        return h.hexdigest()[:16]

    def build(self, corpus: np.ndarray) -> "VectorIndex":
        raise NotImplementedError

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> SearchResult:
        """k-NN. ``alive`` (bool [ntotal], optional) tombstones rows: a
        dead row never appears in the result — not even as a pre-rerank
        candidate inside a composite — its slot padding to (-inf, -1).
        ``alive=None`` must answer bitwise identically to the tier's
        static path. Owned and threaded by :class:`MutableIndex`; static
        callers never pass it.

        ``params`` (:class:`SearchParams`, optional) overrides the tier's
        search knobs for THIS call only: each tier consumes what it
        understands (IVF ``nprobe``, HNSW ``ef_search``, TwoStage
        ``rerank_k1``), forwards the object down composite stacks, and
        ignores knobs it has none of. ``params=None`` must answer bitwise
        identically to the pre-params path."""
        raise NotImplementedError

    def set_params(self, params: SearchParams) -> None:
        """Apply ``params``'s set knobs as this index's new DEFAULTS
        (tuned operating point). Knob attributes are fingerprint state on
        every tier that implements this, so applying a tuned point moves
        the fingerprint — the serving cache can never replay an answer
        computed under different knobs. Tiers without knobs ignore it."""
        del params

    def save(self, directory: str) -> None:
        raise NotImplementedError

    def _require_built(self):
        if not self.built:
            raise RuntimeError(f"{self.kind}: search before build")


def _pad_result(v: jax.Array, i: jax.Array, k_req: int
                ) -> tuple[jax.Array, jax.Array]:
    """FAISS pad convention when fewer than k candidates exist: tail rows
    get score -inf / index -1. Shared by every tier that can come up
    short (IVF probes, quantized lists)."""
    pad = k_req - v.shape[1]
    if pad <= 0:
        return v, i
    v = jnp.concatenate([v, jnp.full((v.shape[0], pad), -jnp.inf, v.dtype)], 1)
    i = jnp.concatenate([i, jnp.full((i.shape[0], pad), -1, i.dtype)], 1)
    return v, i


def _timed(fn: Callable[[], tuple[jax.Array, jax.Array]],
           stats: Optional[dict[str, float]] = None) -> SearchResult:
    """Monotonic wall time of the query, blocking on EVERY device output —
    otherwise the clock measures dispatch, not the scan (jax is async)."""
    t0 = time.perf_counter()
    scores, idx = fn()
    jax.block_until_ready((scores, idx))
    dt = time.perf_counter() - t0
    return SearchResult(scores=np.asarray(scores), indices=np.asarray(idx),
                        latency_s=dt, stats=dict(stats or {}))


def _probed_sizes(queries: np.ndarray, centroids: np.ndarray,
                  cell_sizes: np.ndarray, nprobe: int) -> float:
    """Mean members the probe scan evaluates per query — the IVF
    ``distance_evals`` stat. Recomputes the nprobe-nearest cells on host
    (Q x C, negligible next to the scan itself) so the jitted search path
    stays untouched; the centroid scan is reported separately by callers
    as ``centroid_evals``."""
    q = np.asarray(queries, np.float32)
    c = np.asarray(centroids, np.float32)
    d2 = (np.sum(q * q, 1)[:, None] - 2.0 * q @ c.T
          + np.sum(c * c, 1)[None, :])
    p = min(nprobe, c.shape[0])
    cells = np.argpartition(d2, p - 1, axis=1)[:, :p]
    return float(cell_sizes[cells].sum(axis=1).mean())


# ---------------------------------------------------------------------------
# Flat (exact scan)
# ---------------------------------------------------------------------------
@register_index("flat")
class FlatIndex(VectorIndex):
    """Exact k-NN over the raw corpus via the sharded scan + global top-k
    merge. With a mesh in ``ctx`` the corpus row-shards over ``db_rows``."""

    _fp_exempt = {
        "ctx": "mesh/sharding topology changes where the scan runs, not "
               "what it answers",
    }

    def __init__(self, metric: str = "euclidean", ctx: MeshCtx = NULL_CTX):
        self.metric = metric
        self.ctx = ctx
        self._db: Optional[jax.Array] = None
        self._n = 0  # real rows; a mesh-sharded _db carries pad rows after

    @property
    def ntotal(self) -> int:
        return self._n

    @property
    def built(self) -> bool:
        return self._db is not None

    @property
    def bytes_per_vector(self) -> float:
        self._require_built()
        return float(self._db.shape[1] * self._db.dtype.itemsize)

    @property
    def dim(self) -> int:
        self._require_built()
        return int(self._db.shape[1])

    def _rows(self) -> np.ndarray:
        return np.asarray(self._db)[:self._n]

    def _fingerprint_state(self) -> list:
        return [self.metric, self._rows()]

    def build(self, corpus: np.ndarray) -> "FlatIndex":
        corpus = np.asarray(corpus, np.float32)
        self._db = ds.place_rows(corpus, self.ctx)
        self._n = int(corpus.shape[0])
        return self

    @functools.cached_property
    def _scan(self):
        return jax.jit(
            lambda q, db, alive, k, n: ds.search(q, db, k, self.ctx,
                                                 metric=self.metric,
                                                 alive=alive, n=n),
            static_argnames=("k", "n"))

    def add(self, vecs: np.ndarray) -> None:
        """Streaming insert: append rows to the scanned corpus. New rows
        are searchable immediately; existing rows keep their ids."""
        self._require_built()
        nv = np.asarray(vecs, np.float32)
        if self.ctx.mesh is None:
            self._db = jnp.concatenate([self._db, jnp.asarray(nv)], axis=0)
        else:  # re-place: the sharded slabs are padded to equal size
            self._db = ds.place_rows(np.concatenate([self._rows(), nv]),
                                     self.ctx)
        self._n += int(nv.shape[0])

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> SearchResult:
        del params  # exact scan has no knobs: every row is always scored
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        al = None if alive is None else jnp.asarray(np.asarray(alive, bool))
        return _timed(lambda: self._scan(q, self._db, al,
                                         k=min(k, self.ntotal), n=self._n),
                      stats={"distance_evals": float(self.ntotal)})

    def save(self, directory: str) -> None:
        self._require_built()
        _save_dir(directory, {"kind": self.kind, "metric": self.metric},
                  {"db": self._rows()})

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "FlatIndex":
        return cls(metric=meta["metric"]).build(_load_arrays(directory)["db"])


# ---------------------------------------------------------------------------
# IVF-Flat (coarse quantization)
# ---------------------------------------------------------------------------
@register_index("ivf_flat")
class IVFFlatIndex(VectorIndex):
    """k-means cells + padded-dense probe scan (``search.ivf``). Euclidean
    only (scores = negative squared distance). ``nprobe`` defaults to
    n_cells/16 (min 8): recall-friendly without scanning everything."""

    _fp_exempt = {
        "n_cells": "build-time hyperparam; materialized in the hashed "
                   "centroids/lists arrays",
        "cell_cap": "build-time hyperparam; materialized in the hashed "
                    "lists shape",
        "kmeans_iters": "build-time hyperparam; materialized in the "
                        "hashed centroids",
        "seed": "build-time hyperparam; materialized in the hashed "
                "centroids/lists",
        "_cell_sizes": "derived from _ivf.list_mask (hashed via lists); "
                       "feeds host-side stats only",
    }

    def __init__(self, n_cells: int = 256, nprobe: int = 0,
                 cell_cap: Optional[int] = None, kmeans_iters: int = 10,
                 seed: int = 0):
        self.n_cells = n_cells
        self.nprobe = nprobe or max(8, n_cells // 16)
        self.cell_cap = cell_cap
        self.kmeans_iters = kmeans_iters
        self.seed = seed
        self._ivf: Optional[ivf_lib.IVFIndex] = None
        self._cell_sizes: Optional[np.ndarray] = None  # fixed at build
        self._ntotal = 0

    @property
    def ntotal(self) -> int:
        return self._ntotal

    @property
    def built(self) -> bool:
        return self._ivf is not None

    @property
    def bytes_per_vector(self) -> float:
        """f32 list vector + int32 row id."""
        self._require_built()
        return float(self._ivf.list_vecs.shape[2] * 4 + 4)

    @property
    def dim(self) -> int:
        self._require_built()
        return int(self._ivf.centroids.shape[1])

    def _fingerprint_state(self) -> list:
        # list_vecs is what search actually scores against — centroids +
        # id lists alone could collide across corpora with equal means
        return [f"nprobe={self.nprobe}", self._ivf.centroids,
                self._ivf.lists, self._ivf.list_vecs]

    def build(self, corpus: np.ndarray) -> "IVFFlatIndex":
        corpus = jnp.asarray(corpus, jnp.float32)
        n_cells = min(self.n_cells, corpus.shape[0])
        self._ivf = ivf_lib.build(corpus, n_cells, cell_cap=self.cell_cap,
                                  kmeans_iters=self.kmeans_iters,
                                  seed=self.seed)
        self._cell_sizes = np.asarray(self._ivf.list_mask).sum(axis=1)
        self._ntotal = int(corpus.shape[0])
        return self

    def add(self, vecs: np.ndarray) -> None:
        """Streaming insert: assign each new row to its nearest centroid
        and append into that cell's padded list — the classic IVF append
        (centroids stay FIXED, so a drifting stream skews the cells;
        :meth:`cell_imbalance` exposes the skew and ``MutableIndex``
        re-clusters past its trigger). Touched cells are re-packed
        prefix-dense; list capacity grows when a cell fills."""
        self._require_built()
        nv = np.asarray(vecs, np.float32)
        cent = np.asarray(self._ivf.centroids, np.float32)
        d2 = (np.sum(nv * nv, 1)[:, None] - 2.0 * nv @ cent.T
              + np.sum(cent * cent, 1)[None, :])
        cells = np.argmin(d2, axis=1)
        lists = np.asarray(self._ivf.lists).copy()
        mask = np.asarray(self._ivf.list_mask).copy()
        lvecs = np.asarray(self._ivf.list_vecs).copy()
        need = mask.sum(axis=1)
        np.add.at(need, cells, 1)
        cap = lists.shape[1]
        new_cap = int(max(cap, need.max()))
        if new_cap > cap:
            pad = new_cap - cap
            lists = np.pad(lists, ((0, 0), (0, pad)), constant_values=-1)
            mask = np.pad(mask, ((0, 0), (0, pad)))
            lvecs = np.pad(lvecs, ((0, 0), (0, pad), (0, 0)))
        new_ids = np.arange(self._ntotal, self._ntotal + nv.shape[0],
                            dtype=lists.dtype)
        for c in np.unique(cells):
            sel = cells == c
            old = mask[c]
            ids = np.concatenate([lists[c][old], new_ids[sel]])
            vv = np.concatenate([lvecs[c][old], nv[sel]])
            lists[c] = -1
            mask[c] = False
            lvecs[c, : len(ids)] = vv
            lists[c, : len(ids)] = ids
            mask[c, : len(ids)] = True
        self._ivf = ivf_lib.IVFIndex(
            centroids=self._ivf.centroids, lists=jnp.asarray(lists),
            list_vecs=jnp.asarray(lvecs), list_mask=jnp.asarray(mask),
            spill=self._ivf.spill)
        self._cell_sizes = mask.sum(axis=1)
        self._ntotal += int(nv.shape[0])

    def cell_imbalance(self) -> float:
        """Largest cell over the mean cell size — 1.0 is perfectly
        balanced; appends against fixed centroids push it up, degrading
        probe selectivity (one probe scans the fat cell). The
        re-clustering trigger ``MutableIndex`` watches."""
        self._require_built()
        sizes = np.asarray(self._cell_sizes, np.float64)
        return float(sizes.max() / max(sizes.mean(), 1e-12))

    @functools.cached_property
    def _probe(self):
        """Jitted probe scan (static k/nprobe): one XLA call per search
        instead of an eager op-by-op trace — the q=1 serving path is
        dispatch-bound without this."""
        def fn(q, centroids, lists, list_vecs, list_mask, k, nprobe):
            idx = ivf_lib.IVFIndex(centroids=centroids, lists=lists,
                                   list_vecs=list_vecs, list_mask=list_mask,
                                   spill=0)
            return ivf_lib.search(idx, q, k, nprobe=nprobe)

        return jax.jit(fn, static_argnames=("k", "nprobe"))

    def set_params(self, params: SearchParams) -> None:
        """Adopt a tuned ``nprobe`` default. ``nprobe`` is fingerprint
        state, so the serving cache sees a new index identity."""
        if params.nprobe is not None:
            self.nprobe = params.nprobe

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> SearchResult:
        """Like FAISS, a query whose probed cells hold fewer than k members
        pads the tail with index -1 / score -inf. ``alive`` folds into the
        list mask (ids nulled too), so a tombstoned row can neither score
        nor surface — the probe scan's own signature is unchanged.

        ``params.nprobe`` overrides ``self.nprobe`` for this call; it is
        ladder-snapped (``SearchParams`` guarantees it), so repeated
        laddered calls reuse the same cached ``_probe`` jit entries —
        zero recompiles once a rung is warm."""
        self._require_built()
        q = jnp.asarray(queries, jnp.float32)
        nprobe = (self.nprobe if params is None or params.nprobe is None
                  else params.nprobe)
        nprobe = min(nprobe, int(self._ivf.centroids.shape[0]))
        k_req = min(k, self.ntotal)
        # the probe scan can surface at most nprobe * cell_cap rows
        k_eff = min(k_req, nprobe * int(self._ivf.lists.shape[1]))
        lists, mask = self._ivf.lists, self._ivf.list_mask
        if alive is not None:
            al = jnp.asarray(np.asarray(alive, bool))
            mask = mask & al[jnp.where(lists >= 0, lists, 0)]
            lists = jnp.where(mask, lists, -1)

        def run():
            v, i = self._probe(q, self._ivf.centroids, lists,
                               self._ivf.list_vecs, mask,
                               k=k_eff, nprobe=nprobe)
            return _pad_result(v, i, k_req)

        return _timed(run, stats={
            "distance_evals": _probed_sizes(queries, self._ivf.centroids,
                                            self._cell_sizes, nprobe),
            "centroid_evals": float(self._ivf.centroids.shape[0]),
        })

    def save(self, directory: str) -> None:
        self._require_built()
        meta = {"kind": self.kind, "n_cells": self.n_cells,
                "nprobe": self.nprobe, "kmeans_iters": self.kmeans_iters,
                "seed": self.seed, "ntotal": self._ntotal,
                "spill": int(self._ivf.spill)}
        _save_dir(directory, meta, {
            "centroids": np.asarray(self._ivf.centroids),
            "lists": np.asarray(self._ivf.lists),
            "list_vecs": np.asarray(self._ivf.list_vecs),
            "list_mask": np.asarray(self._ivf.list_mask),
        })

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "IVFFlatIndex":
        self = cls(n_cells=meta["n_cells"], nprobe=meta["nprobe"],
                   kmeans_iters=meta["kmeans_iters"], seed=meta["seed"])
        a = _load_arrays(directory)
        self._ivf = ivf_lib.IVFIndex(
            centroids=jnp.asarray(a["centroids"]),
            lists=jnp.asarray(a["lists"]),
            list_vecs=jnp.asarray(a["list_vecs"]),
            list_mask=jnp.asarray(a["list_mask"]),
            spill=int(meta.get("spill", 0)))
        self._cell_sizes = a["list_mask"].sum(axis=1)
        self._ntotal = int(meta["ntotal"])
        return self


# ---------------------------------------------------------------------------
# TwoStage: reducer -> base index -> full-space rerank
# ---------------------------------------------------------------------------
@register_index("two_stage")
class TwoStageIndex(VectorIndex):
    """Compose any reducer with any base index.

    ``build`` fits the reducer on the corpus (skipped if already fitted —
    pre-trained reducers plug straight in), encodes the corpus into R^m,
    and builds the base index over the REDUCED vectors. ``search`` encodes
    queries, fetches ``k * rerank_factor * base.stage1_oversample``
    candidates from the base index (quantized bases oversample: their
    candidate lists are cheap but their ordering is noisy), and reranks
    them with exact distances in the ORIGINAL space — so scores are
    full-space even when stage 1 is approximate twice over (reduced +
    IVF/PQ)."""

    def __init__(self, reducer: Reducer, base_index: VectorIndex,
                 rerank_factor: int = 4, metric: str = "euclidean",
                 rerank_k1: Optional[int] = None):
        self.reducer = reducer
        self.base = base_index
        self.rerank_factor = rerank_factor
        self.metric = metric
        # tuned absolute stage-1 budget; None = the classic
        # k * rerank_factor * stage1_oversample formula
        self.rerank_k1 = None if rerank_k1 is None else snap_knob(rerank_k1)
        self._db_full: Optional[jax.Array] = None

    @property
    def ntotal(self) -> int:
        return 0 if self._db_full is None else int(self._db_full.shape[0])

    @property
    def built(self) -> bool:
        return self._db_full is not None and self.base.built

    @property
    def bytes_per_vector(self) -> float:
        """Stage-1 payload only: the reduced/quantized structure is what
        lives on the accelerator; the full-space rerank store can stay in
        host RAM (the paper's deployment split)."""
        return self.base.bytes_per_vector

    @property
    def dim(self) -> int:
        """Queries arrive in the ORIGINAL space (the reducer encodes them)."""
        self._require_built()
        return int(self._db_full.shape[1])

    def _reducer_fingerprint(self) -> str:
        """Content hash of the query-time encoder. The reducer transforms
        every query before stage 1, so it is part of index identity:
        without it, two stacks differing only in reducer weights would
        collide in the serving cache. Reducers that implement
        ``fingerprint()`` (all built-ins) hash their fitted state;
        anything else is probed — hash its transform of a fixed input."""
        fp = getattr(self.reducer, "fingerprint", None)
        if fp is not None:
            return fp()
        probe = np.random.default_rng(0).standard_normal(
            (4, int(self._db_full.shape[1]))).astype(np.float32)
        z = np.asarray(self.reducer.transform(probe))
        return hashlib.sha1(z.tobytes()).hexdigest()[:16]

    def _fingerprint_state(self) -> list:
        return [f"rerank={self.rerank_factor}:{self.rerank_k1}:{self.metric}",
                f"reducer={self._reducer_fingerprint()}",
                self.base.fingerprint(), self._db_full]

    def build(self, corpus: np.ndarray) -> "TwoStageIndex":
        corpus = np.asarray(corpus, np.float32)
        # absent `fitted` means unknown -> fit (skipping would hand an
        # unfitted reducer to transform on the next line)
        if not getattr(self.reducer, "fitted", False):
            self.reducer.fit(corpus)
        reduced = self.reducer.transform(corpus)
        self.base.build(reduced)
        self._db_full = jnp.asarray(corpus)
        return self

    def add(self, vecs: np.ndarray) -> None:
        """Streaming insert: encode the new rows once, push them down the
        stack — incrementally when the base supports ``add`` (HNSW graph
        insert, IVF cell append, flat concat), else by rebuilding the
        base over the extended reduced corpus — and extend the full-space
        rerank store. The fitted reducer is NOT refit here: drift policy
        (when its Eq. 15 band breaks) belongs to ``MutableIndex``."""
        self._require_built()
        nv = np.asarray(vecs, np.float32)
        z = np.asarray(self.reducer.transform(nv))
        if hasattr(self.base, "add"):
            self.base.add(z)
        else:
            full = np.concatenate(
                [np.asarray(self._db_full, np.float32), nv])
            self.base.build(np.asarray(self.reducer.transform(full)))
        self._db_full = jnp.concatenate(
            [self._db_full, jnp.asarray(nv, jnp.float32)], axis=0)

    @functools.cached_property
    def _rerank(self):
        # the shared stage-2 engine (search.twostage.rerank_candidates):
        # in-jit candidate gather + exact distances, -1 pads from ANY
        # stage-1 tier (IVF probes, batched HNSW beam) pinned to -inf
        return jax.jit(
            functools.partial(ts_lib.rerank_candidates, metric=self.metric),
            static_argnames=("k",))

    def set_params(self, params: SearchParams) -> None:
        """Adopt a tuned stage-1 budget and forward the rest down the
        stack. ``rerank_k1`` is fingerprint state (as are the base's
        knobs), so a tuned point moves the composite fingerprint."""
        if params.rerank_k1 is not None:
            self.rerank_k1 = params.rerank_k1
        self.base.set_params(params)

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params: Optional[SearchParams] = None) -> SearchResult:
        self._require_built()
        t0 = time.perf_counter()
        zq = self.reducer.transform(np.asarray(queries, np.float32))
        k_eff = min(k, self.ntotal)
        # stage-1 candidate budget: an explicit (tuned / per-call) k1
        # beats the oversample formula; never below k_eff — the rerank
        # cannot return rows stage 1 did not fetch
        pk1 = (self.rerank_k1 if params is None or params.rerank_k1 is None
               else params.rerank_k1)
        if pk1 is not None:
            k1 = min(max(int(pk1), k_eff), self.ntotal)
        else:
            over = getattr(self.base, "stage1_oversample", 1)
            k1 = min(k_eff * self.rerank_factor * over, self.ntotal)
        # tombstones are enforced in stage 1: a deleted row never appears
        # even as a pre-rerank candidate, so the rerank can't resurface it
        stage1 = self.base.search(zq, k1, alive=alive, params=params)
        cand = jnp.asarray(stage1.indices)
        q = jnp.asarray(queries, jnp.float32)
        scores, idx = self._rerank(q, self._db_full, cand, k=k_eff)
        jax.block_until_ready((scores, idx))
        dt = time.perf_counter() - t0
        # total work per query: stage-1 reduced-space evals + the k1
        # full-space rerank distances
        s1_evals = stage1.stats.get("distance_evals", 0.0)
        stats = dict(stage1.stats)
        stats.update({"distance_evals": s1_evals + float(k1),
                      "stage1_distance_evals": s1_evals,
                      "rerank_evals": float(k1)})
        return SearchResult(scores=np.asarray(scores),
                            indices=np.asarray(idx), latency_s=dt,
                            stats=stats)

    def save(self, directory: str) -> None:
        self._require_built()
        _save_dir(directory, {"kind": self.kind,
                              "rerank_factor": self.rerank_factor,
                              "rerank_k1": self.rerank_k1,
                              "metric": self.metric},
                  {"db_full": np.asarray(self._db_full)})
        self.reducer.save(os.path.join(directory, "reducer"))
        self.base.save(os.path.join(directory, "base"))

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "TwoStageIndex":
        reducer = load_reducer(os.path.join(directory, "reducer"))
        base = load_index(os.path.join(directory, "base"))
        self = cls(reducer, base, rerank_factor=meta["rerank_factor"],
                   metric=meta["metric"], rerank_k1=meta.get("rerank_k1"))
        self._db_full = jnp.asarray(_load_arrays(directory)["db_full"])
        return self
