"""``ShardedIndex``: scatter-gather search over N disjoint shards.

The million-vector serving tier (ROADMAP "sharded serving"): the corpus is
partitioned across shards — contiguous row ranges (``partition="rows"``)
or k-means cell assignment (``partition="ivf"``), both via
``distributed.partitioning`` — and each shard is an independent child
:class:`VectorIndex` built from a factory spec (``"Flat"``, ``"IVF256"``,
``"IVF256,PQ8x8"``, ...). ``search`` fans the query batch out to every
shard, maps local hits to global row ids through the shard's row map, and
reduces the gathered ``[Q, k * S]`` candidates with the fused
``topk_merge`` kernel — ties broken by the smaller global id, so the
answer is **bitwise invariant to the shard count** (the contract
docs/sharded_serving.md pins and tests/test_sharded.py asserts).

Two execution modes:

* ``workers="threads"`` (default) — a thread pool searches the S children
  concurrently; each child's scan releases the GIL inside jax, so shards
  overlap even on small hosts. This is the scale-out shape: every shard
  is a self-contained index that could live in its own process.
* ``workers="mesh"`` — with a device mesh in ``ctx`` and flat children,
  the corpus row-shards over the mesh's "db_rows" axes and the (fixed)
  device-parallel scatter-gather in ``search.distributed`` does the
  fan-out + merge on-device (one all-gather of k*S scalars per query).

Composes with the rest of the factory grammar: ``"RAE64,Shard8,IVF256,
Rerank4"`` = reduce once, shard the reduced corpus 8 ways into IVF
children, rerank merged candidates in the full space. ``fingerprint()``
composes over the child fingerprints + row maps, so the serving cache
invalidates when any shard changes.
"""
from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..distributed.partitioning import partition_ivf_cells, partition_rows
from ..kernels.common import PAD_ID
from ..kernels.topk_merge.ops import topk_merge
from ..models.common import NULL_CTX, MeshCtx
from .index import (SearchResult, VectorIndex, _load_arrays, _save_dir,
                    register_index)


@register_index("sharded")
class ShardedIndex(VectorIndex):
    """Partition the corpus across ``n_shards`` child indexes and merge
    per-shard top-k with the deterministic scatter-gather kernel."""

    _fp_exempt = {
        "ctx": "mesh/sharding topology changes where the scan runs, not "
               "what it answers",
        "workers": "thread-pool vs device-mesh fan-out; both produce the "
                   "bitwise-identical merge (shard-count-invariance "
                   "contract) and the built children/row maps are hashed",
        "n_workers": "thread-pool width; execution parallelism only",
        "n_cells": "build-time partitioning hyperparam; materialized in "
                   "the hashed row maps",
        "seed": "build-time partitioning hyperparam; materialized in the "
                "hashed row maps",
        "index_kw": "child constructor knobs; materialized in the hashed "
                    "child fingerprints",
        "_dim": "derived from the built children (hashed via their "
                "fingerprints); cached for the dim property",
    }

    def __init__(self, n_shards: int = 2, child_spec: str = "Flat",
                 partition: str = "rows", metric: str = "euclidean",
                 ctx: MeshCtx = NULL_CTX, workers: str = "threads",
                 n_workers: int = 0, n_cells: int = 0, seed: int = 0,
                 index_kw: Optional[dict[str, Any]] = None):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if partition not in ("rows", "ivf"):
            raise ValueError(f"unknown partition {partition!r} "
                             "(rows | ivf)")
        if workers not in ("threads", "mesh"):
            raise ValueError(f"unknown workers {workers!r} (threads | mesh)")
        self.n_shards = n_shards
        self.child_spec = child_spec
        self.partition = partition
        self.metric = metric
        self.ctx = ctx
        self.workers = workers
        self.n_workers = n_workers
        self.n_cells = n_cells
        self.seed = seed
        self.index_kw = dict(index_kw or {})
        self._shards: list[VectorIndex] = []
        self._row_maps: list[np.ndarray] = []
        self._ntotal = 0
        self._dim = 0

    # -- identity ----------------------------------------------------------
    @property
    def ntotal(self) -> int:
        return self._ntotal

    @property
    def built(self) -> bool:
        return bool(self._shards)

    @property
    def shard_count(self) -> int:
        """Shards actually built (<= n_shards: empty partitions collapse)."""
        return len(self._shards)

    @property
    def bytes_per_vector(self) -> float:
        self._require_built()
        return max(c.bytes_per_vector for c in self._shards)

    @property
    def bytes_per_shard(self) -> float:
        """Largest per-shard payload — the number that must fit one
        worker/device, the memory axis the sharded bench budgets."""
        self._require_built()
        return max(c.ntotal * c.bytes_per_vector for c in self._shards)

    @property
    def dim(self) -> int:
        self._require_built()
        return self._dim

    @property
    def stage1_oversample(self) -> int:
        """Under a rerank, inherit the children's oversample (PQ children
        have noisy ordering; the merge preserves, not fixes, that)."""
        if not self._shards:
            return 1
        return max(getattr(c, "stage1_oversample", 1) for c in self._shards)

    def _fingerprint_state(self) -> list:
        state = [f"shards={self.n_shards}:{self.partition}:"
                 f"{self.child_spec}:{self.metric}"]
        for child in self._shards:
            state.append(child.fingerprint())
        for rows in self._row_maps:
            state.append(rows)
        return state

    # -- build -------------------------------------------------------------
    def _make_child(self) -> VectorIndex:
        from .factory import index_factory, parse_index_spec  # cycle: lazy

        parsed = parse_index_spec(self.child_spec)
        if parsed.reducer or parsed.shards or parsed.rerank_factor > 1:
            raise ValueError(
                f"child_spec {self.child_spec!r} must be a storage stack "
                "(base [, quant]); reducers/Shard/Rerank wrap the sharded "
                "index, not its children")
        return index_factory(self.child_spec, metric=self.metric,
                             index_kw=dict(self.index_kw))

    def build(self, corpus: np.ndarray) -> "ShardedIndex":
        corpus = np.asarray(corpus, np.float32)
        n = int(corpus.shape[0])
        if self.workers == "mesh":
            return self._build_mesh(corpus)
        if self.partition == "rows":
            parts = partition_rows(n, self.n_shards)
        else:
            parts = partition_ivf_cells(corpus, self.n_shards,
                                        n_cells=self.n_cells,
                                        seed=self.seed)
        parts = [p for p in parts if len(p)]  # empty shards answer nothing
        self._shards = []
        self._row_maps = []
        for rows in parts:
            self._shards.append(self._make_child().build(corpus[rows]))
            self._row_maps.append(np.asarray(rows, np.int32))
        self._ntotal = n
        self._dim = int(corpus.shape[1])
        return self

    def _build_mesh(self, corpus: np.ndarray) -> "ShardedIndex":
        """Device-parallel mode: one flat child over the whole corpus with
        the mesh ctx — ``search.distributed`` row-shards it over "db_rows"
        and runs the on-device scatter-gather (same merge kernel, same
        tie-break, so the invariance contract holds across modes)."""
        from .index import FlatIndex

        if self.ctx.mesh is None:
            raise ValueError("workers='mesh' needs a device mesh in ctx")
        from .factory import parse_index_spec  # cycle: lazy

        parsed = parse_index_spec(self.child_spec)
        if parsed.base != "flat" or parsed.quant is not None:
            raise ValueError("workers='mesh' supports flat children only "
                             f"(got {self.child_spec!r}); use threads for "
                             "IVF/quantized shards")
        if self.partition != "rows":
            raise ValueError("workers='mesh' implies contiguous row "
                             "partitioning (the mesh's db_rows sharding)")
        child = FlatIndex(metric=self.metric, ctx=self.ctx).build(corpus)
        self._shards = [child]
        self._row_maps = [np.arange(corpus.shape[0], dtype=np.int32)]
        self._ntotal = int(corpus.shape[0])
        self._dim = int(corpus.shape[1])
        return self

    # -- search ------------------------------------------------------------
    @functools.cached_property
    def _pool(self) -> ThreadPoolExecutor:
        return ThreadPoolExecutor(
            max_workers=self.n_workers or max(1, len(self._shards)),
            thread_name_prefix="shard")

    def set_params(self, params) -> None:
        """Broadcast a tuned operating point to every shard — the children
        hold the knobs (and hash them), so the composed fingerprint moves
        through the child-fingerprint chain."""
        self._require_built()
        for child in self._shards:
            child.set_params(params)

    def search(self, queries: np.ndarray, k: int,
               alive: Optional[np.ndarray] = None,
               params=None) -> SearchResult:
        self._require_built()
        t0 = time.perf_counter()
        q = np.asarray(queries, np.float32)
        k_req = min(k, self.ntotal)
        n_sh = len(self._shards)
        # tombstones slice per shard through the row map: each child sees
        # only ITS rows' alive bits, in its local row order
        al = None if alive is None else np.asarray(alive, bool)
        child_alive = [None if al is None else al[rows]
                       for rows in self._row_maps]
        with TraceAnnotation("sharded.scan"):
            if n_sh == 1:
                results = [self._shards[0].search(
                    q, min(k_req, self._shards[0].ntotal),
                    alive=child_alive[0], params=params)]
            else:
                futs = [self._pool.submit(self._shards[s].search, q,
                                          min(k_req, self._shards[s].ntotal),
                                          alive=child_alive[s], params=params)
                        for s in range(n_sh)]
                results = [f.result() for f in futs]
        with TraceAnnotation("sharded.merge"):
            vals = np.concatenate(
                [np.asarray(r.scores, np.float32) for r in results], axis=1)
            local = np.concatenate(
                [np.asarray(r.indices, np.int64) for r in results], axis=1)
            # local -> global ids shard by shard; -1 pads stay -1
            gids = np.empty_like(local, dtype=np.int32)
            off = 0
            for rows, r in zip(self._row_maps, results):
                w = r.indices.shape[1]
                blk = local[:, off:off + w]
                gids[:, off:off + w] = np.where(
                    blk >= 0, rows[np.clip(blk, 0, len(rows) - 1)], PAD_ID)
                off += w
            v, i = topk_merge(jnp.asarray(vals), jnp.asarray(gids), k_req)
            jax.block_until_ready((v, i))
        dt = time.perf_counter() - t0
        scores = np.array(v)  # copy: jax buffers are read-only views
        idx = np.asarray(i)
        scores[idx < 0] = -np.inf  # API layer speaks the FAISS pad dialect
        stats = {"distance_evals": float(sum(
            r.stats.get("distance_evals", 0.0) for r in results)),
            "shards": float(n_sh)}
        return SearchResult(scores=scores, indices=idx, latency_s=dt,
                            stats=stats)

    # -- persistence -------------------------------------------------------
    def save(self, directory: str) -> None:
        self._require_built()
        meta = {"kind": self.kind, "n_shards": self.n_shards,
                "partition": self.partition, "child_spec": self.child_spec,
                "metric": self.metric, "ntotal": self._ntotal,
                "dim": self._dim, "built_shards": len(self._shards)}
        _save_dir(directory, meta,
                  {f"rows{i}": rows
                   for i, rows in enumerate(self._row_maps)})
        for i, child in enumerate(self._shards):
            child.save(os.path.join(directory, f"shard{i}"))

    @classmethod
    def _load(cls, directory: str, meta: dict[str, Any]) -> "ShardedIndex":
        from .index import load_index  # sibling import kept local for clarity

        self = cls(n_shards=meta["n_shards"], partition=meta["partition"],
                   child_spec=meta["child_spec"], metric=meta["metric"])
        arrays = _load_arrays(directory)
        n_built = int(meta["built_shards"])
        self._row_maps = [np.asarray(arrays[f"rows{i}"], np.int32)
                          for i in range(n_built)]
        self._shards = [load_index(os.path.join(directory, f"shard{i}"))
                        for i in range(n_built)]
        self._ntotal = int(meta["ntotal"])
        self._dim = int(meta["dim"])
        return self
