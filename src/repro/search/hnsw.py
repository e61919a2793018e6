"""HNSW graph search (Malkov & Yashunin 2016) — the sublinear search tier.

Layered navigable-small-world graph: each node draws a top layer from the
geometric distribution ``floor(-ln(U) / ln(M))``; insert runs an
``ef_construction``-bounded beam per layer and connects to at most ``M``
neighbors chosen by the pruning heuristic (Alg. 4: a candidate joins only
if it is closer to the query than to every already-selected neighbor,
which keeps edges spread across directions instead of clustering). Degrees
are capped at ``M`` on upper layers and ``2M`` at layer 0; when a cap
overflows, the overfull list is re-pruned with the same heuristic and the
dropped back-links are removed, so links stay bidirectional (unlike
hnswlib, which leaves asymmetric edges after a shrink — symmetric graphs
are what the invariant suite checks, and pruned slots are refilled with
the nearest rejected candidates to protect connectivity).

Search greedy-descends from the entry point through the upper layers
(ef=1) and runs the ef-bounded best-first beam at layer 0. Two traversal
engines share those semantics:

* :func:`search` — the sequential reference: per-query pointer-chasing on
  host (numpy + heapq), one ``candidate_distances`` call per hop.
* :func:`search_batched` — the array-native serving path: a batched
  frontier loop over the :meth:`HNSWGraph.pack`-ed dense adjacency. Per
  hop it pops the best unexpanded beam entry of EVERY live query at once,
  gathers their neighbor rows, masks visited/pad slots with per-query
  visited stamps, and scores + beam-merges all (query, neighbor) pairs in
  ONE dispatch through the fused ``graph_beam`` kernel triple (Pallas
  gather+L2+merge on TPU, vectorized numpy off-TPU). Expansion order per
  query is identical to the heapq beam — best-first until no in-beam
  candidate is unexpanded — so recall at equal ``ef_search`` matches and
  visited counts agree up to boundary ties (tested within 10%); results
  are bitwise-deterministic and row-independent (a query answers the same
  at q=1 and inside any batch, which the serving cache relies on).

The batched engine optionally traverses *quantized* payloads: attach a
:class:`GraphCodes` (SQ8 or PQ codes trained by :func:`make_graph_codes`)
as ``graph.codec`` and every hop gathers code rows instead of f32 vectors,
scoring via dequant-free asymmetric L2 / a per-query ADC LUT through the
``graph_beam_q`` kernel triple — a 4–20x cut in gather bytes per hop, with
the exact ``Rerank`` stage above recovering full-precision ordering.

Every distance evaluation is counted — both engines return per-query eval
totals, the sublinearity axis the benchmarks report next to recall.

Composes with the paper's RAE exactly like IVF: build the graph over the
*reduced* corpus and rerank in R^n, so beam search pays O(m) per hop
instead of O(n).
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

_MAX_LEVEL = 15


def batched_driver(impl: str = "auto") -> str:
    """The :func:`search_batched` driver ``impl`` names: ``"auto"`` is the
    Pallas-hop traversal (``"fused"``) on TPU and the host loop (``"np"``)
    elsewhere."""
    if impl != "auto":
        return impl
    import jax

    return "fused" if jax.default_backend() == "tpu" else "np"


def candidate_distances(q: np.ndarray, vecs: np.ndarray) -> np.ndarray:
    """Squared L2 from one query [d] to a candidate batch [c, d], on the
    host. Build, insert and the sequential engine call this once per beam
    step with a few dozen candidates; a device dispatch per call (with a
    compile per distinct candidate count) costs far more than the
    arithmetic, so these loops score host arrays on every backend."""
    diff = vecs - q
    return np.einsum("cd,cd->c", diff, diff)


class _Evals:
    """Mutable distance-evaluation counter threaded through the traversal."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


@dataclass
class PackedHNSW:
    """Traversal-ready compilation of an :class:`HNSWGraph`: C-contiguous
    int32 neighbor tables (the batched frontier loop fancy-indexes whole
    rows of them every hop) plus the per-node squared norms the fused
    ``2 q.v - ||v||^2 - ||q||^2`` scoring form needs — computed once here
    instead of once per search. Built by :meth:`HNSWGraph.pack` and
    persisted alongside the graph so a reloaded index serves the batched
    path without repacking. ``device_arrays`` lazily uploads (and caches)
    the jax-side copies the jitted traversal closes over."""

    nbrs0: np.ndarray    # [N, 2M] int32, -1 = pad (layer 0)
    upper: np.ndarray    # [L, N, M] int32 (layers 1..L)
    vecs_sq: np.ndarray  # [N] float32: ||vecs||^2 per node
    _dev: Optional[tuple] = field(default=None, repr=False, compare=False)

    def device_arrays(self, vecs: np.ndarray) -> tuple:
        """(vecs, vecs_sq, nbrs0, upper) as device arrays, uploaded once."""
        if self._dev is None:
            import jax.numpy as jnp

            self._dev = (jnp.asarray(vecs), jnp.asarray(self.vecs_sq),
                         jnp.asarray(self.nbrs0), jnp.asarray(self.upper))
        return self._dev


@dataclass
class GraphCodes:
    """Quantized traversal payload riding alongside the packed graph:
    per-node SQ8 or PQ codes plus the codec state needed to score them.
    When attached (:func:`make_graph_codes` / ``HNSWGraph.codec``), every
    batched driver's hop gathers *codes* instead of f32 rows — at d=64
    that is 68 bytes per gathered neighbor for SQ8 and 12 for PQ8x8
    versus 260 for the f32 row+norm, which is the bandwidth the graph
    tier pays per hop at scale. Scores stay comparable across the whole
    traversal (entry seed, greedy descent, layer-0 beam all score codes),
    and the exact ``Rerank`` stage on top recovers full-precision
    ordering. Codecs live in :mod:`repro.search.quantize`; this class
    only carries their trained state and builds the per-query hop
    operands (see ``kernels/graph_beam_q`` for the unified affine score
    form)."""

    kind: str                 # "sq8" | "pq"
    codes: np.ndarray         # [N, C] uint8 (sq8: C = d; pq: C = m)
    node_bias: np.ndarray     # [N] f32 (sq8: ||decode(c)||^2; pq: zeros)
    vmin: Optional[np.ndarray] = None        # sq8 [d] f32
    step: Optional[np.ndarray] = None        # sq8 [d] f32
    codebooks: Optional[np.ndarray] = None   # pq [m, ksub, dsub] f32
    _dev: Optional[tuple] = field(default=None, repr=False, compare=False)

    @property
    def ksub(self) -> int:
        """LUT stride (pq codebook width; may be < 2**bits on tiny
        corpora — the actual trained width, never the nominal one)."""
        return 0 if self.codebooks is None else int(self.codebooks.shape[1])

    @property
    def gather_bytes(self) -> int:
        """Bytes the hop streams per gathered neighbor: the uint8 code
        row plus its f32 bias term. The f32 hop's equivalent is
        ``4 d + 4`` (row + norm) — the ratio is the tier's bandwidth
        win, reported by the benches as traversal gather bytes/hop."""
        return int(self.codes.shape[1]) + 4

    def query_operands(self, q: np.ndarray, q_sq: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Per-query hop operands ``(q_op [Q, Dop], q_bias [Q])`` (numpy,
        hoisted once per search batch). SQ8: the dequant-free asymmetric
        L2 rearrangement (``q_op = 2 q * step``, ``q_bias = 2 q.vmin -
        ||q||^2``) so the hop scores ``-||q - decode(c)||^2``. PQ: the
        NEGATED flattened ADC LUT, zero bias, so the hop scores
        ``-ADC distance``.

        Every reduction here is per-row (elementwise products + axis
        sums, plain un-optimized einsum) on purpose: a BLAS matvec or
        XLA dot picks its blocking from the BATCH shape, so row i's
        operand would differ in the last ulp between a solo and a
        coalesced dispatch — breaking the serving cache's bitwise
        row-independence contract."""
        if self.kind == "sq8":
            q_op = (2.0 * q * self.step[None, :]).astype(np.float32)
            q_bias = (2.0 * (q * self.vmin[None, :]).sum(axis=1)
                      - q_sq).astype(np.float32)
            return q_op, q_bias
        # the same expanded LUT algebra as quantize.adc_lut (which is
        # jnp, hence batch-blocked — see docstring), term for term
        cb = np.asarray(self.codebooks, np.float32)
        m, ksub, dsub = cb.shape
        qs = q.reshape(q.shape[0], m, dsub)
        lut = ((qs * qs).sum(-1)[:, :, None]
               - 2.0 * np.einsum("qms,mjs->qmj", qs, cb)
               + (cb * cb).sum(-1)[None, :, :]).astype(np.float32)
        return -lut.reshape(q.shape[0], -1), np.zeros(q.shape[0],
                                                      np.float32)

    def device_arrays(self) -> tuple:
        """(codes int32, node_bias, c0, c1) as device arrays, uploaded
        once; c0/c1 = (vmin, step) for sq8, (codebooks, None) for pq.
        Codes are widened to int32 here rather than per dispatch (TPU
        tiling — same convention as ``pq_adc``'s ops layer)."""
        if self._dev is None:
            import jax.numpy as jnp

            if self.kind == "sq8":
                c0, c1 = jnp.asarray(self.vmin), jnp.asarray(self.step)
            else:
                c0, c1 = jnp.asarray(self.codebooks), None
            self._dev = (jnp.asarray(self.codes.astype(np.int32)),
                         jnp.asarray(self.node_bias, jnp.float32), c0, c1)
        return self._dev


def make_graph_codes(vecs: np.ndarray, kind: str, m: int = 8, bits: int = 8,
                     iters: int = 15, seed: int = 0) -> GraphCodes:
    """Train a quantized traversal payload over the (already reduced)
    corpus the graph was built on. ``kind`` = "sq8" | "pq"; ``m``/
    ``bits``/``iters``/``seed`` are the PQ training knobs (ignored for
    SQ8). Attach the result as ``graph.codec`` — the f32 vectors stay
    (build, the sequential engine, and connectivity repair still use
    them); the payload changes what the *batched hop gather* reads."""
    from . import quantize as qz

    v = np.asarray(vecs, np.float32)
    if kind == "sq8":
        sq = qz.sq8_train(v)
        codes = np.asarray(qz.sq8_encode(sq, v))
        nb = np.asarray(qz.sq8_recon_sq_norms(sq, codes), np.float32)
        return GraphCodes(kind="sq8", codes=codes, node_bias=nb,
                          vmin=np.asarray(sq.vmin, np.float32),
                          step=np.asarray(sq.step, np.float32))
    if kind != "pq":
        raise ValueError(f"graph codec kind must be 'sq8' or 'pq', "
                         f"got {kind!r}")
    pq = qz.pq_train(v, m, bits=bits, iters=iters, seed=seed)
    codes = np.asarray(qz.pq_encode(pq, v))
    return GraphCodes(kind="pq", codes=codes,
                      node_bias=np.zeros(v.shape[0], np.float32),
                      codebooks=np.asarray(pq.codebooks, np.float32))


@dataclass
class HNSWGraph:
    """Padded-dense adjacency: ``links0`` [N, 2M] is layer 0, ``links``
    [L, N, M] are layers 1..L (-1 = empty slot; rows of nodes absent from
    a layer are all -1). ``codec``, when set, makes every batched driver
    score quantized code payloads instead of f32 rows (see
    :class:`GraphCodes`); the sequential engine always scores f32."""

    vecs: np.ndarray     # [N, d] float32
    levels: np.ndarray   # [N] int32: top layer of each node
    links0: np.ndarray   # [N, 2M] int32
    links: np.ndarray    # [L, N, M] int32
    entry: int
    M: int
    packed: Optional[PackedHNSW] = field(default=None, repr=False,
                                         compare=False)
    codec: Optional[GraphCodes] = field(default=None, repr=False,
                                        compare=False)

    @property
    def ntotal(self) -> int:
        return int(self.vecs.shape[0])

    @property
    def max_level(self) -> int:
        return int(self.levels[self.entry])

    def adjacency(self, layer: int) -> np.ndarray:
        return self.links0 if layer == 0 else self.links[layer - 1]

    def pack(self) -> PackedHNSW:
        """Compile (and cache) the packed traversal form. Idempotent; a
        graph mutated after packing must null ``packed`` itself."""
        if self.packed is None:
            self.packed = PackedHNSW(
                nbrs0=np.ascontiguousarray(self.links0, np.int32),
                upper=np.ascontiguousarray(self.links, np.int32),
                vecs_sq=np.einsum("nd,nd->n", self.vecs,
                                  self.vecs).astype(np.float32))
        return self.packed


def sample_levels(n: int, M: int, seed: int) -> np.ndarray:
    """Geometric level draw: floor(-ln(U) * mL) with mL = 1/ln(M)."""
    rng = np.random.default_rng(seed)
    u = rng.uniform(np.finfo(np.float64).tiny, 1.0, size=n)
    lv = np.floor(-np.log(u) / np.log(max(M, 2))).astype(np.int32)
    return np.minimum(lv, _MAX_LEVEL)


def _greedy_descent(vecs, adj, q, cur, d_cur, evals, alive=None):
    """ef=1 layer traversal: hop to the closest neighbor until no
    neighbor improves. ``alive`` (bool [N]) hides tombstoned nodes: a
    dead neighbor is never hopped to (``alive=None`` = all alive)."""
    while True:
        nbrs = adj[cur]
        nbrs = nbrs[nbrs >= 0]
        if alive is not None and nbrs.size:
            nbrs = nbrs[alive[nbrs]]
        if nbrs.size == 0:
            return cur, d_cur
        ds = candidate_distances(q, vecs[nbrs])
        evals.n += int(nbrs.size)
        j = int(np.argmin(ds))
        if ds[j] >= d_cur:
            return cur, d_cur
        cur, d_cur = int(nbrs[j]), float(ds[j])


def _search_layer(vecs, adj, q, eps, ef, visited, stamp, evals, alive=None):
    """Best-first beam (Alg. 2): returns the ef closest visited nodes as a
    sorted [(dist, node), ...] list. ``eps`` are (dist, node) entry points
    (already counted); ``visited``/``stamp`` implement an O(1)-reset
    visited set shared across calls. ``alive`` (bool [N]) hides
    tombstoned nodes: a dead neighbor never enters the beam, so it can
    never surface in a result (``alive=None`` = all alive)."""
    cand: list[tuple[float, int]] = []   # min-heap on distance
    res: list[tuple[float, int]] = []    # max-heap via negated distance
    for d, e in eps:
        visited[e] = stamp
        heapq.heappush(cand, (d, e))
        heapq.heappush(res, (-d, e))
    while cand:
        d, c = heapq.heappop(cand)
        if d > -res[0][0] and len(res) >= ef:
            break
        nbrs = adj[c]
        nbrs = nbrs[nbrs >= 0]
        if alive is not None and nbrs.size:
            nbrs = nbrs[alive[nbrs]]
        fresh = nbrs[visited[nbrs] != stamp]
        if fresh.size == 0:
            continue
        visited[fresh] = stamp
        ds = candidate_distances(q, vecs[fresh])
        evals.n += int(fresh.size)
        worst = -res[0][0]
        full = len(res) >= ef
        for dj, nj in zip(ds.tolist(), fresh.tolist()):
            if not full or dj < worst:
                heapq.heappush(cand, (dj, nj))
                heapq.heappush(res, (-dj, nj))
                if len(res) > ef:
                    heapq.heappop(res)
                worst = -res[0][0]
                full = len(res) >= ef
    return sorted((-nd, node) for nd, node in res)


def _select_heuristic(cands, vecs, m, evals, keep_pruned=False):
    """Alg. 4 neighbor selection: scan candidates nearest-first, keep one
    only if it is closer to the query than to every kept neighbor. With
    ``keep_pruned`` the remaining slots are refilled nearest-first (used
    on cap overflow, where dropping to << m edges risks disconnection)."""
    sel: list[int] = []
    sel_vecs: list[np.ndarray] = []
    pruned: list[int] = []
    for d_c, c in cands:
        if len(sel) >= m:
            break
        if sel:
            ds = candidate_distances(vecs[c], np.stack(sel_vecs))
            evals.n += len(sel)
            if not np.all(d_c < ds):
                pruned.append(c)
                continue
        sel.append(c)
        sel_vecs.append(vecs[c])
    if keep_pruned:
        sel.extend(pruned[: m - len(sel)])
    return sel


def _bfs_layer0(links0: np.ndarray, entry: int) -> np.ndarray:
    """Boolean reachability mask of the layer-0 graph from ``entry``."""
    seen = np.zeros(links0.shape[0], bool)
    seen[entry] = True
    stack = [entry]
    while stack:
        c = stack.pop()
        for t in links0[c][links0[c] >= 0].tolist():
            if not seen[t]:
                seen[t] = True
                stack.append(t)
    return seen


def _evict_farthest(links0, vecs, node, evals) -> None:
    """Free one slot in a full row by dropping its farthest link (both
    directions, keeping the graph symmetric)."""
    nbrs = links0[node][links0[node] >= 0]
    ds = candidate_distances(vecs[node], vecs[nbrs])
    evals.n += int(nbrs.size)
    t = int(nbrs[np.argmax(ds)])
    links0[t][links0[t] == node] = -1
    links0[node][links0[node] == t] = -1


def _repair_connectivity(vecs, links0, entry, evals) -> int:
    """Symmetric pruning can (rarely) strand a node at layer 0: every
    neighbor that once pointed at it overflowed and evicted it. Stitch each
    stranded component back via its nearest reachable node — an evictee
    keeps its other edges, so the loop makes monotone progress and the
    layer-0 reachability invariant holds unconditionally."""
    stitched = 0
    for _ in range(links0.shape[0]):
        seen = _bfs_layer0(links0, entry)
        miss = np.flatnonzero(~seen)
        if miss.size == 0:
            return stitched
        u = int(miss[0])
        reach = np.flatnonzero(seen)
        ds = candidate_distances(vecs[u], vecs[reach])
        evals.n += int(reach.size)
        r = int(reach[np.argmin(ds)])
        for node in (u, r):
            if not np.any(links0[node] < 0):
                _evict_farthest(links0, vecs, node, evals)
        links0[u][np.flatnonzero(links0[u] < 0)[0]] = r
        links0[r][np.flatnonzero(links0[r] < 0)[0]] = u
        stitched += 1
    return stitched


def _write_row(adj, node, nbrs):
    row = adj[node]
    row[: len(nbrs)] = nbrs
    row[len(nbrs):] = -1


def _insert_node(vecs, levels, links0, links, M, m0, top, i, entry,
                 ef_construction, visited, evals) -> int:
    """Insert node ``i`` (Alg. 1 body, shared verbatim between
    :func:`build` and :func:`insert_batch`): greedy-descend the upper
    layers, beam + heuristic-select per layer, write bidirectional links
    with overflow re-pruning. Returns the (possibly updated) entry."""
    q = vecs[i]
    l_i = int(levels[i])
    l_ep = int(levels[entry])
    cur = entry
    d_cur = float(candidate_distances(q, vecs[entry][None])[0])
    evals.n += 1
    for layer in range(l_ep, l_i, -1):
        cur, d_cur = _greedy_descent(vecs, links[layer - 1], q, cur,
                                     d_cur, evals)
    eps = [(d_cur, cur)]
    for layer in range(min(l_ep, l_i), -1, -1):
        adj = links0 if layer == 0 else links[layer - 1]
        cap = m0 if layer == 0 else M
        found = _search_layer(vecs, adj, q, eps, ef_construction,
                              visited, i * (top + 1) + layer, evals)
        sel = _select_heuristic(found, vecs, M, evals)
        _write_row(adj, i, sel)
        # bidirectional: add the back-link, re-pruning on overflow and
        # dropping the reverse edge of anything the prune evicts
        for s in sel:
            row = adj[s]
            free = np.flatnonzero(row < 0)  # prune leaves holes anywhere
            if free.size:
                row[free[0]] = i
                continue
            nbrs = row[row >= 0]
            ds = candidate_distances(vecs[s], vecs[nbrs])
            evals.n += int(nbrs.size)
            d_i = float(candidate_distances(vecs[s], q[None])[0])
            evals.n += 1
            merged = sorted([*zip(ds.tolist(), nbrs.tolist()),
                             (d_i, i)])
            kept = _select_heuristic(merged, vecs, cap, evals,
                                     keep_pruned=True)
            for t in nbrs:
                if t not in kept:
                    trow = adj[t]
                    trow[trow == s] = -1
            if i not in kept and len(kept) < cap:
                kept.append(i)  # never orphan the node being inserted
            elif i not in kept:
                irow = adj[i]
                irow[irow == s] = -1
            _write_row(adj, s, kept)
        eps = found
    if l_i > int(levels[entry]):
        entry = i
    return entry


def _compact_pads(links0, links) -> None:
    """Compact pad slots left of real links (prune leaves holes).
    Row-local stable argsort: a row with no holes is bitwise untouched."""
    for adj in (links0, *links):
        order = np.argsort(adj < 0, axis=1, kind="stable")
        adj[:] = np.take_along_axis(adj, order, axis=1)


def build(corpus: np.ndarray, M: int = 32, ef_construction: int = 100,
          seed: int = 0) -> HNSWGraph:
    """Sequential heuristic insert of every corpus row (Alg. 1)."""
    vecs = np.ascontiguousarray(np.asarray(corpus, np.float32))
    n = vecs.shape[0]
    if n == 0:
        raise ValueError("empty corpus")
    m0 = 2 * M
    levels = sample_levels(n, M, seed)
    top = int(levels.max())
    links0 = np.full((n, m0), -1, np.int32)
    links = np.full((top, n, M), -1, np.int32)
    visited = np.full(n, -1, np.int64)
    # the traversal helpers are shared with search(), where the caller
    # consumes the count; at build time it only feeds the helpers
    evals = _Evals()
    entry = 0
    for i in range(1, n):
        entry = _insert_node(vecs, levels, links0, links, M, m0, top, i,
                             entry, ef_construction, visited, evals)
    _repair_connectivity(vecs, links0, entry, evals)
    _compact_pads(links0, links)
    return HNSWGraph(vecs=vecs, levels=levels, links0=links0, links=links,
                     entry=entry, M=M)


def insert_batch(graph: HNSWGraph, new_vecs: np.ndarray,
                 ef_construction: int = 100, seed: int = 0) -> np.ndarray:
    """Incremental insert: append ``new_vecs`` rows to a built graph with
    the SAME per-node machinery as :func:`build` (greedy descent, beam,
    heuristic selection, bidirectional overflow re-pruning), in place.

    Levels for the new nodes are drawn deterministically keyed on
    ``(seed, current size)``, so the same stream of insert batches always
    produces the same graph. New upper layers are allocated when a new
    node out-draws the current top. The packed traversal cache is nulled
    (the :meth:`HNSWGraph.pack` mutation contract) — callers re-pack
    (typically in the background) before the next batched search; the
    re-pack is bitwise-neutral for rows whose adjacency the insert did
    not touch. A :class:`GraphCodes` payload, when attached, is extended
    with codes for the new rows using the already-trained codec (no
    retrain — codec drift is the reducer-drift story, handled above).

    Returns the global ids of the inserted rows.
    """
    nv = np.ascontiguousarray(np.asarray(new_vecs, np.float32))
    b = nv.shape[0]
    if nv.ndim != 2 or (b and nv.shape[1] != graph.vecs.shape[1]):
        raise ValueError(f"insert_batch: expected [b, {graph.vecs.shape[1]}]"
                         f" vectors, got {nv.shape}")
    if b == 0:
        return np.zeros(0, np.int64)
    n0 = graph.ntotal
    M, m0 = graph.M, 2 * graph.M
    new_levels = sample_levels(b, M, seed + n0)
    vecs = np.ascontiguousarray(np.concatenate([graph.vecs, nv], axis=0))
    levels = np.concatenate([graph.levels, new_levels])
    top_old = graph.links.shape[0]
    top = max(top_old, int(new_levels.max()))
    links0 = np.concatenate(
        [graph.links0, np.full((b, m0), -1, np.int32)], axis=0)
    links = np.full((top, n0 + b, M), -1, np.int32)
    if top_old:
        links[:top_old, :n0] = graph.links
    visited = np.full(n0 + b, -1, np.int64)
    evals = _Evals()
    entry = graph.entry
    for i in range(n0, n0 + b):
        entry = _insert_node(vecs, levels, links0, links, M, m0, top, i,
                             entry, ef_construction, visited, evals)
    _repair_connectivity(vecs, links0, entry, evals)
    _compact_pads(links0, links)
    graph.vecs = vecs
    graph.levels = levels
    graph.links0 = links0
    graph.links = links
    graph.entry = entry
    graph.packed = None  # pack() contract: a mutated graph re-packs
    if graph.codec is not None:
        _extend_codec(graph.codec, nv)
    return np.arange(n0, n0 + b, dtype=np.int64)


def _extend_codec(cdx: GraphCodes, new_vecs: np.ndarray) -> None:
    """Encode ``new_vecs`` with the codec's already-trained state and
    append the code rows (and biases) in place; drops the device cache."""
    from . import quantize as qz

    v = np.asarray(new_vecs, np.float32)
    if cdx.kind == "sq8":
        sq = qz.ScalarQuantizer(vmin=cdx.vmin, step=cdx.step)
        codes = np.asarray(qz.sq8_encode(sq, v))
        nb = np.asarray(qz.sq8_recon_sq_norms(sq, codes), np.float32)
    else:
        pq = qz.ProductQuantizer(codebooks=cdx.codebooks)
        codes = np.asarray(qz.pq_encode(pq, v))
        nb = np.zeros(v.shape[0], np.float32)
    cdx.codes = np.ascontiguousarray(
        np.concatenate([cdx.codes, codes], axis=0))
    cdx.node_bias = np.concatenate([cdx.node_bias, nb])
    cdx._dev = None


def reassign_entry(graph: HNSWGraph, alive: np.ndarray) -> int:
    """Point ``graph.entry`` at the highest-level alive node (ties to the
    lowest id). Deleting the entry node would otherwise seed every
    traversal at a tombstone, which the hop mask turns into an empty
    beam. Returns the new entry id; raises if nothing is alive."""
    alive = np.asarray(alive, bool)
    ids = np.flatnonzero(alive)
    if ids.size == 0:
        raise ValueError("reassign_entry: no alive node to anchor at")
    graph.entry = int(ids[np.argmax(graph.levels[ids])])
    return graph.entry


def search(graph: HNSWGraph, queries: np.ndarray, k: int,
           ef_search: int = 64, alive: Optional[np.ndarray] = None
           ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Beam search per query. Returns (scores [Q, k], ids [Q, k], evals
    [Q]): scores = -squared-euclidean (engine convention, higher =
    closer), ids pad with -1 / scores with -inf when the beam holds fewer
    than k nodes, evals = distance computations per query (the visited
    count — the sublinearity metric). ``alive`` (bool [N]) tombstones
    nodes: a dead node never enters a beam or a result; ``graph.entry``
    must point at an alive node (:func:`reassign_entry`)."""
    q = np.asarray(queries, np.float32)
    nq = q.shape[0]
    if alive is not None:
        alive = np.asarray(alive, bool)
        if not alive[graph.entry]:
            raise ValueError("search: graph.entry is tombstoned — call "
                             "reassign_entry() after deleting it")
    ef = max(ef_search, k)
    scores = np.full((nq, k), -np.inf, np.float32)
    ids = np.full((nq, k), -1, np.int32)
    evals = np.zeros(nq, np.int64)
    visited = np.full(graph.ntotal, -1, np.int64)
    for qi in range(nq):
        cnt = _Evals()
        cur = graph.entry
        d_cur = float(candidate_distances(q[qi], graph.vecs[cur][None])[0])
        cnt.n += 1
        for layer in range(graph.max_level, 0, -1):
            cur, d_cur = _greedy_descent(graph.vecs, graph.links[layer - 1],
                                         q[qi], cur, d_cur, cnt, alive)
        found = _search_layer(graph.vecs, graph.links0, q[qi],
                              [(d_cur, cur)], ef, visited, qi, cnt, alive)
        for j, (d, node) in enumerate(found[:k]):
            scores[qi, j] = -d
            ids[qi, j] = node
        evals[qi] = cnt.n
    return scores, ids, evals


def search_batched(graph: HNSWGraph, queries: np.ndarray, k: int,
                   ef_search: int = 64, impl: str = "auto",
                   frontier: int = 8,
                   alive: Optional[np.ndarray] = None
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Array-native batched beam search over the packed adjacency.

    Same semantics as :func:`search` — greedy descent through the upper
    layers, then a best-first beam of width ``ef = max(ef_search, k)`` at
    layer 0 — but the whole batch advances together: per hop, ONE fused
    dispatch scores every live query's frontier neighbors and merges them
    into the per-query beams (heapq and per-query Python loops never
    appear). Visited bookkeeping is a per-query stamp matrix (0 = unseen,
    1 = in beam / seen, 2 = expanded), so a node is scored at most once
    per query and the beam never holds duplicates.

    Drivers (``impl``):

    * ``"np"`` (the ``auto`` default off-TPU) — a host-driven hop loop
      through the vectorized numpy ``graph_beam`` ref, with E-wide
      frontier expansion (``frontier``, default 8) and fresh-candidate
      compaction (see :func:`_search_batched_np`). Host numpy beats XLA
      CPU here — its row gather/scatter primitives are several times
      faster at these shapes — and pays no compile step.
    * ``"fused"`` (the ``auto`` default on TPU) — the ENTIRE frontier
      loop compiles into one XLA ``while_loop`` whose layer-0 hop is the
      ``graph_beam`` Pallas kernel (scalar-prefetch gather + L2 +
      branchless merge on-chip): a search is one dispatch, zero host work
      per hop. The jit cache keys on the batch shape —
      ``SearchEngine.warmup`` pre-compiles every pow2 bucket.
    * ``"jit"`` — the same one-dispatch traversal with an in-jit gather
      and ``lax.top_k`` merge (same first-lowest-index tie rule as the
      kernel's iterative argmax) instead of the Pallas hop; the portable
      in-jit variant for non-TPU accelerators. The jitted drivers always
      run the exact best-first order (``frontier`` is a host-driver
      knob).

    Returns ``(scores [Q, k], ids [Q, k], evals [Q], hops)``: scores are
    -squared-L2 (higher = closer) with -inf/-1 padding like :func:`search`,
    ``evals`` counts fresh distance evaluations per query (same semantics
    as the sequential counter — equal up to beam-boundary ties), ``hops``
    is the number of fused layer-0 dispatches the batch needed (the
    batching win: ~ef hops per BATCH instead of ~ef Python iterations per
    QUERY). Every per-row quantity is independent of the rest of the
    batch, so a query answers identically at q=1 and inside any coalesced
    batch, and repeated searches of a fixed batch are bitwise-
    deterministic (the serving-cache contract).

    ``alive`` (bool [N]) tombstones nodes on every driver: dead
    candidates are masked at the hop (``graph_beam``/``graph_beam_q``'s
    ``db_mask`` operand), so a deleted row can never enter a beam — the
    same never-surfaces contract as the sequential engine. The entry
    node must be alive (:func:`reassign_entry`); ``alive=None`` keeps
    all three drivers bitwise identical to the static graph.
    """
    q = np.ascontiguousarray(np.asarray(queries, np.float32))
    nq = q.shape[0]
    if nq == 0:
        return (np.zeros((0, k), np.float32), np.zeros((0, k), np.int32),
                np.zeros(0, np.int64), 0)
    impl = batched_driver(impl)
    if alive is not None:
        alive = np.asarray(alive, bool)
        if not alive[graph.entry]:
            raise ValueError("search_batched: graph.entry is tombstoned — "
                             "call reassign_entry() after deleting it")
    ef = max(ef_search, k)
    if impl in ("jit", "fused"):
        import jax
        import jax.numpy as jnp

        p = graph.pack()
        dv, dsq, dn0, dup = p.device_arrays(graph.vecs)
        cdx = graph.codec
        if cdx is None:
            codes = node_bias = c0 = c1 = None
            mode, ksub = "f32", 0
        else:
            codes, node_bias, c0, c1 = cdx.device_arrays()
            mode, ksub = cdx.kind, cdx.ksub
        scores, ids, evals, hops = _traverse_jit_fn()(
            jnp.asarray(q), dv, dsq, dn0, dup,
            jnp.asarray(graph.entry, jnp.int32), codes, node_bias, c0, c1,
            None if alive is None else jnp.asarray(alive),
            ef=ef, k=k, use_pallas=(impl == "fused"), mode=mode, ksub=ksub)
        jax.block_until_ready((scores, ids, evals, hops))
        return (np.asarray(scores), np.asarray(ids),
                np.asarray(evals, np.int64), int(hops))
    # narrow beams pin E to 1 (exact best-first order): multi-expansion
    # only pays when the beam is wide enough that its top-E barely moves
    # per hop, and a sub-8-wide beam is fast without it
    frontier = max(1, min(frontier, ef // 8))
    return _search_batched_np(graph, q, k, ef, frontier=frontier,
                              alive=alive)


def _search_batched_np(graph: HNSWGraph, q: np.ndarray, k: int, ef: int,
                       frontier: int = 8,
                       alive: Optional[np.ndarray] = None
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-driven batched driver: one vectorized-numpy ``graph_beam``
    hop per dispatch (see :func:`search_batched`).

    Two throughput levers on top of the plain frontier loop, both
    result-preserving in the common case and bounded where not:

    * ``frontier`` — expand the best ``E`` unexpanded beam entries of each
      live query per hop instead of 1. Python/numpy per-hop overhead is
      the CPU cost floor, so E-wide expansion cuts the hop count ~E-fold.
      E > 1 can expand a node the strictly best-first order would have
      evicted first, so ``evals`` runs a few percent above the sequential
      counter (documented bound: <= 10% at the default E=8 with ef >= 64;
      measured ~2%. E=1 matches the sequential traversal exactly,
      eval-for-eval — :func:`search_batched` pins E=1 when ef < 16).
    * fresh-candidate *compaction* — adjacency rows average far fewer real
      neighbors than their 2M-slot cap, and most have already been
      visited; hop slots are compacted to just the fresh ids (preserving
      slot order, so the stable merge is unchanged) before the fused
      score+merge, which would otherwise burn >80% of its arithmetic on
      masked slots.
    """
    from ..kernels.graph_beam.ops import NEG_INF, graph_beam
    from ..kernels.graph_beam_q.ops import graph_beam_q

    nq = q.shape[0]
    n = graph.ntotal
    p = graph.pack()
    vecs = graph.vecs
    evals = np.zeros(nq, np.int64)
    q_sq = np.einsum("qd,qd->q", q, q)  # hoisted out of the hop loop
    cdx = graph.codec
    if cdx is None:
        # per-row hop operands: the query rows + their norms
        op_a, op_b = q, q_sq

        def hop(ha, hb, ids, bv, bi):
            return graph_beam(ha, vecs, ids, bv, bi, db_sq=p.vecs_sq,
                              q_sq=hb, db_mask=alive, impl="np")
    else:
        # quantized payload: per-query affine operands hoisted once per
        # search; every hop (seed, descent, layer 0) scores codes
        op_a, op_b = cdx.query_operands(q, q_sq)

        def hop(ha, hb, ids, bv, bi):
            return graph_beam_q(ha, hb, cdx.codes, cdx.node_bias, ids, bv,
                                bi, db_mask=alive, mode=cdx.kind,
                                ksub=cdx.ksub, impl="np")

    # entry seed: a 1-wide merge against the lone entry candidate yields
    # (score, id) of the entry point for every query in one dispatch
    sv, si = hop(op_a, op_b, np.full((nq, 1), graph.entry, np.int32),
                 np.full((nq, 1), NEG_INF, np.float32),
                 np.full((nq, 1), -1, np.int32))
    s_cur = sv[:, 0].copy()
    cur = si[:, 0].copy()
    evals += 1

    # upper layers: batched greedy descent. An ef=1 beam merge picks the
    # best of {current} ∪ neighbors; stable ties keep the current node, so
    # "merge returned the same id" IS the sequential stop condition.
    for layer in range(graph.max_level, 0, -1):
        adj = p.upper[layer - 1]
        live = np.arange(nq)
        while live.size:
            ids = adj[cur[live]]                             # [R, M]
            evals[live] += (ids >= 0).sum(axis=1)
            nv, ni = hop(op_a[live], op_b[live], ids, s_cur[live][:, None],
                         cur[live][:, None])
            moved = ni[:, 0] != cur[live]
            s_cur[live] = nv[:, 0]
            cur[live] = ni[:, 0]
            live = live[moved]

    # layer 0: batched best-first beam. state stamps make the visited set
    # O(1) to query/update for the whole batch at once. The loop body
    # special-cases "every query still live" (the common hop — the batch
    # finishes around the same depth) to skip all row-subset copies.
    state = np.zeros((nq, n), np.uint8)     # 0 unseen / 1 seen / 2 expanded
    rows_all = np.arange(nq)
    col_rows = rows_all[:, None]
    beam_v = np.full((nq, ef), NEG_INF, np.float32)
    beam_i = np.full((nq, ef), -1, np.int32)
    beam_v[:, 0] = s_cur
    beam_i[:, 0] = cur
    state[rows_all, cur] = 1
    hops = 0
    while True:
        in_beam = beam_i >= 0
        safe_beam = np.where(in_beam, beam_i, 0)
        unexp = in_beam & (state[col_rows, safe_beam] == 1)
        live = unexp.any(axis=1)
        if not live.any():
            break
        if live.all():
            rows, rcol = rows_all, col_rows
            hq, hq_sq, ue = op_a, op_b, unexp
            bv, bi = beam_v, beam_i
        else:
            rows = np.flatnonzero(live)
            rcol = rows[:, None]
            hq, hq_sq, ue = op_a[rows], op_b[rows], unexp[rows]
            bv, bi = beam_v[rows], beam_i[rows]
        nr = rows.size
        if frontier == 1:
            j = ue.argmax(axis=1)           # beam sorted desc -> first
            nodes = bi[np.arange(nr), j][:, None]
        else:
            # first `frontier` unexpanded slots per row: nonzero emits
            # True positions row-major, searchsorted ranks them within
            # their row; rows with fewer repeat their best node (a no-op
            # re-expansion)
            rn, cn = np.nonzero(ue)
            rank = np.arange(rn.size) - np.searchsorted(rn, rn)
            keep = rank < frontier
            rn, cn, rank = rn[keep], cn[keep], rank[keep]
            nodes = np.full((nr, frontier), -1, np.int32)
            nodes[rn, rank] = bi[rn, cn]
            nodes = np.where(nodes >= 0, nodes, nodes[:, :1])
        state[rcol, nodes] = 2
        nbrs = p.nbrs0[nodes].reshape(nr, -1)                # [R, E*2M]
        valid = nbrs >= 0
        # compact the real neighbor ids left IMMEDIATELY (slot order
        # preserved -> the stable merge is unchanged): adjacency rows
        # average far fewer links than their 2M cap, so every op below
        # runs at ~mean-degree width instead of E*2M
        cnt = valid.cumsum(axis=1)
        width = max(int(cnt[:, -1].max()), 1)
        cand = np.full((nr, width), -1, np.int32)
        vr, vs = np.nonzero(valid)
        cand[vr, cnt[vr, vs] - 1] = nbrs[vr, vs]
        # pad slots alias the (already-expanded) first frontier node so
        # the stamp scatter below can never collide with a real neighbor
        safe = np.where(cand >= 0, cand, nodes[:, :1])
        fresh = (cand >= 0) & (state[rcol, safe] == 0)
        # NOTE: the stamp scatter uses the PRE-dedup mask — every
        # occurrence of a node writes the same value, so numpy's
        # last-write-wins scatter is deterministic
        state[rcol, safe] |= fresh.astype(np.uint8)
        if frontier > 1:
            # E expansions can name the same fresh neighbor twice inside
            # one hop; keep the first slot (stable), mask the rest
            order = np.argsort(safe, axis=1, kind="stable")
            ss = np.take_along_axis(safe, order, axis=1)
            first = np.ones_like(ss, bool)
            first[:, 1:] = ss[:, 1:] != ss[:, :-1]
            dedup = np.empty_like(first)
            np.put_along_axis(dedup, order, first, axis=1)
            fresh &= dedup
        evals[rows] += fresh.sum(axis=1)
        cand = np.where(fresh, cand, -1)
        nv, ni = hop(hq, hq_sq, cand, bv, bi)
        if rows is rows_all:
            beam_v, beam_i = nv, ni
        else:
            beam_v[rows] = nv
            beam_i[rows] = ni
        hops += 1

    scores = beam_v[:, :k].copy()
    ids = beam_i[:, :k].copy()
    scores[ids < 0] = -np.inf
    return scores, ids, evals, hops


def _traverse_impl(q, vecs, vecs_sq, nbrs0, upper, entry, codes, node_bias,
                   c0, c1, alive=None, *, ef: int, k: int, use_pallas: bool,
                   mode: str = "f32", ksub: int = 0):
    """The whole batched traversal as ONE traceable function: greedy
    descent (one ``lax.while_loop`` per upper layer) then the layer-0
    frontier loop (a single ``lax.while_loop`` whose body is one fused
    hop). Jitted via :func:`_traverse_jit_fn`; a search is one XLA
    dispatch, so per-hop cost is pure compute — no host round-trips.

    ``mode`` (static) selects what the hop scores: ``"f32"`` gathers
    corpus rows (``codes``/``node_bias``/``c0``/``c1`` are None);
    ``"sq8"``/``"pq"`` gather the ``codes`` payload and score via the
    unified affine form (c0/c1 = vmin/step for sq8, codebooks/None for
    pq — see :class:`GraphCodes`). The whole traversal switches space
    uniformly — entry seed, greedy descent, and the layer-0 beam all
    score the same payload, so beam ordering is self-consistent.

    Dead rows (queries whose beam is fully expanded) keep looping with
    all-masked candidates until the whole batch converges; every masked
    merge is a bitwise no-op, which is what makes a row's answer
    independent of who else shares its batch.

    ``alive`` (bool [N], traced) tombstones nodes: dead candidate ids are
    demoted to -1 before every score/hop, so a deleted row never enters a
    beam; ``alive=None`` traces the mask-free graph bitwise unchanged."""
    import jax
    import jax.numpy as jnp

    from ..kernels.common import EXACT
    from ..kernels.graph_beam.kernel import (NEG_INF, gather_layout,
                                             graph_beam_pallas)
    from ..kernels.graph_beam_q.kernel import graph_beam_q_pallas

    nq = q.shape[0]
    n = vecs.shape[0]
    rows = jnp.arange(nq)
    rr = rows[:, None]
    q_sq = jnp.einsum("qd,qd->q", q, q)

    if mode == "sq8":
        q_op = (2.0 * q * c1[None, :]).astype(jnp.float32)
        q_bias = (2.0 * jnp.matmul(q, c0, precision=EXACT)
                  - q_sq).astype(jnp.float32)
    elif mode == "pq":
        from ..search.quantize import adc_lut  # the ONE LUT formula home

        q_op = -adc_lut(c0, q).reshape(nq, -1)
        q_bias = jnp.zeros((nq,), jnp.float32)

    def demote_dead(cand):
        """-1 out tombstoned candidate ids (no-op when alive is None)."""
        if alive is None:
            return cand
        safe = jnp.where(cand >= 0, cand, 0)
        return jnp.where((cand >= 0) & alive[safe], cand, -1)

    def score(cand):
        """[Q, W] score of candidate ids; -1 slots -> NEG_INF. f32 mode
        scores -squared-L2 on corpus rows; quantized modes score the
        code payload (same algebra as ``graph_beam_q``)."""
        cand = demote_dead(cand)
        safe = jnp.where(cand >= 0, cand, 0)
        if mode == "f32":
            g = vecs[safe]                                   # [Q, W, d]
            s = (2.0 * jnp.einsum("qwd,qd->qw", g, q, precision=EXACT)
                 - vecs_sq[safe]
                 - q_sq[:, None])
        elif mode == "sq8":
            g = codes[safe].astype(jnp.float32)              # [Q, W, d]
            s = (jnp.einsum("qwd,qd->qw", g, q_op, precision=EXACT)
                 + q_bias[:, None]
                 - node_bias[safe])
        else:
            m = codes.shape[1]
            offs = codes[safe] + jnp.arange(m, dtype=jnp.int32) * ksub
            w = cand.shape[1]
            g = jnp.take_along_axis(q_op, offs.reshape(nq, w * m), axis=1)
            s = (g.reshape(nq, w, m).sum(-1) + q_bias[:, None]
                 - node_bias[safe])
        return jnp.where(cand >= 0, s, NEG_INF)

    def merge_jnp(bv, bi, cand, out_w):
        """top_k merge: first-lowest-index tie rule == the kernel's
        iterative argmax; pads canonicalized to (NEG_INF, -1)."""
        allv = jnp.concatenate([bv, score(cand)], axis=1)
        alli = jnp.concatenate([bi, demote_dead(cand)], axis=1)
        nv, idx = jax.lax.top_k(allv, out_w)
        ni = jnp.take_along_axis(alli, idx, axis=1)
        ni = jnp.where(nv <= NEG_INF, -1, ni)
        nv = jnp.where(ni >= 0, nv, NEG_INF)
        return nv, ni

    # entry seed (scored in whatever space the traversal runs in)
    s_cur = score(jnp.full((nq, 1), entry, jnp.int32))[:, 0].astype(
        jnp.float32)
    cur = jnp.full((nq,), entry, jnp.int32)
    evals = jnp.ones((nq,), jnp.int32)

    # upper layers: batched greedy descent (ef=1 merge; stable ties keep
    # the current node, which IS the sequential stop condition)
    for layer in range(upper.shape[0], 0, -1):
        adj = upper[layer - 1]

        def desc_body(c, adj=adj):
            cur, s_cur, active, evals = c
            ids = adj[cur]                                   # [Q, M]
            valid = (ids >= 0) & active[:, None]
            evals = evals + valid.sum(axis=1, dtype=jnp.int32)
            nv, ni = merge_jnp(s_cur[:, None], cur[:, None],
                               jnp.where(valid, ids, -1), 1)
            moved = (ni[:, 0] != cur) & active
            cur = jnp.where(active, ni[:, 0], cur)
            s_cur = jnp.where(active, nv[:, 0], s_cur)
            return cur, s_cur, moved, evals

        cur, s_cur, _, evals = jax.lax.while_loop(
            lambda c: c[2].any(), desc_body,
            (cur, s_cur, jnp.ones((nq,), bool), evals))

    # layer 0: batched best-first beam over per-query visited stamps
    beam_v = jnp.full((nq, ef), NEG_INF, jnp.float32).at[:, 0].set(s_cur)
    beam_i = jnp.full((nq, ef), -1, jnp.int32).at[:, 0].set(cur)
    state = jnp.zeros((nq, n), jnp.uint8).at[rows, cur].set(1)

    def unexpanded(beam_i, state):
        in_beam = beam_i >= 0
        safe_b = jnp.where(in_beam, beam_i, 0)
        return in_beam & (jnp.take_along_axis(state, safe_b, axis=1) == 1)

    if use_pallas:
        # the hop kernel's tile layout of what it gathers, built once here
        # and not per hop (a pad inside the loop body would copy per hop)
        hop_rows, hop_bias = gather_layout(
            *((vecs, vecs_sq) if mode == "f32" else (codes, node_bias)))

    def hop_body(c):
        beam_v, beam_i, state, evals, hops = c
        unexp = unexpanded(beam_i, state)
        live = unexp.any(axis=1)
        j = jnp.argmax(unexp, axis=1)     # beam sorted desc -> first
        node = jnp.take_along_axis(beam_i, j[:, None], axis=1)[:, 0]
        node = jnp.where(live, node, 0)
        state = state.at[rows, node].max(
            jnp.where(live, jnp.uint8(2), jnp.uint8(0)))
        nbrs = nbrs0[node]                                   # [Q, 2M]
        valid = (nbrs >= 0) & live[:, None]
        # pad slots alias the expanded node: the stamp scatter can never
        # collide with a real neighbor (adjacency has no self-loops)
        safe = jnp.where(valid, nbrs, node[:, None])
        fresh = valid & (jnp.take_along_axis(state, safe, axis=1) == 0)
        state = state.at[rr, safe].max(fresh.astype(jnp.uint8))
        evals = evals + fresh.sum(axis=1, dtype=jnp.int32)
        cand = demote_dead(jnp.where(fresh, nbrs, -1))
        if not use_pallas:
            nv, ni = merge_jnp(beam_v, beam_i, cand, ef)
        elif mode == "f32":
            nv, ni = graph_beam_pallas(q, hop_rows, hop_bias, cand,
                                       beam_v, beam_i)
        else:
            nv, ni = graph_beam_q_pallas(q_op, q_bias, hop_rows, hop_bias,
                                         cand, beam_v, beam_i, mode=mode,
                                         ksub=ksub)
        return nv, ni, state, evals, hops + 1

    beam_v, beam_i, _, evals, hops = jax.lax.while_loop(
        lambda c: unexpanded(c[1], c[2]).any(), hop_body,
        (beam_v, beam_i, state, evals, jnp.int32(0)))

    scores = beam_v[:, :k]
    ids = beam_i[:, :k]
    return jnp.where(ids >= 0, scores, -jnp.inf), ids, evals, hops


_TRAVERSE_JIT = None


def _traverse_jit_fn():
    """Jitted :func:`_traverse_impl` (lazy: this module must import
    without jax). One compile per (batch, graph, ef, k) shape — the
    serving engine's pow2 warm-up visits exactly these."""
    global _TRAVERSE_JIT
    if _TRAVERSE_JIT is None:
        import jax

        _TRAVERSE_JIT = jax.jit(_traverse_impl,
                                static_argnames=("ef", "k", "use_pallas",
                                                 "mode", "ksub"))
    return _TRAVERSE_JIT


def recall_vs_exact(graph: HNSWGraph, corpus: np.ndarray,
                    queries: np.ndarray, k: int, ef_search: int) -> float:
    import jax.numpy as jnp

    from ..core.metrics import knn_indices, set_overlap

    exact = knn_indices(jnp.asarray(queries), jnp.asarray(corpus), k)
    _, got, _ = search(graph, queries, k, ef_search)
    return float(set_overlap(exact, jnp.asarray(got)))
