"""HNSW graph tier: structural invariants, ef monotonicity, stats,
factory/persistence integration, 20k acceptance.

Invariants follow the construction contract in ``repro.search.hnsw``:
degree caps (M upper / 2M layer 0), symmetric links *after* pruning,
entry point on the top layer, layer-0 reachability, layer membership.
Each property runs as a deterministic seed sweep (always on) plus a
``hypothesis`` fuzz variant via the optional-dependency shim.
"""
import jax
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro import api
from repro.data import synthetic
from repro.search import hnsw

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def corpus():
    return synthetic.embedding_corpus(2000, 32, n_clusters=8, intrinsic=12,
                                      seed=13)


@pytest.fixture(scope="module")
def queries(corpus):
    rng = np.random.default_rng(4)
    picks = rng.integers(0, corpus.shape[0], 48)
    return corpus[picks] + 0.01 * rng.standard_normal(
        (48, corpus.shape[1])).astype(np.float32)


@pytest.fixture(scope="module")
def graph(corpus):
    return hnsw.build(corpus, M=8, ef_construction=60, seed=0)


# ---------------------------------------------------------------------------
# Structural invariants
# ---------------------------------------------------------------------------
def check_graph_invariants(g: hnsw.HNSWGraph):
    n = g.ntotal
    # entry point sits on the top layer; no node exceeds it
    assert int(g.levels[g.entry]) == int(g.levels.max())
    assert np.all(g.levels <= g.levels[g.entry])
    for layer in range(g.max_level + 1):
        adj = g.adjacency(layer)
        cap = 2 * g.M if layer == 0 else g.M
        deg = (adj >= 0).sum(axis=1)
        # degree cap
        assert deg.max() <= cap, (layer, int(deg.max()), cap)
        src, slot = np.nonzero(adj >= 0)
        dst = adj[src, slot]
        # links stay inside the corpus and never self-loop
        assert np.all((dst >= 0) & (dst < n))
        assert np.all(src != dst)
        # both endpoints are members of this layer
        assert np.all(g.levels[src] >= layer)
        assert np.all(g.levels[dst] >= layer)
        # no duplicate slots
        assert len(set(zip(src.tolist(), dst.tolist()))) == len(src)
        # bidirectional after pruning: edge set equals its transpose
        edges = set(zip(src.tolist(), dst.tolist()))
        assert all((b, a) in edges for a, b in edges), f"layer {layer}"
    # layer 0 is reachable from the entry point
    assert hnsw._bfs_layer0(g.links0, g.entry).all()


def test_graph_invariants_deterministic(graph):
    check_graph_invariants(graph)


@pytest.mark.parametrize("seed,n,m", [(1, 50, 2), (2, 300, 4), (3, 777, 6),
                                      (4, 120, 16), (5, 1, 4), (6, 2, 4)])
def test_graph_invariants_sweep(seed, n, m):
    x = synthetic.embedding_corpus(max(n, 8), 16, n_clusters=4, intrinsic=8,
                                   seed=seed)[:n]
    check_graph_invariants(hnsw.build(x, M=m, ef_construction=30, seed=seed))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 250),
       m=st.integers(2, 12), efc=st.integers(4, 60))
def test_graph_invariants_fuzz(seed, n, m, efc):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    check_graph_invariants(hnsw.build(x, M=m, ef_construction=efc, seed=seed))


def test_level_sampling_geometric():
    """Levels follow the floor(-ln(U)/ln(M)) law: P(level >= L) ~ M^-L."""
    lv = hnsw.sample_levels(200_000, 16, seed=0)
    frac1 = float((lv >= 1).mean())
    assert abs(frac1 - 1 / 16) < 0.005
    frac2 = float((lv >= 2).mean())
    assert abs(frac2 - 1 / 256) < 0.002


# ---------------------------------------------------------------------------
# Search behaviour: ef monotonicity + beam padding
# ---------------------------------------------------------------------------
def test_ef_recall_monotone_deterministic(graph, corpus, queries):
    recalls = [hnsw.recall_vs_exact(graph, corpus, queries, 10, ef)
               for ef in (10, 20, 40, 80, 160)]
    for lo, hi in zip(recalls, recalls[1:]):
        assert hi >= lo, recalls
    assert recalls[-1] >= 0.95, recalls


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ef_recall_monotone_fuzz(seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((400, 12)).astype(np.float32)
    g = hnsw.build(x, M=6, ef_construction=40, seed=seed)
    q = x[:16] + 0.01 * rng.standard_normal((16, 12)).astype(np.float32)
    recalls = [hnsw.recall_vs_exact(g, x, q, 5, ef) for ef in (5, 20, 80)]
    # greedy beams are not *theoretically* monotone query-by-query; allow
    # a hair of noise pairwise but require the sweep to end at least as
    # high as it starts
    for lo, hi in zip(recalls, recalls[1:]):
        assert hi >= lo - 0.02, recalls
    assert recalls[-1] >= recalls[0], recalls


def test_search_pads_when_beam_short(corpus):
    """k beyond the beam/corpus pads with -1/-inf (FAISS convention)."""
    g = hnsw.build(corpus[:6], M=4, ef_construction=20, seed=0)
    scores, ids, _ = hnsw.search(g, corpus[:3], 10)
    assert ids.shape == (3, 10)
    assert np.all(ids[:, 6:] == -1)
    assert np.all(np.isneginf(scores[:, 6:]))
    valid = ids >= 0
    assert np.all(np.isfinite(scores[valid]))


def test_candidate_distances_matches_brute_force():
    """The host scorer of build/insert/sequential search returns squared
    L2 in input order (float64 brute force as the reference)."""
    rng = np.random.default_rng(7)
    q = rng.standard_normal(24).astype(np.float32)
    vecs = rng.standard_normal((33, 24)).astype(np.float32)
    want = ((vecs.astype(np.float64) - q) ** 2).sum(axis=1)
    np.testing.assert_allclose(hnsw.candidate_distances(q, vecs), want,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Batched (array-native) traversal vs the sequential heapq beam
# ---------------------------------------------------------------------------
def test_batched_frontier1_matches_sequential_exactly(graph, queries):
    """At frontier=1 the batched loop expands in the identical best-first
    order: neighbor sets AND eval counters match the heapq engine
    query-for-query, at every beam width."""
    for ef in (10, 40, 128):
        s_sc, s_id, s_ev = hnsw.search(graph, queries, 10, ef_search=ef)
        b_sc, b_id, b_ev, hops = hnsw.search_batched(
            graph, queries, 10, ef_search=ef, impl="np", frontier=1)
        np.testing.assert_array_equal(s_id, b_id)
        np.testing.assert_array_equal(s_ev, b_ev)
        assert hops > 0


def test_batched_default_frontier_recall_and_evals_bound(graph, corpus,
                                                         queries):
    """The default multi-expansion frontier (E=8): recall at equal
    efSearch identical to sequential within 0.01, >= 99% of returned
    neighbor sets identical, eval counters within the documented 10%."""
    import jax.numpy as jnp

    from repro.core.metrics import knn_indices
    gt = np.asarray(knn_indices(jnp.asarray(queries), jnp.asarray(corpus),
                                10))
    s_sc, s_id, s_ev = hnsw.search(graph, queries, 10, ef_search=80)
    b_sc, b_id, b_ev, _ = hnsw.search_batched(graph, queries, 10,
                                              ef_search=80, impl="np")
    rec = lambda ids: np.mean([len(set(a) & set(b)) / 10
                               for a, b in zip(gt, ids)])
    assert abs(rec(s_id) - rec(b_id)) <= 0.01
    same = np.mean([set(a.tolist()) == set(b.tolist())
                    for a, b in zip(s_id, b_id)])
    assert same >= 0.99, same
    ratio = b_ev.mean() / s_ev.mean()
    assert 0.9 <= ratio <= 1.1, ratio


def test_batched_drivers_agree(graph, queries):
    """The one-dispatch jitted driver returns the same neighbors and the
    same eval counters as the host-driven numpy driver (both at the exact
    best-first order the jit driver always uses)."""
    n_sc, n_id, n_ev, _ = hnsw.search_batched(graph, queries[:8], 10,
                                              ef_search=64, impl="np",
                                              frontier=1)
    j_sc, j_id, j_ev, _ = hnsw.search_batched(graph, queries[:8], 10,
                                              ef_search=64, impl="jit")
    np.testing.assert_array_equal(n_id, j_id)
    np.testing.assert_array_equal(n_ev, j_ev)
    np.testing.assert_allclose(n_sc, j_sc, rtol=1e-5, atol=1e-5)


def test_batched_deterministic_and_row_independent(graph, queries):
    """Fixed batch -> bitwise-identical reruns; and every row's answer is
    independent of its batch-mates (the serving-cache contract: a query
    answers the same alone and coalesced)."""
    q = queries[:12]
    r1 = hnsw.search_batched(graph, q, 10, ef_search=64, impl="np")
    r2 = hnsw.search_batched(graph, q, 10, ef_search=64, impl="np")
    for a, b in zip(r1[:3], r2[:3]):
        np.testing.assert_array_equal(a, b)
    for i in (0, 5, 11):
        solo = hnsw.search_batched(graph, q[i:i + 1], 10, ef_search=64,
                                   impl="np")
        np.testing.assert_array_equal(solo[0][0], r1[0][i])  # scores bitwise
        np.testing.assert_array_equal(solo[1][0], r1[1][i])


def test_batched_ragged_shapes(corpus):
    """q=1, q not a power of two, k > efSearch, and k > ntotal all follow
    the sequential engine's shape/padding contract."""
    g = hnsw.build(corpus[:300], M=6, ef_construction=40, seed=1)
    for nq in (1, 5):
        q = corpus[:nq]
        s = hnsw.search(g, q, 7, ef_search=3)   # ef < k -> ef = k
        b = hnsw.search_batched(g, q, 7, ef_search=3, impl="np")
        assert b[0].shape == (nq, 7) and b[1].shape == (nq, 7)
        np.testing.assert_array_equal(s[1], b[1])
    # k beyond the corpus: FAISS pad convention, same as sequential
    tiny = hnsw.build(corpus[:6], M=4, ef_construction=20, seed=0)
    sc, ids, ev, _ = hnsw.search_batched(tiny, corpus[:3], 10, impl="np")
    assert ids.shape == (3, 10)
    assert np.all(ids[:, 6:] == -1)
    assert np.all(np.isneginf(sc[:, 6:]))
    assert np.all(np.isfinite(sc[ids >= 0]))


def test_batched_disconnected_node(corpus):
    """A node unreachable from the entry point is never returned, and the
    short beam pads instead of crashing (graph hand-mutated: the build
    path guarantees connectivity, so sever it manually)."""
    g = hnsw.build(corpus[:8], M=4, ef_construction=20, seed=0)
    # sever node furthest from entry: drop all its links, both directions
    victim = max(range(8), key=lambda i: 0 if i == g.entry else
                 float(((g.vecs[i] - g.vecs[g.entry]) ** 2).sum()))
    g.links0[victim] = -1
    g.links0[g.links0 == victim] = -1
    g.links[g.links == victim] = -1
    g.packed = None  # graph mutated after pack: recompile
    sc, ids, ev, _ = hnsw.search_batched(g, corpus[:4], 8, impl="np")
    assert not np.any(ids == victim)
    assert np.all(ids[:, 7:] == -1)          # only 7 reachable nodes
    assert np.all(np.isneginf(sc[:, 7:]))


def test_hnsw_index_engine_routing(corpus):
    """``batched='auto'`` serves lone queries on the sequential engine
    and batches on the array-native one (``beam_hops`` in stats marks the
    batched path); True/False pin either engine."""
    idx = api.HNSWIndex(m=8, ef_construction=40).build(corpus[:500])
    assert idx._g.packed is not None         # build packs eagerly
    lone = idx.search(corpus[:1], 5)
    assert "beam_hops" not in lone.stats
    batch = idx.search(corpus[:4], 5)
    assert batch.stats.get("beam_hops", 0) > 0
    pinned = api.HNSWIndex(m=8, ef_construction=40, batched=True)
    pinned._g = idx._g
    assert "beam_hops" in pinned.search(corpus[:1], 5).stats
    seq = api.HNSWIndex(m=8, ef_construction=40, batched=False)
    seq._g = idx._g
    assert "beam_hops" not in seq.search(corpus[:4], 5).stats
    # both engines return the same neighbors either way
    np.testing.assert_array_equal(batch.indices,
                                  seq.search(corpus[:4], 5).indices)


# ---------------------------------------------------------------------------
# Quantized graph payloads: SQ8/PQ codes inside the batched traversal
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module", params=["sq8", "pq"])
def quant_graph(request, corpus):
    kw = {"quant": request.param}
    if request.param == "pq":
        kw.update(pq_m=8, pq_bits=8)
    idx = api.HNSWIndex(m=8, ef_construction=60, seed=0, **kw)
    return idx.build(corpus[:800])


def test_quant_graph_drivers_agree(quant_graph, queries):
    """np and jit drivers score the same code payload: identical neighbor
    ids and eval counters at frontier=1, scores allclose."""
    g = quant_graph._g
    assert g.codec is not None and g.codec.kind == quant_graph.quant
    n_sc, n_id, n_ev, _ = hnsw.search_batched(g, queries[:8], 10,
                                              ef_search=64, impl="np",
                                              frontier=1)
    j_sc, j_id, j_ev, _ = hnsw.search_batched(g, queries[:8], 10,
                                              ef_search=64, impl="jit")
    np.testing.assert_array_equal(n_id, j_id)
    np.testing.assert_array_equal(n_ev, j_ev)
    np.testing.assert_allclose(n_sc, j_sc, rtol=1e-4, atol=1e-4)


def test_quant_graph_deterministic_and_row_independent(quant_graph, queries):
    """The serving-cache contract holds over codes too: bitwise-stable
    reruns, and each row answers the same alone and coalesced."""
    g = quant_graph._g
    q = queries[:12]
    r1 = hnsw.search_batched(g, q, 10, ef_search=64, impl="np")
    r2 = hnsw.search_batched(g, q, 10, ef_search=64, impl="np")
    for a, b in zip(r1[:3], r2[:3]):
        np.testing.assert_array_equal(a, b)
    for i in (0, 11):
        solo = hnsw.search_batched(g, q[i:i + 1], 10, ef_search=64,
                                   impl="np")
        np.testing.assert_array_equal(solo[0][0], r1[0][i])
        np.testing.assert_array_equal(solo[1][0], r1[1][i])


def test_quant_graph_ragged_shapes(corpus):
    """q=1, ef < k, and k > ntotal keep the sequential engine's
    shape/padding contract when the hop reads codes."""
    for quant in ("sq8", "pq"):
        kw = {"pq_m": 8} if quant == "pq" else {}
        idx = api.HNSWIndex(m=6, ef_construction=40, seed=1, quant=quant,
                            **kw).build(corpus[:300])
        for nq in (1, 5):
            sc, ids, ev, _ = hnsw.search_batched(idx._g, corpus[:nq], 7,
                                                 ef_search=3, impl="np")
            assert sc.shape == (nq, 7) and ids.shape == (nq, 7)
            assert np.all(ids >= 0)
        tiny = api.HNSWIndex(m=4, ef_construction=20, seed=0,
                             quant=quant, **kw).build(corpus[:6])
        sc, ids, ev, _ = hnsw.search_batched(tiny._g, corpus[:3], 10,
                                             impl="np")
        assert ids.shape == (3, 10)
        assert np.all(ids[:, 6:] == -1)
        assert np.all(np.isneginf(sc[:, 6:]))
        assert np.all(np.isfinite(sc[ids >= 0]))


def test_quant_graph_recall_close_to_f32(quant_graph, corpus, queries):
    """Pre-rerank neighbor quality over codes tracks the f32 traversal:
    recall@10 vs exact within the codec's documented slack (SQ8 is
    near-exact; raw PQ8x8 ordering is noisy — the Rerank stage recovers
    it, see the acceptance test)."""
    import jax.numpy as jnp

    from repro.core.metrics import knn_indices
    x = corpus[:800]
    gt = np.asarray(knn_indices(jnp.asarray(queries), jnp.asarray(x), 10))
    f32 = api.HNSWIndex(m=8, ef_construction=60, seed=0).build(x)
    rec = lambda idx: np.mean([len(set(a) & set(b)) / 10 for a, b in zip(
        gt, idx.search(queries, 10).indices)])
    slack = 0.02 if quant_graph.quant == "sq8" else 0.35
    assert rec(quant_graph) >= rec(f32) - slack


def test_quant_graph_lone_query_pins_batched(quant_graph, queries):
    """quant pins ALL queries to the batched engine — the sequential heapq
    scores f32 rows, which would break row-independent caching."""
    res = quant_graph.search(queries[:1], 5)
    assert res.stats.get("beam_hops", 0) > 0
    assert "gather_bytes_per_hop" in res.stats


def test_quant_graph_gather_bytes_stat(corpus, queries):
    """The traversal-traffic accounting: bytes/hop scales with the codec's
    per-row gather width (f32: 4d+4, sq8: d+4, pq: m+4)."""
    x = corpus[:500]
    d = x.shape[1]
    widths = {}
    for quant, width in ((None, 4 * d + 4), ("sq8", d + 4), ("pq", 8 + 4)):
        kw = {"pq_m": 8} if quant == "pq" else {}
        idx = api.HNSWIndex(m=8, ef_construction=40, seed=0, quant=quant,
                            **kw).build(x)
        res = idx.search(queries[:8], 10)
        per_eval = res.stats["gather_bytes_per_hop"] * \
            res.stats["beam_hops"] / res.distance_evals / 8
        widths[quant] = per_eval
        np.testing.assert_allclose(per_eval, width, rtol=1e-6)
    assert widths[None] / widths["sq8"] >= 3.0
    assert widths[None] / widths["pq"] >= 4.0


def test_quant_graph_save_load_and_fingerprints(quant_graph, corpus,
                                                queries, tmp_path):
    """Codec state round-trips (same neighbors, same fingerprint after
    reload), and the fingerprint separates f32 / SQ8 / PQ builds of the
    same graph — the serving cache must never alias them."""
    res = quant_graph.search(queries[:8], 10)
    quant_graph.save(str(tmp_path / "qg"))
    idx2 = api.load_index(str(tmp_path / "qg"))
    assert isinstance(idx2, api.HNSWIndex)
    assert idx2.quant == quant_graph.quant
    assert idx2._g.codec is not None
    assert idx2.fingerprint() == quant_graph.fingerprint()
    res2 = idx2.search(queries[:8], 10)
    np.testing.assert_array_equal(res2.indices, res.indices)
    np.testing.assert_allclose(res2.scores, res.scores, rtol=1e-5)
    f32 = api.HNSWIndex(m=8, ef_construction=60, seed=0).build(corpus[:800])
    assert f32.fingerprint() != quant_graph.fingerprint()


def test_quant_graph_fingerprints_distinct_across_codecs(corpus):
    x = corpus[:300]
    fps = {q: api.HNSWIndex(m=6, ef_construction=40, seed=0, quant=q)
           .build(x).fingerprint() for q in (None, "sq8", "pq")}
    assert len(set(fps.values())) == 3


def test_quant_graph_bytes_per_vector_accounts_codec(corpus):
    x = corpus[:300]
    d = x.shape[1]
    base = api.HNSWIndex(m=6, ef_construction=40, seed=0).build(x)
    sq8 = api.HNSWIndex(m=6, ef_construction=40, seed=0,
                        quant="sq8").build(x)
    pq = api.HNSWIndex(m=6, ef_construction=40, seed=0, quant="pq",
                       pq_m=8).build(x)
    assert sq8.bytes_per_vector == base.bytes_per_vector + d + 4
    assert pq.bytes_per_vector == base.bytes_per_vector + 8 + 4


# ---------------------------------------------------------------------------
# distance_evals stats: the sublinearity contract, asserted per tier
# ---------------------------------------------------------------------------
def test_distance_evals_flat_is_n(corpus, queries):
    res = api.FlatIndex().build(corpus).search(queries, 10)
    assert res.distance_evals == corpus.shape[0]


def test_distance_evals_ivf_is_probed_sizes(corpus, queries):
    idx = api.IVFFlatIndex(n_cells=32, nprobe=4).build(corpus)
    res = idx.search(queries, 10)
    # probed sizes: more than k, far less than the full corpus
    assert 10 <= res.distance_evals < corpus.shape[0]
    assert res.stats["centroid_evals"] == 32
    # probing more cells evaluates more distances
    more = api.IVFFlatIndex(n_cells=32, nprobe=16).build(corpus)
    assert more.search(queries, 10).distance_evals > res.distance_evals


def test_distance_evals_hnsw_is_visited_and_sublinear(graph, corpus,
                                                      queries):
    idx = api.HNSWIndex(m=8, ef_construction=60)
    idx._g = graph  # reuse the module-scoped build
    res = idx.search(queries, 10)
    assert 10 <= res.distance_evals < corpus.shape[0]
    # widening the beam visits more
    wide = api.HNSWIndex(m=8, ef_search=256)
    wide._g = graph
    assert wide.search(queries, 10).distance_evals > res.distance_evals


def test_distance_evals_two_stage_composes(corpus, queries):
    idx = api.TwoStageIndex(api.make_reducer("pca", 8),
                            api.HNSWIndex(m=8, ef_construction=60),
                            rerank_factor=4)
    idx.build(corpus)
    res = idx.search(queries, 10)
    k1 = 10 * 4 * api.HNSWIndex.stage1_oversample
    assert res.stats["rerank_evals"] == k1
    assert res.distance_evals == (res.stats["stage1_distance_evals"] + k1)


# ---------------------------------------------------------------------------
# Factory + persistence integration
# ---------------------------------------------------------------------------
def test_factory_hnsw_knobs_flow_through():
    idx = api.index_factory("HNSW16", index_kw={"ef_construction": 33,
                                                "ef_search": 44, "seed": 5})
    assert isinstance(idx, api.HNSWIndex)
    assert (idx.m, idx.ef_construction, idx.ef_search, idx.seed) == \
        (16, 33, 44, 5)
    stack = api.index_factory("RAE64,HNSW32,Rerank4")
    assert isinstance(stack, api.TwoStageIndex)
    assert isinstance(stack.base, api.HNSWIndex)
    assert stack.rerank_factor == 4


def test_factory_hnsw_rejects_cosine():
    with pytest.raises(ValueError, match="euclidean only"):
        api.index_factory("HNSW32", metric="cosine")


@pytest.mark.parametrize("spec", ["HNSW32,SQ8", "HNSW16,PQ8x8",
                                  "RAE64,HNSW32,SQ8,Rerank4",
                                  "RAE64,HNSW32,PQ8x8,Rerank4"])
def test_factory_quant_graph_specs_parse_and_roundtrip(spec):
    """Quantized payloads compose with the graph base (the ISSUE 8 grammar
    opening), and parse(str(spec)) round-trips."""
    parsed = api.parse_index_spec(spec)
    assert parsed.base == "hnsw"
    assert api.parse_index_spec(str(parsed)) == parsed


def test_factory_quant_graph_knobs_flow_through():
    idx = api.index_factory("HNSW16,SQ8")
    assert isinstance(idx, api.HNSWIndex)
    assert (idx.m, idx.quant) == (16, "sq8")
    pq = api.index_factory("HNSW16,PQ4x6")
    assert (pq.quant, pq.pq_m, pq.pq_bits) == ("pq", 4, 6)
    # PQ navigation is noisy: the instance over-fetches harder under a
    # rerank, without touching the class-level default
    assert pq.stage1_oversample == 8
    assert api.HNSWIndex.stage1_oversample == 2


def test_hnsw_save_load_roundtrip_with_upper_layers(tmp_path):
    """Force a multi-layer graph (small M -> tall hierarchy) and check the
    adjacency stack round-trips bit-exact."""
    x = synthetic.embedding_corpus(600, 16, n_clusters=4, intrinsic=8,
                                   seed=21)
    idx = api.HNSWIndex(m=4, ef_construction=40, seed=3).build(x)
    assert idx._g.max_level >= 1  # the point of the test
    res = idx.search(x[:16], 5)
    idx.save(str(tmp_path / "g"))
    idx2 = api.load_index(str(tmp_path / "g"))
    assert isinstance(idx2, api.HNSWIndex)
    np.testing.assert_array_equal(idx2._g.links0, idx._g.links0)
    np.testing.assert_array_equal(idx2._g.links, idx._g.links)
    np.testing.assert_array_equal(idx2._g.levels, idx._g.levels)
    assert idx2._g.entry == idx._g.entry
    res2 = idx2.search(x[:16], 5)
    np.testing.assert_array_equal(res2.indices, res.indices)
    check_graph_invariants(idx2._g)


def test_packed_saved_and_loaded_without_repack(tmp_path):
    """Persistence carries the packed dense adjacency + norms: a reloaded
    index has the packed form in hand (no repack) and answers the batched
    path bitwise-identically."""
    x = synthetic.embedding_corpus(400, 16, n_clusters=4, intrinsic=8,
                                   seed=7)
    idx = api.HNSWIndex(m=6, ef_construction=40, seed=2).build(x)
    res = idx.search(x[:8], 5)
    idx.save(str(tmp_path / "g"))
    idx2 = api.load_index(str(tmp_path / "g"))
    p, p2 = idx._g.pack(), idx2._g.packed
    assert p2 is not None, "load must restore the packed form"
    np.testing.assert_array_equal(p2.nbrs0, p.nbrs0)
    np.testing.assert_array_equal(p2.upper, p.upper)
    np.testing.assert_array_equal(p2.vecs_sq, p.vecs_sq)
    res2 = idx2.search(x[:8], 5)
    np.testing.assert_array_equal(res2.indices, res.indices)
    np.testing.assert_array_equal(res2.scores, res.scores)


def test_fingerprint_covers_packed_form_and_engine(corpus):
    """The serving cache keys on fingerprint(): an index serving the
    packed/batched path can never alias one pinned to the ragged
    sequential engine — and packing (a pure derivation of arrays already
    hashed) can never shift an index's identity as a side effect."""
    x = corpus[:400]
    auto = api.HNSWIndex(m=8, ef_construction=40).build(x)
    seq = api.HNSWIndex(m=8, ef_construction=40, batched=False).build(x)
    assert auto.fingerprint() != seq.fingerprint()
    before = seq.fingerprint()
    seq._g.pack()   # e.g. save() packs a sequential-pinned index
    assert seq.fingerprint() == before


def test_pack_is_idempotent_and_correct(graph):
    p1 = graph.pack()
    assert graph.pack() is p1
    np.testing.assert_array_equal(p1.nbrs0, graph.links0)
    np.testing.assert_allclose(
        p1.vecs_sq, (graph.vecs.astype(np.float32) ** 2).sum(1),
        rtol=1e-6)


def test_bytes_per_vector_accounts_links(corpus):
    idx = api.HNSWIndex(m=8, ef_construction=40).build(corpus)
    d = corpus.shape[1]
    # vector + layer-0 slots at least; strictly more than flat storage
    assert idx.bytes_per_vector >= d * 4 + 4 * 2 * 8
    flat = api.FlatIndex().build(corpus)
    assert idx.bytes_per_vector > flat.bytes_per_vector


# ---------------------------------------------------------------------------
# Acceptance: the ISSUE 3 criterion, on the shared 20k fixture
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(900)
def test_acceptance_20k_hnsw_recall_and_sublinearity(tmp_path,
                                                     acceptance_corpus,
                                                     acceptance_queries,
                                                     acceptance_gt):
    """``RAE64,HNSW32,Rerank4`` reaches recall@10 >= 0.9 vs the exact scan
    while evaluating distances on < 10% of the corpus per query (the
    ``distance_evals`` stat), and survives save -> load bit-exact."""
    idx = api.index_factory("RAE64,HNSW32,Rerank4",
                            reducer_kw={"steps": 1000, "seed": 0})
    idx.build(acceptance_corpus)
    res = idx.search(acceptance_queries, 10)
    recall = (acceptance_gt[:, :, None] ==
              res.indices[:, None, :]).any(-1).mean()
    assert recall >= 0.9, recall

    n = acceptance_corpus.shape[0]
    assert res.distance_evals < 0.10 * n, (res.distance_evals, n)
    check_graph_invariants(idx.base._g)

    idx.save(str(tmp_path / "hnsw"))
    res2 = api.load_index(str(tmp_path / "hnsw")).search(acceptance_queries,
                                                         10)
    np.testing.assert_array_equal(res2.indices, res.indices)


@pytest.mark.slow
@pytest.mark.timeout(900)
@pytest.mark.parametrize("quant,floor", [("SQ8", 3.0), ("PQ8x8", 4.0)])
def test_acceptance_20k_quant_graph_recall_and_bytes(tmp_path, quant, floor,
                                                     acceptance_corpus,
                                                     acceptance_queries,
                                                     acceptance_gt):
    """The ISSUE 8 criterion: ``RAE64,HNSW32,<quant>,Rerank4`` holds
    post-rerank recall@10 within 0.01 of the f32 graph twin while the
    traversal gathers >= 3x (SQ8) / >= 4x (PQ8x8) fewer payload bytes per
    hop, and survives save -> load bit-exact."""
    f32 = api.index_factory("RAE64,HNSW32,Rerank4",
                            reducer_kw={"steps": 1000, "seed": 0})
    f32.build(acceptance_corpus)
    f32_res = f32.search(acceptance_queries, 10)
    f32_recall = (acceptance_gt[:, :, None] ==
                  f32_res.indices[:, None, :]).any(-1).mean()

    idx = api.index_factory(f"RAE64,HNSW32,{quant},Rerank4",
                            reducer_kw={"steps": 1000, "seed": 0})
    idx.build(acceptance_corpus)
    res = idx.search(acceptance_queries, 10)
    recall = (acceptance_gt[:, :, None] ==
              res.indices[:, None, :]).any(-1).mean()
    assert recall >= f32_recall - 0.01, (recall, f32_recall)

    ratio = f32_res.stats["gather_bytes_per_hop"] / \
        res.stats["gather_bytes_per_hop"]
    assert ratio >= floor, ratio

    idx.save(str(tmp_path / "qg"))
    idx2 = api.load_index(str(tmp_path / "qg"))
    assert idx2.fingerprint() == idx.fingerprint()
    res2 = idx2.search(acceptance_queries, 10)
    np.testing.assert_array_equal(res2.indices, res.indices)
