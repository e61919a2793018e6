"""Unified retrieval API: registry, factory parsing, persistence, recall."""
import jax
import numpy as np
import pytest

from repro import api
from repro.core import rae as rae_lib
from repro.data import synthetic
from repro.models.common import NULL_CTX
from repro.search import twostage

jax.config.update("jax_platform_name", "cpu")

ALL_REDUCERS = ("pca", "rp", "mds", "isomap", "umap", "rae")


@pytest.fixture(scope="module")
def small_corpus():
    return synthetic.embedding_corpus(1500, 32, n_clusters=8, intrinsic=12,
                                      seed=11)


@pytest.fixture(scope="module")
def queries(small_corpus):
    rng = np.random.default_rng(1)
    picks = rng.integers(0, small_corpus.shape[0], 32)
    return small_corpus[picks] + 0.01 * rng.standard_normal(
        (32, small_corpus.shape[1])).astype(np.float32)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_lists_all_six():
    assert set(ALL_REDUCERS) <= set(api.list_reducers())


@pytest.mark.parametrize("name", ALL_REDUCERS)
def test_registry_constructs_and_reduces(name, small_corpus):
    kw = {"steps": 40} if name == "rae" else {}
    red = api.make_reducer(name, 8, **kw)
    assert red.kind == name
    assert red.out_dim == 8
    assert not red.fitted
    tr = small_corpus[:400]
    red.fit(tr)
    assert red.fitted
    z = red.transform(small_corpus[400:464])
    assert z.shape == (64, 8)
    assert np.all(np.isfinite(z))


def test_registry_unknown_name():
    with pytest.raises(KeyError, match="unknown reducer"):
        api.make_reducer("tsne", 8)


def test_transform_before_fit_raises():
    with pytest.raises(RuntimeError, match="before fit"):
        api.make_reducer("pca", 4).transform(np.zeros((2, 8), np.float32))


# ---------------------------------------------------------------------------
# Factory spec parsing
# ---------------------------------------------------------------------------
def test_parse_full_stack():
    s = api.parse_index_spec("RAE64,IVF256,Rerank4")
    assert s == api.IndexSpec(reducer="rae", out_dim=64, base="ivf",
                              n_cells=256, rerank_factor=4)


def test_parse_case_insensitive_and_defaults():
    s = api.parse_index_spec("pca32,flat")
    assert s.reducer == "pca" and s.out_dim == 32
    assert s.base == "flat" and s.rerank_factor == 1
    assert api.parse_index_spec("Flat") == api.IndexSpec()
    assert api.parse_index_spec("IVF64").n_cells == 64


@pytest.mark.parametrize("bad", [
    "", " ,Flat", "RAE64", "Rerank4", "Flat,Flat", "IVF", "Flat9",
    "Bogus64,Flat", "Flat,Rerank4", "Flat,PCA32", "RAE64,PCA32,Flat",
    "RAE64,Rerank4,Flat", "RAE64,Flat,Rerank4,Rerank2", "RAE,Flat",
])
def test_parse_rejects_invalid(bad):
    with pytest.raises(ValueError, match="bad index spec"):
        api.parse_index_spec(bad)


# ---------------------------------------------------------------------------
# Spec round-trip: every registered grammar form renders back canonically
# ---------------------------------------------------------------------------
# One spec per registered grammar form (base x quant x reducer x rerank).
ALL_SPEC_FORMS = [
    "Flat", "IVF32", "HNSW8", "SQ8", "PQ4x8", "Flat,SQ8",
    "IVF32,SQ8", "IVF32,PQ4x8",
    "PCA8,Flat", "PCA8,IVF32,Rerank2", "PCA8,HNSW8,Rerank2",
    "PCA8,SQ8,Rerank2", "PCA8,PQ4x8,Rerank2", "PCA8,IVF32,PQ4x8,Rerank2",
    "RAE8,Flat,Rerank2",
]


@pytest.mark.parametrize("spec", ALL_SPEC_FORMS)
def test_parse_str_roundtrip_idempotent(spec):
    """``str(parsed)`` is a canonical spec: re-parsing it is a fixed
    point, in both the parsed and the rendered domain."""
    parsed = api.parse_index_spec(spec)
    assert api.parse_index_spec(str(parsed)) == parsed
    assert str(api.parse_index_spec(str(parsed))) == str(parsed)


def test_factory_builds_each_shape(small_corpus, queries):
    for spec, cls in [("Flat", api.FlatIndex),
                      ("IVF32", api.IVFFlatIndex),
                      ("HNSW8", api.HNSWIndex),
                      ("PCA8,Flat", api.TwoStageIndex)]:
        idx = api.index_factory(spec, index_kw={"ef_construction": 40}
                                if "HNSW" in spec else None)
        assert isinstance(idx, cls)
        idx.build(small_corpus)
        res = idx.search(queries, 5)
        assert isinstance(res, api.SearchResult)
        assert res.indices.shape == (32, 5) and res.k == 5
        assert res.latency_s > 0


# ---------------------------------------------------------------------------
# Persistence round-trips
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ALL_REDUCERS)
def test_reducer_save_load_roundtrip(name, small_corpus, queries, tmp_path):
    kw = {"steps": 40} if name == "rae" else {}
    red = api.make_reducer(name, 6, **kw).fit(small_corpus[:400])
    z = red.transform(queries)
    red.save(str(tmp_path / name))
    red2 = api.load_reducer(str(tmp_path / name))
    assert red2.kind == name and red2.fitted
    np.testing.assert_allclose(red2.transform(queries), z, rtol=1e-6)


@pytest.mark.parametrize("spec", [
    "Flat", "IVF32", "HNSW8", "SQ8", "PQ4x8", "IVF32,SQ8", "IVF32,PQ4x8",
    "RAE8,IVF32,Rerank2", "PCA8,HNSW8,Rerank2",
])
def test_index_save_load_roundtrip(spec, small_corpus, queries, tmp_path):
    """Every registered spec form: save -> load -> search returns
    identical ids (and scores) on a fixed corpus."""
    reducer_kw = {"steps": 40} if spec.startswith("RAE") else None
    index_kw = {"ef_construction": 60} if "HNSW" in spec else None
    idx = api.index_factory(spec, reducer_kw=reducer_kw, index_kw=index_kw)
    idx.build(small_corpus)
    res = idx.search(queries, 5)
    idx.save(str(tmp_path / "idx"))
    idx2 = api.load_index(str(tmp_path / "idx"))
    assert idx2.ntotal == idx.ntotal
    res2 = idx2.search(queries, 5)
    np.testing.assert_array_equal(res2.indices, res.indices)
    np.testing.assert_allclose(res2.scores, res.scores, rtol=1e-6)


def test_twostage_ivf_padding_never_outranks_real(queries):
    """IVF pads short results with id -1; the rerank must pin those to
    -inf so a pad can never beat a real candidate."""
    tiny = synthetic.embedding_corpus(200, 32, n_clusters=8, intrinsic=12,
                                      seed=3)
    # cap = ceil(2.5 * 200 / 64) = 8 per cell; nprobe=8 probes hold at most
    # 64 rows < k1 = 10 * 16 = 160, so stage 1 is guaranteed to pad.
    idx = api.TwoStageIndex(api.make_reducer("pca", 8),
                            api.IVFFlatIndex(n_cells=64, nprobe=8),
                            rerank_factor=16)
    idx.build(tiny)
    res = idx.search(queries, 10)
    valid = res.indices >= 0
    assert np.all(np.isfinite(res.scores[valid]))
    assert np.all(np.isneginf(res.scores[~valid]))
    # every real neighbor in the probed cells must rank above every pad
    assert not np.any(valid[:, 1:] & ~valid[:, :-1])


def test_ivf_probe_bytes_and_answers(small_corpus, queries):
    """``probe_bytes`` is the whole member blocks of the probed cells up
    to each extent, clipped at the store's width; the answers are the
    exact top-k of the probed cells' members."""
    from repro.kernels.ivf_scan.kernel import block_cols

    idx = api.IVFFlatIndex(n_cells=16, nprobe=4).build(small_corpus)
    res = idx.search(queries, 10)
    ivf = idx._ivf
    cent = np.asarray(ivf.centroids)
    d2c = ((queries[:, None, :] - cent[None]) ** 2).sum(-1)
    cells = np.argsort(d2c, axis=1)[:, :4]
    _, depth, width = ivf.list_vecs.shape
    block = block_cols(width, depth)
    sizes = np.asarray(ivf.extent)[cells]
    read = np.minimum(np.maximum(-(-sizes // block), 1) * block, width)
    assert res.stats["probe_bytes"] == pytest.approx(
        (read * depth * 4).sum(axis=1).mean())
    assert res.distance_evals == pytest.approx(sizes.sum(axis=1).mean())
    lists = np.asarray(ivf.lists)
    for qi, q in enumerate(queries):
        members = lists[cells[qi]].ravel()
        members = members[members >= 0]
        dist = ((small_corpus[members] - q) ** 2).sum(-1)
        order = np.argsort(dist, kind="stable")[:10]
        np.testing.assert_array_equal(res.indices[qi], members[order])
        np.testing.assert_allclose(-res.scores[qi], dist[order], rtol=1e-4,
                                   atol=1e-4)


def test_ivf_fingerprint_hashes_member_rows(small_corpus):
    """The IVF fingerprint hashes the member rows [C, cap, d] (the saved
    form), not the device store, whose layout may pad and transpose."""
    idx = api.IVFFlatIndex(n_cells=16, nprobe=4).build(small_corpus)
    rows = idx._fingerprint_state()[-1]
    lists = np.asarray(idx._ivf.lists)
    assert rows.shape == lists.shape + (small_corpus.shape[1],)
    live = lists >= 0
    np.testing.assert_array_equal(rows[live], small_corpus[lists[live]])


def test_twostage_fits_reducer_without_fitted_attr(small_corpus, queries):
    """A minimal third-party Reducer (no `fitted` attribute) must be fitted
    by build, not silently skipped."""

    class Halver:
        kind = "halver"
        out_dim = 16

        def __init__(self):
            self.fit_calls = 0

        def fit(self, x):
            self.fit_calls += 1
            return self

        def transform(self, x):
            return np.asarray(x, np.float32)[:, :self.out_dim]

        def save(self, directory):
            raise NotImplementedError

    red = Halver()
    idx = api.TwoStageIndex(red, api.FlatIndex(), rerank_factor=2)
    idx.build(small_corpus)
    assert red.fit_calls == 1
    assert idx.search(queries, 5).indices.shape == (32, 5)


def test_pretrained_reducer_plugs_in(small_corpus, queries):
    """A reducer fitted elsewhere is NOT refit by TwoStageIndex.build."""
    red = api.make_reducer("pca", 8).fit(small_corpus[:500])
    w_before = red._impl.components_.copy()
    idx = api.TwoStageIndex(red, api.FlatIndex(), rerank_factor=2)
    idx.build(small_corpus)
    np.testing.assert_array_equal(red._impl.components_, w_before)
    assert idx.search(queries, 5).indices.shape == (32, 5)


# ---------------------------------------------------------------------------
# Recall parity with the legacy two-stage path
# ---------------------------------------------------------------------------
def test_twostage_matches_legacy_two_stage_search(small_corpus, queries):
    import jax.numpy as jnp

    red = api.make_reducer("rae", 8, steps=120, seed=0).fit(small_corpus)
    idx = api.TwoStageIndex(red, api.FlatIndex(), rerank_factor=4)
    idx.build(small_corpus)
    res = idx.search(queries, 10)

    db = jnp.asarray(small_corpus)
    db_red = twostage.encode_corpus(red.params_, db, NULL_CTX)
    _, legacy_idx = twostage.two_stage_search(
        jnp.asarray(queries), db, db_red, red.params_, 10, NULL_CTX,
        rerank_factor=4)
    overlap = (res.indices[:, :, None] ==
               np.asarray(legacy_idx)[:, None, :]).any(-1).mean()
    assert overlap >= 0.999


def test_rae_reducer_encode_matches_core(small_corpus, queries):
    import jax.numpy as jnp

    red = api.make_reducer("rae", 8, steps=40).fit(small_corpus[:400])
    z_api = red.transform(queries)
    z_core = np.asarray(rae_lib.encode(red.params_, jnp.asarray(queries)))
    np.testing.assert_allclose(z_api, z_core, rtol=1e-6)


# ---------------------------------------------------------------------------
# Acceptance: 20k x 256, both factory stacks, recall@10 >= 0.9, save+reload
# (corpus/queries/ground truth are the session-scoped conftest fixtures,
# shared with the quantized and graph acceptance tests)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(900)
@pytest.mark.parametrize("spec", ["RAE64,Flat,Rerank4", "RAE64,IVF256,Rerank4"])
def test_acceptance_20k_recall(spec, tmp_path, acceptance_corpus,
                               acceptance_queries, acceptance_gt):
    idx = api.index_factory(spec, reducer_kw={"steps": 1000, "seed": 0})
    idx.build(acceptance_corpus)
    res = idx.search(acceptance_queries, 10)
    recall = (acceptance_gt[:, :, None] ==
              res.indices[:, None, :]).any(-1).mean()
    assert recall >= 0.9, (spec, recall)

    idx.save(str(tmp_path / "acc"))
    res2 = api.load_index(str(tmp_path / "acc")).search(acceptance_queries, 10)
    np.testing.assert_array_equal(res2.indices, res.indices)
