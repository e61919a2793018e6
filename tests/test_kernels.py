"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Two layers of coverage:
* per-kernel happy-path sweeps + equivalence with the model/engine code
  that the kernel replaces (the original suite);
* a shared PARITY HARNESS (bottom of file) that drives EVERY kernel triple
  through its ragged/odd shapes — row counts not divisible by the block
  size, k larger than the candidate pool, degenerate d=1 — in both f32 and
  bf16. Kernels historically break exactly at those pad/edge paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.kernels import (embedding_bag, flash_decode, graph_beam,
                           graph_beam_q, ivf_scan, l2_topk, pq_adc,
                           rae_encode, topk_merge)
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.kernels.graph_beam.ref import NEG_INF, graph_beam_ref
from repro.kernels.graph_beam_q.ref import graph_beam_q_ref
from repro.kernels.ivf_scan.kernel import block_cols, store_shape
from repro.kernels.ivf_scan.ref import ivf_scan_ref
from repro.kernels.l2_topk.ref import l2_topk_ref
from repro.kernels.pq_adc.ref import pq_adc_ref
from repro.kernels.rae_encode.ref import rae_encode_ref
from repro.kernels.topk_merge.ref import topk_merge_ref

jax.config.update("jax_platform_name", "cpu")


def _arr(seed, shape, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# l2_topk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,n,d,k", [
    (32, 256, 32, 5), (100, 1000, 64, 10), (17, 513, 48, 7),
    (128, 2048, 128, 32),
])
@pytest.mark.parametrize("metric", ["euclidean", "cosine"])
def test_l2_topk_sweep(q, n, d, k, metric):
    qs = _arr(q + n, (q, d))
    db = _arr(n, (n, d))
    v, i = l2_topk(qs, db, k, metric=metric, impl="pallas", bq=32, bn=128,
                   interpret=True)
    if metric == "cosine":
        qn = qs / jnp.linalg.norm(qs, axis=-1, keepdims=True)
        dn = db / jnp.linalg.norm(db, axis=-1, keepdims=True)
        vr, ir = l2_topk_ref(qn, dn, k, metric)
    else:
        vr, ir = l2_topk_ref(qs, db, k, metric)
    assert float((i == ir).mean()) > 0.999  # ties may swap, values must match
    np.testing.assert_allclose(np.sort(v, 1), np.sort(vr, 1),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_topk_dtypes(dtype):
    qs = _arr(1, (32, 64), dtype)
    db = _arr(2, (512, 64), dtype)
    v, i = l2_topk(qs, db, 8, impl="pallas", bq=32, bn=128, interpret=True)
    vr, ir = l2_topk_ref(qs, db, 8)
    assert float((i == ir).mean()) > 0.97  # bf16 rounding can reorder ties


def test_l2_topk_matches_search_engine():
    from repro.models.common import NULL_CTX
    from repro.search import search

    qs = _arr(5, (16, 32))
    db = _arr(6, (300, 32))
    v, i = l2_topk(qs, db, 5, impl="ref")
    sv, si = search(qs, db, 5, NULL_CTX)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(si))


# ---------------------------------------------------------------------------
# rae_encode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows,n,m", [(256, 512, 128), (300, 768, 96),
                                      (64, 384, 192), (1000, 1024, 256)])
@pytest.mark.parametrize("normalize", [True, False])
def test_rae_encode_sweep(rows, n, m, normalize):
    x = _arr(rows, (rows, n))
    w = _arr(n, (n, m)) * 0.05
    z = rae_encode(x, w, normalize=normalize, impl="pallas", br=64, bk=128,
                   interpret=True)
    zr = rae_encode_ref(x, w, normalize)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), rtol=1e-4,
                               atol=1e-5)


def test_rae_encode_matches_model_encode():
    from repro.configs import RAEConfig
    from repro.core import rae as rae_lib

    cfg = RAEConfig(in_dim=64, out_dim=16)
    params = rae_lib.init(cfg, jax.random.PRNGKey(0))
    x = _arr(9, (128, 64))
    z_kernel = rae_encode(x, params["w_e"], normalize=False, impl="pallas",
                          br=64, bk=64, interpret=True)
    z_model = rae_lib.encode(params, x)
    np.testing.assert_allclose(np.asarray(z_kernel), np.asarray(z_model),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,kh,g,dh,s,cur", [
    (2, 2, 4, 16, 64, 37), (4, 4, 1, 32, 128, 128), (1, 1, 8, 64, 256, 1),
    (3, 8, 2, 16, 96, 50),
])
def test_flash_decode_sweep(b, kh, g, dh, s, cur):
    q = _arr(b, (b, kh, g, dh))
    kc = _arr(b + 1, (b, s, kh, dh))
    vc = _arr(b + 2, (b, s, kh, dh))
    o = flash_decode(q, kc, vc, cur, impl="pallas", bs=32, interpret=True)
    orf = flash_decode_ref(q, kc, vc, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), rtol=2e-4,
                               atol=2e-5)


def test_flash_decode_matches_model_decode_attention():
    """Kernel == the shard-local math of attention.decode_attention."""
    from repro.models.common import NULL_CTX
    from repro.models.transformer import attention as attn

    b, kh, g, dh, s = 2, 2, 3, 16, 32
    h = kh * g
    q = _arr(0, (b, h, dh))
    kc = _arr(1, (b, s, kh, dh))
    vc = _arr(2, (b, s, kh, dh))
    kn = _arr(3, (b, kh, dh))
    vn = _arr(4, (b, kh, dh))
    cur = jnp.asarray(20, jnp.int32)
    out, k2, v2 = attn.decode_attention(q, kc, vc, kn, vn, cur, NULL_CTX)
    # reference: write new kv at position cur, then kernel over cur+1
    kc2 = kc.at[:, 20].set(kn)
    vc2 = vc.at[:, 20].set(vn)
    o_k = flash_decode(q.reshape(b, kh, g, dh), kc2, vc2, 21, impl="pallas",
                       bs=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(b, kh, g, dh),
                               np.asarray(o_k), rtol=3e-3, atol=3e-4)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(kc2), atol=1e-6)


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("v,d,b,l", [(50, 32, 8, 6), (1000, 16, 32, 20),
                                     (128, 64, 4, 3)])
@pytest.mark.parametrize("mode", ["mean", "sum"])
def test_embedding_bag_sweep(v, d, b, l, mode):
    tbl = _arr(v, (v, d))
    rng = np.random.default_rng(v + b)
    ids = jnp.asarray(rng.integers(0, v, (b, l)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, l + 1, (b,)), jnp.int32)
    eb = embedding_bag(tbl, ids, lens, mode=mode, impl="pallas",
                       interpret=True)
    ebr = embedding_bag_ref(tbl, ids, lens, mode)
    np.testing.assert_allclose(np.asarray(eb), np.asarray(ebr), rtol=1e-5,
                               atol=1e-5)


def test_embedding_bag_matches_model_path():
    from repro.models.common import NULL_CTX, embedding_bag as model_bag

    tbl = _arr(7, (64, 8))
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 64, (16, 5)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, 6, (16,)), jnp.int32)
    a = embedding_bag(tbl, ids, lens, impl="pallas", interpret=True)
    bq = model_bag(tbl, ids, lens, NULL_CTX, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bq), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# pq_adc
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,n,m,ksub,dsub,k", [
    (32, 512, 8, 256, 4, 10), (16, 200, 4, 16, 8, 5), (8, 1024, 2, 64, 16, 32),
])
def test_pq_adc_sweep(q, n, m, ksub, dsub, k):
    rng = np.random.default_rng(q + n)
    qs = jnp.asarray(rng.normal(size=(q, m * dsub)), jnp.float32)
    cb = jnp.asarray(rng.normal(size=(m, ksub, dsub)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, ksub, (n, m)), jnp.int32)
    v, i = pq_adc(qs, cb, codes, k, impl="pallas", bq=32, bn=128,
                  interpret=True)
    vr, ir = pq_adc_ref(qs, cb, codes, k)
    assert float((i == ir).mean()) > 0.999  # ties may swap
    np.testing.assert_allclose(np.sort(v, 1), np.sort(vr, 1), rtol=2e-4,
                               atol=2e-4)


def test_pq_adc_matches_engine_ivfpq_on_one_cell():
    """Kernel == the engine's LUT-gather math (search.quantize) when the
    'IVF' is a single cell holding the whole corpus."""
    from repro.search import quantize as qz

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(300, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    pq = qz.pq_train(x, m=4, bits=6, iters=6, seed=0)
    codes = qz.pq_encode(pq, x)
    v, i = pq_adc(q, pq.codebooks, codes, 7, impl="pallas", bq=16, bn=64,
                  interpret=True)
    dist = qz.pq_adc_gather(qz.pq_adc_lut(pq, q), codes)
    ve, ie = jax.lax.top_k(-dist, 7)
    np.testing.assert_allclose(np.asarray(v), np.asarray(ve), rtol=1e-4,
                               atol=1e-4)
    assert float((i == ie).mean()) > 0.999


# ---------------------------------------------------------------------------
# graph_beam
# ---------------------------------------------------------------------------
def _beam_case(seed, q_n, n, d, w, ef, dtype=jnp.float32, seed_beam=2):
    """Random hop inputs: queries, db, ids (some masked -1), and a sorted-
    descending beam with ``seed_beam`` live entries."""
    rng = np.random.default_rng(seed)
    qs = jnp.asarray(rng.standard_normal((q_n, d)), dtype)
    db = jnp.asarray(rng.standard_normal((n, d)), dtype)
    ids = jnp.asarray(rng.integers(-1, n, (q_n, w)), jnp.int32)
    bv = np.full((q_n, ef), NEG_INF, np.float32)
    bi = np.full((q_n, ef), -1, np.int32)
    for s in range(min(seed_beam, ef)):
        bv[:, s] = -0.25 * (s + 1)   # sorted descending
        bi[:, s] = s
    return qs, db, ids, jnp.asarray(bv), jnp.asarray(bi)


@pytest.mark.parametrize("q_n,n,d,w,ef", [
    (8, 64, 16, 9, 7), (1, 40, 8, 5, 12), (16, 128, 32, 16, 10),
])
def test_graph_beam_sweep(q_n, n, d, w, ef):
    qs, db, ids, bv, bi = _beam_case(q_n + n, q_n, n, d, w, ef)
    got = graph_beam(qs, db, ids, bv, bi, impl="pallas", interpret=True)
    want = graph_beam_ref(qs, db, ids, bv, bi)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)
    # merged beam stays sorted descending with pads at the tail
    v = np.asarray(want[0])
    assert np.all(np.diff(v, axis=1) <= 1e-6)
    assert np.all(v[np.asarray(want[1]) < 0] == NEG_INF)


# ---------------------------------------------------------------------------
# graph_beam_q: the quantized hop (SQ8 / PQ payloads)
# ---------------------------------------------------------------------------
def _beam_q_case(seed, mode, q_n, n, cdim, ksub, w, ef, dtype=jnp.float32,
                 seed_beam=2):
    """Random quantized hop inputs. ``cdim`` = stored code width (sq8: d;
    pq: m), ``ksub`` = LUT stride (pq only; codes stay < ksub, modelling
    the tiny-corpus clamp when ksub < 256)."""
    rng = np.random.default_rng(seed)
    hi = 256 if mode == "sq8" else ksub
    codes = jnp.asarray(rng.integers(0, hi, (n, cdim)), jnp.uint8)
    dop = cdim if mode == "sq8" else cdim * ksub
    q_op = jnp.asarray(0.1 * rng.standard_normal((q_n, dop)), dtype)
    q_bias = jnp.asarray(rng.standard_normal(q_n), dtype)
    node_bias = jnp.asarray(np.abs(rng.standard_normal(n)), dtype)
    ids = jnp.asarray(rng.integers(-1, n, (q_n, w)), jnp.int32)
    bv = np.full((q_n, ef), NEG_INF, np.float32)
    bi = np.full((q_n, ef), -1, np.int32)
    for s in range(min(seed_beam, ef)):
        bv[:, s] = -0.25 * (s + 1)   # sorted descending
        bi[:, s] = s
    return q_op, q_bias, codes, node_bias, ids, jnp.asarray(bv), \
        jnp.asarray(bi)


def test_graph_beam_q_rejects_bad_mode_and_ksub():
    a = _beam_q_case(0, "sq8", 2, 10, 4, 0, 3, 4)
    with pytest.raises(ValueError, match="mode"):
        graph_beam_q(*a, mode="fp4")
    with pytest.raises(ValueError, match="ksub"):
        graph_beam_q(*a, mode="pq", ksub=0)


def test_graph_beam_q_sq8_matches_decoded_f32_hop():
    """The dequant-free affine form == the f32 hop on decoded rows: build
    real SQ8 operands from a real codec and cross-check against
    graph_beam over decode(codes)."""
    from repro.search import hnsw as hnsw_lib

    rng = np.random.default_rng(11)
    x = rng.standard_normal((60, 12)).astype(np.float32)
    cdx = hnsw_lib.make_graph_codes(x, "sq8")
    q = rng.standard_normal((5, 12)).astype(np.float32)
    q_sq = (q * q).sum(1).astype(np.float32)
    q_op, q_bias = cdx.query_operands(q, q_sq)
    ids = jnp.asarray(rng.integers(-1, 60, (5, 7)), jnp.int32)
    bv = jnp.full((5, 6), NEG_INF, jnp.float32)
    bi = jnp.full((5, 6), -1, jnp.int32)
    got = graph_beam_q(q_op, q_bias, cdx.codes, cdx.node_bias, ids, bv, bi,
                       mode="sq8", impl="np")
    from repro.search.quantize import ScalarQuantizer, sq8_decode
    dec = np.asarray(sq8_decode(
        ScalarQuantizer(vmin=jnp.asarray(cdx.vmin),
                        step=jnp.asarray(cdx.step)),
        jnp.asarray(cdx.codes)))
    want = graph_beam_ref(jnp.asarray(q), jnp.asarray(dec), ids, bv, bi)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


def test_graph_beam_merge_matches_traversal_semantics():
    """A full-corpus hop against an empty beam is exact top-ef — pin the
    merge to l2_topk's ordering (same branchless merge, same tie rule)."""
    rng = np.random.default_rng(3)
    qs = jnp.asarray(rng.standard_normal((4, 12)), jnp.float32)
    db = jnp.asarray(rng.standard_normal((50, 12)), jnp.float32)
    ids = jnp.tile(jnp.arange(50, dtype=jnp.int32), (4, 1))
    bv = jnp.full((4, 8), NEG_INF, jnp.float32)
    bi = jnp.full((4, 8), -1, jnp.int32)
    v, i = graph_beam(qs, db, ids, bv, bi, impl="np")
    lv, li = l2_topk(qs, db, 8, impl="ref")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(li))
    np.testing.assert_allclose(np.asarray(v), np.asarray(lv), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# Shared ragged/odd-shape parity harness: every kernel triple, both dtypes
# ---------------------------------------------------------------------------
def _tol(dtype):
    """(rtol, atol, min index agreement). All refs compute in f32 after
    casting, so bf16 slack only covers input rounding + reassociation."""
    return (2e-4, 2e-4, 0.999) if dtype == jnp.float32 else (3e-2, 3e-2, 0.9)


def _topk_parity(got, want, dtype, k_valid=None):
    """Compare (scores, indices) pairs; ties may swap, values must match."""
    rtol, atol, imatch = _tol(dtype)
    v, i = np.asarray(got[0]), np.asarray(got[1])
    vr, ir = np.asarray(want[0]), np.asarray(want[1])
    if k_valid is not None:  # the k > n tail must be -inf / -1 padding
        assert np.all(np.isneginf(v[:, k_valid:]))
        assert np.all(i[:, k_valid:] == -1)
        v, i, vr, ir = v[:, :k_valid], i[:, :k_valid], vr[:, :k_valid], \
            ir[:, :k_valid]
    assert float((i == ir).mean()) >= imatch
    np.testing.assert_allclose(np.sort(v, 1), np.sort(vr, 1), rtol=rtol,
                               atol=atol)


def _parity_l2_topk(case, dtype):
    q_n, n, d, k, bq, bn = case
    qs = _arr(q_n + n, (q_n, d), dtype)
    db = _arr(n, (n, d), dtype)
    got = l2_topk(qs, db, k, impl="pallas", bq=bq, bn=bn, interpret=True)
    _topk_parity(got, l2_topk_ref(qs, db, k), dtype)


def _parity_rae_encode(case, dtype):
    rows, n, m, br, bk = case
    x = _arr(rows, (rows, n), dtype)
    w = _arr(n, (n, m), dtype) * 0.05
    z = rae_encode(x, w, normalize=True, impl="pallas", br=br, bk=bk,
                   interpret=True)
    rtol, atol, _ = _tol(dtype)
    np.testing.assert_allclose(np.asarray(z),
                               np.asarray(rae_encode_ref(x, w, True)),
                               rtol=rtol, atol=atol)


def _parity_flash_decode(case, dtype):
    b, kh, g, dh, s, cur, bs = case
    q = _arr(b, (b, kh, g, dh), dtype)
    kc = _arr(b + 1, (b, s, kh, dh), dtype)
    vc = _arr(b + 2, (b, s, kh, dh), dtype)
    o = flash_decode(q, kc, vc, cur, impl="pallas", bs=bs, interpret=True)
    rtol, atol, _ = _tol(dtype)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(flash_decode_ref(q, kc, vc, cur),
                                          np.float32),
                               rtol=max(rtol, 3e-3), atol=max(atol, 3e-4))


def _parity_embedding_bag(case, dtype):
    v_n, d, b, l = case
    tbl = _arr(v_n, (v_n, d), dtype)
    rng = np.random.default_rng(v_n + b)
    ids = jnp.asarray(rng.integers(0, v_n, (b, l)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, l + 1, (b,)), jnp.int32)
    eb = embedding_bag(tbl, ids, lens, mode="mean", impl="pallas",
                       interpret=True)
    rtol, atol, _ = _tol(dtype)
    np.testing.assert_allclose(np.asarray(eb, np.float32),
                               np.asarray(embedding_bag_ref(tbl, ids, lens,
                                                            "mean"),
                                          np.float32),
                               rtol=rtol, atol=atol)


def _parity_graph_beam(case, dtype):
    q_n, n, d, w, ef = case
    qs, db, ids, bv, bi = _beam_case(q_n + n + d, q_n, n, d, w, ef, dtype)
    got = graph_beam(qs, db, ids, bv, bi, impl="pallas", interpret=True)
    want = graph_beam_ref(qs, db, ids, bv, bi)
    rtol, atol, imatch = _tol(dtype)
    assert float((np.asarray(got[1]) == np.asarray(want[1])).mean()) >= imatch
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=rtol, atol=atol)


def _parity_graph_beam_q(case, dtype):
    mode, q_n, n, cdim, ksub, w, ef = case
    a = _beam_q_case(q_n * 7 + n + cdim, mode, q_n, n, cdim, ksub, w, ef,
                     dtype)
    kw = {"mode": mode, "ksub": ksub if mode == "pq" else 0}
    got = graph_beam_q(*a, impl="pallas", interpret=True, **kw)
    want = graph_beam_q_ref(*a, **kw)
    rtol, atol, imatch = _tol(dtype)
    assert float((np.asarray(got[1]) == np.asarray(want[1])).mean()) >= imatch
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=rtol, atol=atol)


def _parity_pq_adc(case, dtype):
    q_n, n, m, ksub, dsub, k, bq, bn = case
    rng = np.random.default_rng(q_n + n)
    qs = jnp.asarray(rng.normal(size=(q_n, m * dsub)), dtype)
    cb = jnp.asarray(rng.normal(size=(m, ksub, dsub)), dtype)
    codes = jnp.asarray(rng.integers(0, ksub, (n, m)), jnp.int32)
    got = pq_adc(qs, cb, codes, k, impl="pallas", bq=bq, bn=bn,
                 interpret=True)
    want = pq_adc_ref(qs, cb, codes, min(k, n))
    _topk_parity(got, want, dtype, k_valid=min(k, n) if k > n else None)


def _parity_topk_merge(case, dtype):
    q_n, c, k, bq = case
    rng = np.random.default_rng(q_n + c + k)
    vals = jnp.asarray(rng.integers(-4, 4, (q_n, c)), dtype)  # dense ties
    ids = np.stack([rng.permutation(4 * c)[:c].astype(np.int32)
                    for _ in range(q_n)])  # unique per row (merge contract)
    ids[rng.random((q_n, c)) < 0.15] = -1  # scattered pad slots
    ids[0] = -1                            # fully drained row
    ids = jnp.asarray(ids)
    got = topk_merge(vals, ids, k, impl="pallas", bq=bq, interpret=True)
    want = topk_merge_ref(jnp.asarray(vals, jnp.float32), ids, k)
    # the id tie-break makes the merge a total order: bitwise, not
    # tolerance, parity — and exactly the shard-count-invariance contract
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    v, i = np.asarray(got[0]), np.asarray(got[1])
    kv = min(k, c)  # the k > c tail (and drained rows) is canonical padding
    assert np.all(v[:, kv:] == NEG_INF) and np.all(i[:, kv:] == -1)
    assert np.all(i[0] == -1) and np.all(v[0] == NEG_INF)
    assert np.all(v[i >= 0] > NEG_INF)  # live slots never carry pad scores


def _parity_ivf_scan(case, dtype):
    q_n, n_probe, c, cap, d = case
    rng = np.random.default_rng(q_n + c + cap + d)
    shape = store_shape(c, cap, d)
    lv = _arr(c + cap, shape, dtype).at[:, d:].set(0)  # pad features are 0
    qs = _arr(q_n, (q_n, d), dtype)
    block = block_cols(shape[2], shape[1])
    ext = rng.integers(0, cap + 1, c)
    # an empty cell, a full one (its last block partial where the width is
    # not a block multiple) and an extent inside a block, all probed by
    # query 0
    ext[:3] = 0, cap, min(block + 3, cap - 1)
    ext = jnp.asarray(ext, jnp.int32)
    cells = np.stack([rng.permutation(c)[:n_probe] for _ in range(q_n)])
    cells[0, :3] = 0, 1, 2
    cells = jnp.asarray(cells, jnp.int32)
    got = np.asarray(ivf_scan(qs, cells, ext, lv, impl="pallas",
                              interpret=True))
    want = np.asarray(ivf_scan_ref(qs, cells, ext, lv))
    assert got.shape == want.shape == (q_n, n_probe, shape[2])
    dead = (np.arange(shape[2])
            >= np.asarray(ext)[np.asarray(cells)][..., None])
    assert np.array_equal(np.isneginf(got), dead)
    assert np.array_equal(np.isneginf(want), dead)
    np.testing.assert_allclose(got[~dead], want[~dead], rtol=2e-5,
                               atol=2e-4)


def _parity_ivf_scan_alive(case, dtype):
    """A tombstone inside a cell's prefix folds into the list mask only:
    the scan still reads the cell to its extent, so the live rows behind
    the tombstone surface, on the Pallas path as on the ref."""
    n, d, n_cells = case
    rng = np.random.default_rng(n + d)
    corpus = rng.standard_normal((n, d)).astype(np.float32)
    probe = api.IVFFlatIndex(n_cells=n_cells, nprobe=2).build(corpus)
    ivf = probe._ivf
    cell = int(np.argmax(np.asarray(ivf.extent)))
    members = np.asarray(ivf.lists)[cell, :int(ivf.extent[cell])]
    first, behind = int(members[0]), int(members[-1])
    alive = np.ones(n, bool)
    alive[first] = False
    q = np.asarray(jnp.asarray(corpus[[behind, first]], dtype), np.float32)
    ref = probe.search(q, 5, alive=alive)
    traced = []

    def pallas_scan(*args):
        traced.append(True)
        return ivf_scan(*args, impl="pallas", interpret=True)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.search.ivf.ivf_scan", pallas_scan)
        mp.delattr(probe, "_probe")  # the cached jit traced the ref path
        got = probe.search(q, 5, alive=alive)
    assert traced
    assert got.indices[0, 0] == behind
    assert first not in got.indices
    np.testing.assert_array_equal(got.indices, ref.indices)
    np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-5, atol=1e-4)


# case ids name the edge they exercise; every kernel gets n-not-divisible-
# by-block, a k/cur overflow variant where meaningful, and d=1.
PARITY_CASES = [
    ("l2_topk", "ragged_n", (32, 333, 16, 5, 32, 128), _parity_l2_topk),
    ("l2_topk", "ragged_q", (19, 256, 16, 5, 32, 128), _parity_l2_topk),
    ("l2_topk", "d1", (16, 100, 1, 3, 16, 32), _parity_l2_topk),
    ("rae_encode", "ragged_rows", (77, 64, 16, 64, 64), _parity_rae_encode),
    ("rae_encode", "ragged_k", (64, 129, 16, 64, 128), _parity_rae_encode),
    ("rae_encode", "d1", (32, 1, 8, 32, 128), _parity_rae_encode),
    ("flash_decode", "ragged_s", (2, 2, 2, 8, 50, 37, 32),
     _parity_flash_decode),
    ("flash_decode", "cur1", (1, 1, 4, 8, 64, 1, 32), _parity_flash_decode),
    ("flash_decode", "dh1", (2, 1, 2, 1, 33, 20, 16), _parity_flash_decode),
    ("embedding_bag", "odd_shapes", (13, 5, 7, 3), _parity_embedding_bag),
    ("embedding_bag", "d1", (10, 1, 4, 5), _parity_embedding_bag),
    ("pq_adc", "ragged_n", (17, 337, 4, 16, 4, 5, 32, 128), _parity_pq_adc),
    ("pq_adc", "k_gt_n", (4, 6, 2, 4, 2, 10, 8, 8), _parity_pq_adc),
    ("pq_adc", "d1", (8, 64, 1, 8, 1, 3, 8, 32), _parity_pq_adc),
    # (q_n, n, d, w, ef): ragged q (pow2 row pad), 1-wide hop (the greedy-
    # descent shape), ef wider than the candidate pool, d=1
    ("graph_beam", "ragged_q", (7, 60, 16, 9, 8), _parity_graph_beam),
    ("graph_beam", "w1", (5, 30, 8, 1, 6), _parity_graph_beam),
    ("graph_beam", "ef_gt_w", (3, 20, 4, 3, 15), _parity_graph_beam),
    ("graph_beam", "d1", (4, 25, 1, 5, 4), _parity_graph_beam),
    # (mode, q_n, n, cdim, ksub, w, ef): quantized hop — same edges as
    # graph_beam per codec, plus ksub < 2**bits (the tiny-corpus clamp)
    # and the pq m=1 single-subspace shape
    ("graph_beam_q", "sq8_ragged_q", ("sq8", 7, 60, 16, 0, 9, 8),
     _parity_graph_beam_q),
    ("graph_beam_q", "sq8_w1", ("sq8", 5, 30, 8, 0, 1, 6),
     _parity_graph_beam_q),
    ("graph_beam_q", "sq8_ef_gt_w", ("sq8", 3, 20, 4, 0, 3, 15),
     _parity_graph_beam_q),
    ("graph_beam_q", "sq8_d1", ("sq8", 4, 25, 1, 0, 5, 4),
     _parity_graph_beam_q),
    ("graph_beam_q", "pq_ragged_q", ("pq", 7, 60, 8, 16, 9, 8),
     _parity_graph_beam_q),
    ("graph_beam_q", "pq_ef_gt_w", ("pq", 3, 20, 4, 256, 3, 15),
     _parity_graph_beam_q),
    ("graph_beam_q", "pq_m1_tiny_ksub", ("pq", 5, 9, 1, 7, 4, 6),
     _parity_graph_beam_q),
    # (q_n, c, k, bq): q not divisible by bq + non-lane-aligned pool,
    # k wider than the candidate pool, single-candidate pool
    ("topk_merge", "ragged_q", (19, 96, 8, 16), _parity_topk_merge),
    ("topk_merge", "k_gt_c", (4, 6, 10, 8), _parity_topk_merge),
    ("topk_merge", "c1", (5, 1, 3, 8), _parity_topk_merge),
    # (q_n, nprobe, C, cap, d): a store width that is a ragged multiple of
    # the block at d 384 (640-member blocks) and d 64 (4096), one block
    # for the whole width, every cell probed, d not a whole sublane; each
    # case holds an empty cell, a full one and an extent inside a block
    ("ivf_scan", "d384_q16_ragged_width", (16, 3, 6, 1500, 384),
     _parity_ivf_scan),
    ("ivf_scan", "d64_q1_nprobe_all", (1, 5, 5, 4500, 64), _parity_ivf_scan),
    ("ivf_scan", "one_block", (4, 3, 7, 100, 13), _parity_ivf_scan),
    # (n, d, n_cells): the alive path through IVFFlatIndex.search
    ("ivf_scan", "alive_tombstone", (600, 16, 8), _parity_ivf_scan_alive),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel,case,params,fn", PARITY_CASES,
                         ids=[f"{k}-{c}" for k, c, _, _ in PARITY_CASES])
def test_kernel_parity(kernel, case, params, fn, dtype):
    fn(params, dtype)
