"""Property-based invariants for the quantizers + the paper's theory bound.

Every invariant runs twice: a deterministic seed sweep (always on, so the
container without ``hypothesis`` still exercises the property) and a
``hypothesis`` randomized variant via ``hypothesis_compat`` (skipped when
the package is absent, live fuzzing when present).

Invariants:
* SQ8 round-trip error <= half a quantization step per dim, any data range.
* PQ ADC distance == exact distance on the dequantized codes (the ADC LUT
  is exact, not an approximation — PQ's only error is reconstruction).
* recall@k is monotone non-decreasing in ``nprobe`` (probing more cells
  scans a superset; with exact in-cell distances a true neighbor can only
  be displaced by another true neighbor).
* the Eq. 15 norm-distortion bound sigma_min||x|| <= ||Wx|| <= sigma_max
  ||x|| holds on random RAE-style weights and on actually-trained RAE
  encoders.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import theory
from repro.search import ivf as ivf_lib
from repro.search import quantize as qz

jax.config.update("jax_platform_name", "cpu")


def _corpus(seed, n, d, scale=1.0, offset=0.0):
    rng = np.random.default_rng(seed)
    return (offset + scale * rng.standard_normal((n, d))).astype(np.float32)


# ---------------------------------------------------------------------------
# SQ8 round-trip
# ---------------------------------------------------------------------------
def _check_sq8_roundtrip(seed, n, d, scale, offset):
    x = _corpus(seed, n, d, scale, offset)
    sq = qz.sq8_train(x)
    rec = np.asarray(qz.sq8_decode(sq, qz.sq8_encode(sq, x)))
    err = np.abs(rec - x)
    bound = np.asarray(sq.step)[None, :] / 2
    assert np.all(err <= bound * (1 + 1e-4) + 1e-6), float(
        (err - bound).max())


@pytest.mark.parametrize("seed", range(8))
def test_sq8_roundtrip_half_step(seed):
    scale = 10.0 ** ((seed % 5) - 2)          # 1e-2 .. 1e2
    _check_sq8_roundtrip(seed, 200, 3 + seed * 5, scale, offset=seed - 4.0)


def test_sq8_roundtrip_constant_dim():
    """A zero-range dim must round-trip exactly (step floor, no div-by-0)."""
    x = np.ones((50, 4), np.float32) * 3.25
    x[:, 1] = np.linspace(-1, 1, 50)
    sq = qz.sq8_train(x)
    rec = np.asarray(qz.sq8_decode(sq, qz.sq8_encode(sq, x)))
    np.testing.assert_allclose(rec[:, 0], x[:, 0], atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 300),
       d=st.integers(1, 48), scale=st.floats(1e-3, 1e3),
       offset=st.floats(-100.0, 100.0))
def test_sq8_roundtrip_half_step_fuzz(seed, n, d, scale, offset):
    _check_sq8_roundtrip(seed, n, d, scale, offset)


# ---------------------------------------------------------------------------
# PQ ADC exactness on dequantized codes
# ---------------------------------------------------------------------------
def _check_pq_adc_exact(seed, n, m, dsub, bits):
    x = _corpus(seed, n, m * dsub)
    q = _corpus(seed + 1, 8, m * dsub)
    pq = qz.pq_train(x, m=m, bits=bits, iters=4, seed=seed)
    codes = qz.pq_encode(pq, x)
    adc = np.asarray(qz.pq_adc_gather(qz.pq_adc_lut(pq, q), codes))
    rec = np.asarray(qz.pq_decode(pq, codes))
    exact = ((q[:, None, :] - rec[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(adc, exact, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("seed,m,dsub,bits", [
    (0, 1, 1, 1), (1, 2, 3, 2), (2, 4, 8, 4), (3, 8, 4, 8), (4, 3, 5, 6),
])
def test_pq_adc_matches_exact(seed, m, dsub, bits):
    _check_pq_adc_exact(seed, 150, m, dsub, bits)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(4, 200),
       m=st.integers(1, 8), dsub=st.integers(1, 8), bits=st.integers(1, 8))
def test_pq_adc_matches_exact_fuzz(seed, n, m, dsub, bits):
    _check_pq_adc_exact(seed, n, m, dsub, bits)


# ---------------------------------------------------------------------------
# nprobe monotonicity
# ---------------------------------------------------------------------------
def _recalls_vs_nprobe(seed, quant):
    x = jnp.asarray(_corpus(seed, 600, 16))
    q = x[:32] + 0.01
    index = ivf_lib.build(x, n_cells=16, kmeans_iters=5, seed=seed)
    probes = (1, 2, 4, 8, 16)
    if quant == "flat":
        return [ivf_lib.recall_vs_exact(index, x, q, 10, p) for p in probes]
    from repro.core.metrics import knn_indices, set_overlap

    pq = qz.pq_train(x, m=4, bits=8, iters=8, seed=seed)
    rows = ivf_lib.store_rows(index)
    c, cap, d = rows.shape
    codes = qz.pq_encode(pq, jnp.asarray(rows.reshape(c * cap, d))) \
        .reshape(c, cap, 4)
    exact = knn_indices(q, x, 10)
    out = []
    for p in probes:
        _, got = qz.ivf_pq_search(index.centroids, index.lists, codes,
                                  index.list_mask, pq.codebooks, q, 10, p)
        out.append(float(set_overlap(exact, got)))
    return out


@pytest.mark.parametrize("seed", range(4))
def test_ivf_flat_recall_monotone_in_nprobe(seed):
    rec = _recalls_vs_nprobe(seed, "flat")
    assert all(b >= a for a, b in zip(rec, rec[1:])), rec
    assert rec[-1] == 1.0  # probing every cell == exact scan


@pytest.mark.parametrize("seed", range(4))
def test_ivf_pq_recall_monotone_in_nprobe(seed):
    """ADC ranking is approximate, so allow a hair of non-monotonicity."""
    rec = _recalls_vs_nprobe(seed, "pq")
    assert all(b >= a - 0.02 for a, b in zip(rec, rec[1:])), rec
    assert rec[-1] >= rec[0]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ivf_flat_recall_monotone_in_nprobe_fuzz(seed):
    rec = _recalls_vs_nprobe(seed, "flat")
    assert all(b >= a for a, b in zip(rec, rec[1:])), rec


# ---------------------------------------------------------------------------
# Theory: Eq. 15 norm-distortion bound
# ---------------------------------------------------------------------------
def _check_norm_bound(seed, m, n, scale):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(0, scale, (m, n)).astype(np.float32))
    xs = jnp.asarray(rng.normal(0, 1, (64, n)).astype(np.float32))
    assert bool(theory.norm_bounds_hold(w, xs))
    d = theory.empirical_distortion(w, xs)
    assert float(d["ratio_max"]) <= float(d["sigma_max"]) * (1 + 1e-4) + 1e-6
    assert float(d["kappa"]) >= 1.0 - 1e-5


@pytest.mark.parametrize("seed", range(6))
def test_norm_bound_random_weights(seed):
    _check_norm_bound(seed, 4 + seed * 3, 16 + seed * 8,
                      scale=10.0 ** ((seed % 3) - 1))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(2, 32),
       extra=st.integers(1, 64), scale=st.floats(1e-2, 10.0))
def test_norm_bound_random_weights_fuzz(seed, m, extra, scale):
    _check_norm_bound(seed, m, m + extra, scale)


def test_norm_bound_trained_rae_encoder():
    """The bound is not just for gaussian W: it holds for the encoder the
    trainer actually produces (weight decay keeps kappa small — that IS the
    paper's mechanism)."""
    from repro.configs import RAEConfig
    from repro.core import trainer
    from repro.data import synthetic

    data = synthetic.embedding_corpus(400, 24, n_clusters=4, intrinsic=8,
                                      seed=3)
    cfg = RAEConfig(in_dim=24, out_dim=8, steps=120, weight_decay=0.1)
    res = trainer.train(cfg, data, log_every=10 ** 9)
    w = res.params["w_e"].T  # encode is x @ w_e; theory wants W [m, n]
    assert bool(theory.norm_bounds_hold(w, jnp.asarray(data)))
