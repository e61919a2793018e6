"""Compile-only tests: the main path's Pallas kernels, at real widths, built
by the TPU compiler for a described (not attached) v5e:2x2 topology.

Interpret mode runs a kernel's logic on the CPU but never shows what
Mosaic refuses: an in-kernel gather, a block whose last two dims are not
(8, 128)-aligned, too much VMEM. These compiles do, without a chip. They
assert only that each program compiles and holds the kernel
(``tpu_custom_call``); nothing runs, so they say nothing about results or
time — the interpret-mode parity tests in ``test_kernels.py`` own those.

The topology is described inside a module fixture (never at import: one
process at a time may load the TPU library), which skips where it cannot
be described. The persistent compilation cache is off around the
compiles: an entry compiled for a described chip cannot be read back.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.index import ivf_stage
from repro.core.rae import encode
from repro.kernels.graph_beam.kernel import gather_layout, graph_beam_pallas
from repro.kernels.graph_beam_q.kernel import graph_beam_q_pallas
from repro.kernels.ivf_scan.kernel import ivf_scan_pallas, store_shape
from repro.kernels.l2_topk.kernel import l2_topk_pallas
from repro.kernels.pq_adc.kernel import pq_adc_pallas
from repro.kernels.topk_merge.kernel import topk_merge_pallas
from repro.search.hnsw import _traverse_impl
from repro.search.twostage import rerank_stage, twostage_search

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "can't"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes) -> str:
    """Compile ``fn`` for the described chip; shapes are (shape, dtype)
    pairs or None (an absent optional operand)."""
    args = [None if s is None
            else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d", [64, 768])
def test_l2_topk_compiles(one_chip, d):
    text = _compiled_text(
        one_chip, lambda q, db, sq: l2_topk_pallas(q, db, sq, 40),
        ((128, d), F32), ((20_480, d), F32), ((20_480,), F32))
    assert "tpu_custom_call" in text


def test_pq_adc_compiles(one_chip):
    m, ksub, dsub = 8, 256, 8
    text = _compiled_text(
        one_chip,
        lambda q, cb, codes, pen: pq_adc_pallas(q, cb, codes, pen, 40, m=m,
                                                ksub=ksub, dsub=dsub),
        ((128, m * dsub), F32), ((m * ksub, dsub), F32),
        ((20_480, m), I32), ((20_480,), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("q_n,n_probe,n_cells,cap,d", [
    (16, 64, 1024, 2442, 384),   # RAE384,IVF1024 over 1M rows, nprobe 64
    (16, 16, 256, 9766, 64),     # RAE64,IVF256 over 1M rows, nprobe 16
    # the autotuner's top nprobe rung, min(C, 512), at both shapes
    (16, 512, 1024, 2442, 384),
    (16, 256, 256, 9766, 64),
])
def test_ivf_scan_compiles(one_chip, q_n, n_probe, n_cells, cap, d):
    """The probe scan at the served shapes, on the store in its default
    layout: the kernel reads it in place, so the program holds no copy of
    it (a copy would be the store's size in temporaries), and its VMEM
    does not grow with nprobe (the top rung would not fit otherwise)."""
    shape = store_shape(n_cells, cap, d)
    compiled = jax.jit(ivf_scan_pallas).lower(*(
        jax.ShapeDtypeStruct(s, t, sharding=one_chip)
        for s, t in (((shape[1], q_n), F32), ((q_n, n_probe), I32),
                     ((n_cells,), I32), (shape, F32)))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < n_cells * cap * d


@pytest.mark.parametrize("q_n,n,m,n_cells,cap,n_probe,k1,k", [
    (16, 128, 64, 256, 9766, 16, 40, 10),        # RAE64,IVF256,Rerank4
    (16, 768, 384, 1024, 2442, 64, 400, 100),    # RAE384,IVF1024,Rerank4
])
def test_twostage_search_compiles(one_chip, monkeypatch, q_n, n, m, n_cells,
                                  cap, n_probe, k1, k):
    """The one program of a two-stage search (encode, the IVF probe with
    the ``ivf_scan`` kernel, the rerank) over 1M rows at the served
    shapes: the store and the corpus are its arguments, read in place, so
    its temporaries are a sliver of either (a copy would be its size)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, store = 1_000_000, store_shape(n_cells, cap, m)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = twostage_search.lower(
        arg((q_n, n), F32), {"w_e": arg((n, m), F32)},
        (arg((n_cells, m), F32), arg((n_cells, cap), I32), arg(store, F32),
         arg((n_cells, cap), jnp.bool_), arg((n_cells,), I32), None),
        arg((rows, n), F32),
        encode=("rae_encode", encode, ()),
        stage1=("ivf_probe", ivf_stage,
                (("k", k1), ("k_req", k1), ("nprobe", n_probe))),
        rerank=("rerank_candidates", rerank_stage,
                (("k", k), ("metric", "euclidean")))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    store_bytes = store[0] * store[1] * store[2] * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(store_bytes, rows * n * 4) // 10, temp


# graph hop shapes: Q 32 queries, N 20,000 nodes, d 64, W = ef = 64
Q, N, D, W, EF = 32, 20_000, 64, 64, 64


def test_graph_beam_compiles(one_chip):
    def hop(q, db, db_sq, nbrs, bv, bi):
        return graph_beam_pallas(q, *gather_layout(db, db_sq), nbrs, bv, bi)

    text = _compiled_text(one_chip, hop, ((Q, D), F32), ((N, D), F32),
                          ((N,), F32), ((Q, W), I32), ((Q, EF), F32),
                          ((Q, EF), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode,dop,cw,ksub", [("sq8", D, D, 0),
                                              ("pq", 8 * 256, 8, 256)])
def test_graph_beam_q_compiles(one_chip, mode, dop, cw, ksub):
    def hop(q_op, q_bias, codes, node_bias, nbrs, bv, bi):
        return graph_beam_q_pallas(q_op, q_bias,
                                   *gather_layout(codes, node_bias), nbrs,
                                   bv, bi, mode=mode, ksub=ksub)

    text = _compiled_text(one_chip, hop, ((Q, dop), F32), ((Q,), F32),
                          ((N, cw), I32), ((N,), F32), ((Q, W), I32),
                          ((Q, EF), F32), ((Q, EF), I32))
    assert "tpu_custom_call" in text


def test_topk_merge_compiles(one_chip):
    text = _compiled_text(
        one_chip, lambda v, i: topk_merge_pallas(v, i, 10, bq=32),
        ((32, 40), F32), ((32, 40), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["f32", "sq8", "pq"])
def test_traverse_with_pallas_hop_compiles(one_chip, mode):
    """The whole jitted batched HNSW traversal (upper-layer descent + the
    layer-0 frontier loop with the Pallas hop), as served."""
    m_deg, levels = 32, 3
    codec = {"f32": [None, None, None, None],
             "sq8": [((N, D), I32), ((N,), F32), ((D,), F32), ((D,), F32)],
             "pq": [((N, 8), I32), ((N,), F32), ((8, 256, D // 8), F32),
                    None]}[mode]
    fn = functools.partial(_traverse_impl, ef=EF, k=40, use_pallas=True,
                           mode=mode, ksub=256 if mode == "pq" else 0)
    text = _compiled_text(one_chip, fn, ((Q, D), F32), ((N, D), F32),
                          ((N,), F32), ((N, 2 * m_deg), I32),
                          ((levels, N, m_deg), I32), ((), I32), *codec)
    assert "tpu_custom_call" in text
