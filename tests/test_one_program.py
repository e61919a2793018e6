"""A two-stage search over an IVF or Flat base is one device program.

``TwoStageIndex.search`` runs encode, stage 1 and rerank as one jitted
program (``search.twostage.twostage_search``) where the reducer and the
base both hand over a device part, and makes three calls otherwise. Both
paths must answer alike: ids equal, scores within 1e-6 relative (XLA may
order a reduction differently inside one program), stats equal. The three
-call path is reached here as a stack whose reducer has no device part.
"""
import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro import api
from repro.search import twostage

N, DIM = 900, 16
STACKS = ("RAE8,IVF16,Rerank4", "RAE8,Flat,Rerank4",
          "Mut,RAE8,IVF16,Rerank4")
DEAD = np.arange(0, N, 7)


@pytest.fixture(scope="module")
def corpus():
    return np.random.default_rng(11).standard_normal((N, DIM)).astype(
        np.float32)


@pytest.fixture(scope="module")
def queries():
    return np.random.default_rng(12).standard_normal((16, DIM)).astype(
        np.float32)


@pytest.fixture(scope="module")
def stacks(corpus):
    out = {}
    for spec in STACKS:
        index = api.index_factory(spec, reducer_kw={"steps": 20}).build(
            corpus)
        if spec.startswith("Mut"):
            index.delete(DEAD)
        out[spec] = index
    return out


def _two_stage(index):
    return index._inner if isinstance(index, api.MutableIndex) else index


def _in_calls(monkeypatch, index):
    """Take the reducer's device part away: the stack makes three calls."""
    monkeypatch.setattr(_two_stage(index).reducer, "device_encoder",
                        lambda: None)


@pytest.mark.parametrize("nprobe", [None, 12])
@pytest.mark.parametrize("b", [1, 3, 16])
@pytest.mark.parametrize("k", [5, 100])
@pytest.mark.parametrize("spec", STACKS)
def test_one_program_answers_as_three_calls(stacks, queries, monkeypatch,
                                            spec, k, b, nprobe):
    index = stacks[spec]
    params = None if nprobe is None else api.SearchParams(nprobe=nprobe)
    one = index.search(queries[:b], k, params=params)
    with monkeypatch.context() as m:
        _in_calls(m, index)
        calls = index.search(queries[:b], k, params=params)
    np.testing.assert_array_equal(one.indices, calls.indices)
    np.testing.assert_allclose(one.scores, calls.scores, rtol=1e-6)
    assert one.stats == calls.stats
    assert one.indices.shape == (b, k)
    if spec.startswith("Mut"):
        assert not np.isin(one.indices, DEAD).any()


def _count_trace(trace_dir: str) -> collections.Counter:
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return collections.Counter(
        e.name for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events)


@pytest.mark.parametrize("spec", STACKS[:2])
def test_one_upload_one_dispatch_one_readback(stacks, queries, monkeypatch,
                                              tmp_path, spec):
    """Counted in the host trace (each upload, each program run) and by
    ``jax.device_get``; no implicit transfer is allowed at all."""
    index = stacks[spec]
    index.search(queries, 5)   # compiled outside the count
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or real_get(x))
    with jax.transfer_guard("disallow"), \
            jax.profiler.trace(str(tmp_path / "one")):
        index.search(queries, 5)
    seen = _count_trace(str(tmp_path / "one"))
    assert seen["DevicePutWithSharding"] == 1
    assert seen["PjRtCpuExecutable::Execute"] == 1
    assert seen["PjitFunction(twostage_search)"] >= 1
    assert len(gets) == 1
    # the count tells the paths apart: three programs without the fusion
    _in_calls(monkeypatch, index)
    index.search(queries, 5)
    with jax.profiler.trace(str(tmp_path / "calls")):
        index.search(queries, 5)
    assert _count_trace(str(tmp_path / "calls"))[
        "PjRtCpuExecutable::Execute"] == 3


def test_other_bases_keep_three_calls(corpus, queries, monkeypatch):
    """An HNSW base hands over no device part: its stack never reaches the
    one program, and answers as a stack without a device encoder does."""
    index = api.index_factory("RAE8,HNSW8,Rerank4",
                              reducer_kw={"steps": 20}).build(corpus)
    assert index.base.device_search(20) is None
    before = index.search(queries, 5)

    def refuse(*args, **kwargs):
        raise AssertionError("the one program ran")

    monkeypatch.setattr(twostage, "twostage_search", refuse)
    again = index.search(queries, 5)
    _in_calls(monkeypatch, index)
    calls = index.search(queries, 5)
    for r in (again, calls):
        np.testing.assert_array_equal(before.indices, r.indices)
        np.testing.assert_array_equal(before.scores, r.scores)
        assert before.stats == r.stats


def test_a_search_set_on_the_base_is_called(stacks, queries, monkeypatch):
    """A ``search`` set on the base instance (a wrapper) is honoured: the
    stack calls it, through the three-call path."""
    index = stacks["RAE8,IVF16,Rerank4"]
    inner = index.base.search
    seen = []

    def wrapped(q, k, alive=None, params=None):
        seen.append(k)
        return inner(q, k, alive=alive, params=params)

    plain = index.search(queries[:3], 5)
    monkeypatch.setattr(index.base, "search", wrapped)
    got = index.search(queries[:3], 5)
    assert seen == [20]
    np.testing.assert_array_equal(plain.indices, got.indices)


def test_latency_is_the_whole_search(stacks, queries):
    r = stacks["RAE8,IVF16,Rerank4"].search(queries, 5)
    assert 0.0 < r.latency_s < 60.0
    assert set(r.stats) == {"distance_evals", "centroid_evals",
                            "probe_bytes", "stage1_distance_evals",
                            "rerank_evals"}
    assert r.stats["rerank_evals"] == 20.0
