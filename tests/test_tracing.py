"""Spans, counters and program names of the serving path.

The engine and the index stack open ``jax.profiler.TraceAnnotation`` spans
at each layer's boundary, and every jitted program on the hot path has a
stable name, so a device trace reads as the program's own layers. These
tests record a trace on the CPU and read it with ``ProfileData``.
"""
import asyncio
import contextlib
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import rae
from repro.search import twostage
from repro.serve import SearchEngine

jax.config.update("jax_platform_name", "cpu")

N, DIM, K = 600, 16, 5
TWO_STAGE_SPANS = {"engine.batch", "index.search", "engine.scatter",
                   "twostage.encode", "twostage.stage1", "twostage.rerank",
                   "twostage.dispatch", "twostage.fetch", "ivf.probe",
                   "ivf.count"}
SHARDED_SPANS = {"engine.batch", "index.search", "engine.scatter",
                 "sharded.scan", "sharded.merge"}


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    return rng.standard_normal((N, DIM)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(4)
    return rng.standard_normal((12, DIM)).astype(np.float32)


@pytest.fixture(scope="module")
def two_stage(corpus):
    return api.index_factory("RAE8,IVF16,Rerank4",
                             reducer_kw={"steps": 20}).build(corpus)


@pytest.fixture(scope="module")
def sharded(corpus):
    return api.index_factory("Shard2,Flat").build(corpus)


def _serve(index, queries, trace_dir=None) -> list:
    """Each query through the engine's queue, all in flight at once; with
    ``trace_dir``, under the profiler (the warm-up stays outside it)."""
    with SearchEngine(index, max_batch=4, max_wait_ms=2.0,
                      cache_size=0) as eng:
        eng.warmup(ks=(K,))
        with (jax.profiler.trace(trace_dir) if trace_dir
              else contextlib.nullcontext()):
            futs = [asyncio.run_coroutine_threadsafe(eng.asearch(q, K),
                                                     eng.loop)
                    for q in queries]
            return [f.result(timeout=60) for f in futs]


def _host_events(trace_dir: str) -> list[tuple]:
    """(thread, name, start_ns, end_ns, stats) of every host event."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats))
                           for e in line.events)
    return out


@pytest.mark.parametrize("stack,spans", [("two_stage", TWO_STAGE_SPANS),
                                         ("sharded", SHARDED_SPANS)])
def test_every_span_nests_under_its_engine_batch(stack, spans, queries,
                                                 request, tmp_path):
    _serve(request.getfixturevalue(stack), queries, str(tmp_path))
    events = _host_events(str(tmp_path))
    assert spans <= {e[1] for e in events}
    batches = [e for e in events if e[1] == "engine.batch"]
    ids = [b[4]["batch"] for b in batches]
    assert len(set(ids)) == len(ids) >= 3
    assert all(1 <= b[4]["size"] <= b[4]["bucket"] <= 4 for b in batches)
    assert sum(b[4]["size"] for b in batches) == len(queries)
    # spans nest by time on the executor thread: each one lies inside
    # exactly one engine.batch on its own thread
    for thread, name, s, e, _ in events:
        if name in spans - {"engine.batch"}:
            owners = [b for b in batches
                      if b[0] == thread and b[2] <= s and e <= b[3]]
            assert len(owners) == 1, (name, thread)


def test_answers_are_bitwise_the_same_under_the_profiler(two_stage, sharded,
                                                         queries, tmp_path):
    for index in (two_stage, sharded):
        plain = _serve(index, queries)
        traced = _serve(index, queries, str(tmp_path / index.kind))
        for a, b in zip(plain, traced):
            np.testing.assert_array_equal(a.indices, b.indices)
            np.testing.assert_array_equal(a.scores, b.scores)


def test_wait_counter_is_bounded_by_latency(two_stage, queries):
    with SearchEngine(two_stage, max_batch=4, max_wait_ms=2.0,
                      cache_size=0) as eng:
        eng.warmup(ks=(K,))
        futs = [asyncio.run_coroutine_threadsafe(eng.asearch(q, K), eng.loop)
                for q in queries]
        for f in futs:
            f.result(timeout=60)
        stats = eng.stats()
        latencies = list(eng.metrics._lat)
    assert stats["requests"] == len(queries) == len(latencies)
    assert 0.0 < stats["wait_s_total"] <= sum(latencies)
    assert stats["wait_ms_mean"] == pytest.approx(
        stats["wait_s_total"] / len(queries) * 1e3, abs=1e-3)


def test_rae_encode_is_the_eager_encode_bitwise(two_stage, queries):
    params = two_stage.reducer.params_
    x = jnp.asarray(queries)
    np.testing.assert_array_equal(np.asarray(rae.rae_encode(params, x)),
                                  np.asarray(rae.encode(params, x)))
    biased = dict(params, b_e=jnp.linspace(-1.0, 1.0, 8))
    np.testing.assert_array_equal(np.asarray(rae.rae_encode(biased, x)),
                                  np.asarray(rae.encode(biased, x)))


def test_hot_path_programs_carry_stable_names(two_stage, corpus, queries):
    q = jnp.asarray(queries)
    zq = jnp.asarray(two_stage.reducer.transform(queries))
    cand = jnp.zeros((len(queries), 20), jnp.int32)
    flat = api.FlatIndex().build(corpus)
    probe = two_stage.base.device_search(20)
    encode, stage1, rerank = two_stage._device_parts(K, 20, None, None)
    lowered = {
        "rae_encode": rae.rae_encode.lower(two_stage.reducer.params_, q),
        "ivf_probe": two_stage.base._probe.lower(probe.operands, zq,
                                                 **dict(probe.static)),
        "rerank_candidates": two_stage._rerank.lower(
            q, two_stage._db_full, cand, k=K),
        "flat_scan": flat._scan.lower(q, flat._db, flat._norms, None,
                                      k=K, n=N),
        "twostage_search": twostage.twostage_search.lower(
            q, encode.operands, stage1.operands, rerank.operands,
            encode=encode.spec, stage1=stage1.spec, rerank=rerank.spec),
    }
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text(), name
    # the one program keeps each stage findable by its scope
    text = lowered["twostage_search"].as_text(debug_info=True)
    for scope in ("rae_encode", "ivf_probe", "rerank_candidates"):
        assert f"/{scope}/" in text, scope


_SCOPES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re
import jax, numpy as np
from repro.api import index_factory
from repro.launch.mesh import make_host_mesh
from repro.models.common import MeshCtx

rng = np.random.default_rng(5)
corpus = rng.standard_normal((1001, 16)).astype(np.float32)
q = rng.standard_normal((4, 16)).astype(np.float32)
flat = index_factory("Shard4,Flat", ctx=MeshCtx(mesh=make_host_mesh())
                     ).build(corpus)._shards[0]
text = flat._scan.lower(q, flat._db, flat._norms, None, k=10,
                        n=1001).as_text(debug_info=True)
names = re.findall(r'loc\("([^"]+)"', text)
for scope in ("shard_scan", "topk_merge"):
    ops = [n for n in names if n.startswith(scope + "/")]
    print(scope, len(ops), any("all_gather" in n for n in ops))
"""


@pytest.mark.timeout(300)
def test_shard_map_body_carries_scan_and_merge_scopes():
    """The mesh scan's ops carry ``shard_scan`` and its exchange and merge
    ``topk_merge`` in their names (four forced host devices, so it runs
    in a subprocess)."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCOPES_SCRIPT], env=env,
                       cwd=".", capture_output=True, text=True, timeout=280)
    assert r.returncode == 0, r.stderr[-3000:]
    found = {line.split()[0]: line.split()[1:] for line in
             r.stdout.strip().splitlines()}
    assert int(found["shard_scan"][0]) > 0 and found["shard_scan"][1] == \
        "False"
    assert int(found["topk_merge"][0]) > 0 and found["topk_merge"][1] == \
        "True"
