"""Sharded serving: partitioning, scatter-gather merge, shard-count
invariance, and the legacy distributed-layer regressions.

The contract under test (docs/sharded_serving.md): sharded search is
**bitwise invariant to the shard count**. Integer-valued f32 corpora make
the per-shard arithmetic exact, so any S in {1, 2, 8} must produce the
identical (scores, indices) a FlatIndex over the whole corpus produces —
including on score ties (broken by the smaller global id) and ragged
(prime-sized) corpora. The three regression groups mirror the bugs the
rewrite of ``search/distributed.py`` fixed: dropped tail rows when
``n % n_shards != 0``, ``lax.top_k`` crashes when ``k > n_loc``, and
gather-order-dependent tie resolution.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (FlatIndex, ShardedIndex, index_factory, load_index,
                       parse_index_spec)
from repro.distributed.partitioning import (partition_ivf_cells,
                                            partition_rows)

jax.config.update("jax_platform_name", "cpu")


def _int_corpus(n, d, seed=0):
    """Integer-valued f32: exact arithmetic, dense score ties."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-8, 8, (n, d)).astype(np.float32)
    x[n // 2] = x[n // 3]  # planted duplicate rows -> guaranteed ties
    return x


def _queries(n, d, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(-8, 8, (n, d)).astype(np.float32)


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,s", [(101, 4), (509, 8), (7, 7), (5, 8), (0, 3)])
def test_partition_rows_disjoint_cover(n, s):
    parts = partition_rows(n, s)
    cat = np.concatenate(parts) if parts else np.empty(0, np.int32)
    np.testing.assert_array_equal(np.sort(cat), np.arange(n))
    # balanced: sizes differ by at most one — the ragged tail is spread,
    # not dumped on (or dropped from) the last shard
    sizes = [len(p) for p in parts]
    if sizes:
        assert max(sizes) - min(sizes) <= 1
    for p in parts:
        assert np.all(np.diff(p) > 0) if len(p) > 1 else True


def test_partition_rows_rejects_bad_count():
    with pytest.raises(ValueError):
        partition_rows(10, 0)


@pytest.mark.parametrize("n,s", [(101, 4), (64, 8)])
def test_partition_ivf_cells_disjoint_cover(n, s):
    corpus = _int_corpus(n, 8)
    parts = partition_ivf_cells(corpus, s, seed=3)
    cat = np.concatenate([p for p in parts if len(p)])
    np.testing.assert_array_equal(np.sort(cat), np.arange(n))
    for p in parts:
        if len(p) > 1:
            assert np.all(np.diff(p) > 0)  # ascending within each shard


# ---------------------------------------------------------------------------
# shard-count invariance (the tentpole contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [101, 509])  # primes: every split is ragged
@pytest.mark.parametrize("s", [1, 2, 8])
def test_sharded_bitwise_matches_flat(n, s):
    corpus = _int_corpus(n, 16)
    q = _queries(9, 16)
    ref = FlatIndex().build(corpus).search(q, 10)
    got = ShardedIndex(n_shards=s).build(corpus).search(q, 10)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(ref.indices))
    np.testing.assert_array_equal(np.asarray(got.scores),
                                  np.asarray(ref.scores))


def test_sharded_invariant_across_shard_counts():
    corpus = _int_corpus(257, 12, seed=5)
    q = _queries(6, 12, seed=6)
    outs = [ShardedIndex(n_shards=s).build(corpus).search(q, 7)
            for s in (1, 2, 8)]
    for other in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0].indices),
                                      np.asarray(other.indices))
        np.testing.assert_array_equal(np.asarray(outs[0].scores),
                                      np.asarray(other.scores))


def test_ivf_partition_matches_flat():
    corpus = _int_corpus(150, 16, seed=7)
    q = _queries(5, 16, seed=8)
    ref = FlatIndex().build(corpus).search(q, 10)
    got = ShardedIndex(n_shards=4, partition="ivf",
                       seed=11).build(corpus).search(q, 10)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(ref.indices))


def test_ragged_tail_rows_are_searchable():
    # legacy bug: n // n_shards slabs silently dropped the tail rows —
    # a query sitting exactly on a tail row must get it back as top-1
    corpus = _int_corpus(101, 16, seed=9)
    idx = ShardedIndex(n_shards=8).build(corpus)
    for row in (100, 97, 96):  # the 101 % 8 = 5 tail region and beyond
        r = idx.search(corpus[row:row + 1], 1)
        assert int(r.indices[0, 0]) == row


def test_k_larger_than_shard_size():
    # legacy bug: lax.top_k(s_l, k) crashed when k > rows-per-shard
    corpus = _int_corpus(101, 8, seed=10)
    q = _queries(4, 8, seed=11)
    ref = FlatIndex().build(corpus).search(q, 50)
    got = ShardedIndex(n_shards=8).build(corpus).search(q, 50)  # n_loc=13
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(ref.indices))


def test_k_larger_than_corpus():
    corpus = _int_corpus(11, 8, seed=12)
    got = ShardedIndex(n_shards=4).build(corpus).search(_queries(3, 8), 64)
    assert got.indices.shape == (3, 11)  # clamped to ntotal, no pad columns
    assert np.all(got.indices >= 0)


# ---------------------------------------------------------------------------
# factory grammar
# ---------------------------------------------------------------------------
def test_factory_parse_shard_round_trip():
    for s in ("Shard8,Flat", "RAE64,Shard8,IVF256,Rerank4",
              "PCA8,Shard4,IVF16,Rerank2", "Shard2,Flat,SQ8"):
        assert str(parse_index_spec(s)) == s
    # implicit stages canonicalize ("SQ8" alone means a flat SQ8 scan)
    assert str(parse_index_spec("Shard8")) == "Shard8,Flat"
    assert str(parse_index_spec("Shard2,SQ8")) == "Shard2,Flat,SQ8"
    assert parse_index_spec("Shard8").shards == 8


@pytest.mark.parametrize("bad", ["Shard", "Shard0", "Flat,Shard2",
                                 "Shard2,Shard4", "Shard2,RAE8,Flat"])
def test_factory_rejects_bad_shard_specs(bad):
    with pytest.raises(ValueError):
        parse_index_spec(bad)


def test_factory_builds_sharded_stack():
    corpus = _int_corpus(220, 16, seed=13)
    q = _queries(5, 16, seed=14)
    idx = index_factory("PCA8,Shard4,IVF16,Rerank2").build(corpus)
    base = idx.base
    assert isinstance(base, ShardedIndex) and base.shard_count == 4
    r = idx.search(q, 5)
    assert r.indices.shape == (5, 5) and np.all(r.indices >= 0)


def test_sharded_rejects_nested_wrappers_in_child_spec():
    with pytest.raises(ValueError):
        ShardedIndex(child_spec="Shard2,Flat").build(_int_corpus(20, 4))
    with pytest.raises(ValueError):
        ShardedIndex(child_spec="PCA4,Flat").build(_int_corpus(20, 4))


# ---------------------------------------------------------------------------
# persistence + fingerprint
# ---------------------------------------------------------------------------
def test_save_load_fingerprint_round_trip(tmp_path):
    corpus = _int_corpus(101, 8, seed=15)
    q = _queries(4, 8, seed=16)
    idx = ShardedIndex(n_shards=3, child_spec="IVF4").build(corpus)
    d = os.path.join(str(tmp_path), "idx")
    idx.save(d)
    idx2 = load_index(d)
    assert idx2.fingerprint() == idx.fingerprint()
    np.testing.assert_array_equal(np.asarray(idx.search(q, 5).indices),
                                  np.asarray(idx2.search(q, 5).indices))


def test_fingerprint_sensitive_to_sharding():
    corpus = _int_corpus(60, 8, seed=17)
    a = ShardedIndex(n_shards=2).build(corpus)
    b = ShardedIndex(n_shards=3).build(corpus)
    c = ShardedIndex(n_shards=2).build(_int_corpus(60, 8, seed=18))
    assert a.fingerprint() != b.fingerprint()  # layout differs
    assert a.fingerprint() != c.fingerprint()  # content differs


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def test_sharded_serves_through_engine():
    from repro.serve.engine import SearchEngine

    corpus = _int_corpus(101, 8, seed=19)
    q = _queries(6, 8, seed=20)
    idx = ShardedIndex(n_shards=4).build(corpus)
    ref = FlatIndex().build(corpus).search(q, 5)
    with SearchEngine(idx) as eng:
        res = eng.search(q, k=5)
        st = eng.stats()
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  np.asarray(ref.indices))
    assert st["index"]["shards"] == 4


# ---------------------------------------------------------------------------
# device-parallel path (nightly: ci.sh forces 8 XLA host devices)
# ---------------------------------------------------------------------------
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.mark.slow
@needs_mesh
def test_mesh_search_matches_flat_ragged():
    from repro.models.common import MeshCtx
    from repro.search.distributed import search as dist_search

    mesh = jax.make_mesh((8,), ("data",))
    ctx = MeshCtx(mesh=mesh, rules={"db_rows": ("data",)})
    corpus = _int_corpus(101, 16, seed=21)  # ragged: 101 % 8 != 0
    q = _queries(7, 16, seed=22)
    ref = FlatIndex().build(corpus).search(q, 10)
    v, i = dist_search(jnp.asarray(q), jnp.asarray(corpus), 10, ctx)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref.indices))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ref.scores))


@pytest.mark.slow
@needs_mesh
def test_mesh_distributed_topk_small_shards():
    from repro.models.common import MeshCtx
    from repro.search.distributed import distributed_topk

    mesh = jax.make_mesh((8,), ("data",))
    ctx = MeshCtx(mesh=mesh, rules={"db_rows": ("data",)})
    rng = np.random.default_rng(23)
    scores = jnp.asarray(rng.integers(-100, 100, (37,)), jnp.float32)
    k = 20  # > ceil(37 / 8) = 5 rows per shard: the legacy crash shape
    v, i = distributed_topk(scores, k, ctx)
    order = np.lexsort((np.arange(37), -np.asarray(scores)))[:k]
    np.testing.assert_array_equal(np.asarray(i), order)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(scores)[order])


@pytest.mark.slow
@needs_mesh
def test_mesh_sharded_index_matches_threads():
    from repro.models.common import MeshCtx

    mesh = jax.make_mesh((8,), ("data",))
    ctx = MeshCtx(mesh=mesh, rules={"db_rows": ("data",)})
    corpus = _int_corpus(509, 16, seed=24)
    q = _queries(6, 16, seed=25)
    threads = ShardedIndex(n_shards=8).build(corpus).search(q, 10)
    meshed = ShardedIndex(n_shards=8, ctx=ctx,
                          workers="mesh").build(corpus).search(q, 10)
    np.testing.assert_array_equal(np.asarray(meshed.indices),
                                  np.asarray(threads.indices))
    np.testing.assert_array_equal(np.asarray(meshed.scores),
                                  np.asarray(threads.scores))


_PLACE_ROWS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.api import FlatIndex, index_factory
from repro.launch.mesh import make_host_mesh
from repro.models.common import MeshCtx

rng = np.random.default_rng(26)
corpus = rng.integers(-8, 8, (1001, 16)).astype(np.float32)  # 1001 % 4 != 0
q = rng.integers(-8, 8, (5, 16)).astype(np.float32)
ix = index_factory("Shard4,Flat", ctx=MeshCtx(mesh=make_host_mesh()))
ix.build(corpus)
db = ix._shards[0]._db
# row-sharded from the host: each device holds one padded slab, none the whole
assert db.shape == (1004, 16), db.shape
assert sorted(s.data.shape[0] for s in db.addressable_shards) == [251] * 4
assert len({s.device for s in db.addressable_shards}) == 4
ref = FlatIndex().build(corpus)
got = ix.search(q, 10)
np.testing.assert_array_equal(got.indices, ref.search(q, 10).indices)
assert ix._shards[0].fingerprint() == ref.fingerprint()
# streaming insert re-places the grown corpus, ids continue after the tail
extra = rng.integers(-8, 8, (7, 16)).astype(np.float32)
ix._shards[0].add(extra)
ref.add(extra)
assert ix._shards[0].ntotal == 1008 == ref.ntotal
np.testing.assert_array_equal(ix._shards[0].search(q, 10).indices,
                              ref.search(q, 10).indices)
print("PLACE ROWS OK")
"""


@pytest.mark.timeout(300)
def test_mesh_flat_places_rows_sharded_subprocess():
    """A mesh FlatIndex puts the corpus on the devices row-sharded from
    the host (padded to whole slabs, never staged on one device) and
    answers exactly like the one-device scan, before and after an add.
    4 forced host devices, so it runs in a subprocess."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PLACE_ROWS_SCRIPT], env=env,
                       cwd=".", capture_output=True, text=True, timeout=280)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "PLACE ROWS OK" in r.stdout
