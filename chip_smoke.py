"""Bring-up smoke test: the retrieval path end to end on a TPU.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the mesh-sharded scan only

Everything runs in this one process, through the entry points a user
calls (``api.index_factory`` -> ``build`` -> ``SearchEngine`` / HTTP).
All data is generated from ``--seed``. There is no CPU path: without a
TPU backend the script exits non-zero before doing anything.

* Phase A — ``RAE64,IVF256,Rerank4`` at SIFT1M scale (1,000,003 x 128;
  RAE fitted on the first 100,000 rows, 600 steps; IVF k-means 6 iters),
  the single-chip configuration of ``benchmarks/table6_sharded.py``. 256
  queries go through the warmed engine; recall@10 is taken against the
  exact ``FlatIndex`` scan on the chip, whose ids are cross-checked with a
  float64 numpy brute force. Then the HTTP front end answers a few
  ``POST /search`` and ``GET /healthz`` requests.
* Phase B — ``RAE64,HNSW32,Rerank4`` on the 20,000 x 256 acceptance
  corpus; batched search must take the jitted traversal whose layer-0
  hop is the compiled Pallas kernel.
* ``--four-chips`` — ``Shard4,Flat`` over a 4-device mesh, 4,000,000 x
  768 f32 placed row-sharded from the host, 64 queries; the ids must equal
  a numpy brute force (ties to the smaller id).

Per-phase numbers are printed as JSON lines; the last line is
``{"ok": true, "device": {...}}``. Any failed check raises.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
K = 10
#: recall@10 of RAE64,IVF256,Rerank4 in results/BENCH_sharded.json (CPU)
PHASE_A_RECALL_REF = 0.8492
PHASE_A_RECALL_TOL = 0.02


def report(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def perturbed_rows(corpus: np.ndarray, n_queries: int, seed: int
                   ) -> np.ndarray:
    """Queries as perturbed corpus rows (the benches' protocol)."""
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, corpus.shape[0], n_queries)
    noise = 0.01 * rng.standard_normal((n_queries, corpus.shape[1]))
    return corpus[picks] + noise.astype(np.float32)


def brute_force(corpus: np.ndarray, queries: np.ndarray, k: int,
                chunk: int = 262_144) -> np.ndarray:
    """Exact k-NN ids on the host in float64; ties go to the smaller id."""
    q = queries.astype(np.float64)
    q2 = (q * q).sum(1)[:, None]
    best_d = np.full((q.shape[0], 0), np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for s in range(0, corpus.shape[0], chunk):
        x = corpus[s:s + chunk].astype(np.float64)
        d = q2 - 2.0 * q @ x.T + (x * x).sum(1)[None, :]
        top = np.argpartition(d, min(k, d.shape[1] - 1), axis=1)[:, :k + 1]
        best_d = np.concatenate([best_d, np.take_along_axis(d, top, 1)], 1)
        best_i = np.concatenate([best_i, top + s], 1)
    order = np.lexsort((best_i, best_d), axis=1)[:, :k]
    return np.take_along_axis(best_i, order, 1)


def recall(pred: np.ndarray, truth: np.ndarray) -> float:
    return float((truth[:, :, None] == pred[:, None, :]).any(-1).mean())


def http(base: str, path: str, body: dict | None = None) -> tuple[int, dict]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(base + path, data=data,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.loads(resp.read())


def phase_a(seed: int) -> None:
    from repro import api
    from repro.data import synthetic
    from repro.serve import SearchEngine, make_server

    n, dim = 1_000_003, 128
    t0 = time.perf_counter()
    corpus = synthetic.embedding_corpus(n, dim, n_clusters=64, intrinsic=32,
                                        seed=seed)
    queries = perturbed_rows(corpus, 256, seed + 1)
    data_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    exact = api.FlatIndex().build(corpus)
    gt = exact.search(queries, K).indices
    exact_s = time.perf_counter() - t0
    check(np.array_equal(gt[:16], brute_force(corpus, queries[:16], K)),
          "phase A: exact search ids differ from the numpy brute force")

    index = api.index_factory("RAE64,IVF256,Rerank4",
                              reducer_kw={"steps": 600, "seed": seed},
                              index_kw={"kmeans_iters": 6})
    t0 = time.perf_counter()
    index.reducer.fit(corpus[:100_000])
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index.build(corpus)
    build_s = time.perf_counter() - t0

    engine = SearchEngine(index, max_batch=16, max_wait_ms=4.0,
                          cache_size=0).start()
    server = None
    try:
        t0 = time.perf_counter()
        engine.warmup(ks=(K,))
        warmup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = np.concatenate([engine.search(queries[s:s + 16], K).indices
                              for s in range(0, len(queries), 16)])
        serve_s = time.perf_counter() - t0
        r = recall(got, gt)
        report(phase="A", spec="RAE64,IVF256,Rerank4", n=corpus.shape[0],
               dim=dim, recall_at_10=r, recall_ref_cpu=PHASE_A_RECALL_REF,
               exact_vs_numpy_queries=16, data_s=data_s, exact_s=exact_s,
               rae_fit_s=fit_s, build_s=build_s, warmup_s=warmup_s,
               serve_256_queries_s=serve_s,
               distance_evals=engine.stats().get("distance_evals"))
        check(abs(r - PHASE_A_RECALL_REF) <= PHASE_A_RECALL_TOL,
              f"phase A: recall@10 {r:.4f} is not within "
              f"{PHASE_A_RECALL_TOL} of {PHASE_A_RECALL_REF}")

        server = make_server(engine, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = "http://127.0.0.1:%d" % server.server_address[1]
        status, health = http(base, "/healthz")
        check(status == 200 and health["status"] == "ok",
              f"phase A: /healthz answered {status} {health}")
        agree = []
        for i in range(4):
            status, body = http(base, "/search",
                                {"query": queries[i].tolist(), "k": K})
            check(status == 200 and len(body["indices"]) == K,
                  f"phase A: POST /search answered {status}")
            agree.append(len(set(body["indices"]) & set(got[i].tolist())))
        status, body = http(base, "/search",
                            {"queries": queries[4:8].tolist(), "k": K})
        check(status == 200 and np.shape(body["indices"]) == (4, K),
              f"phase A: batched POST /search answered {status}")
        report(phase="A-http", requests=6, all_200=True,
               single_query_agreement=sum(agree) / (K * len(agree)))
        check(sum(agree) >= 0.9 * K * len(agree),
              "phase A: HTTP answers disagree with the engine's")
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        engine.stop()


def phase_b(seed: int) -> None:
    from repro import api
    from repro.data import synthetic

    n, dim = 20_000, 256
    corpus = synthetic.embedding_corpus(n, dim, n_clusters=16, intrinsic=64,
                                        seed=seed)
    queries = perturbed_rows(corpus, 64, seed + 1)
    gt = api.FlatIndex().build(corpus).search(queries, K).indices

    index = api.index_factory("RAE64,HNSW32,Rerank4",
                              reducer_kw={"steps": 1000, "seed": seed})
    t0 = time.perf_counter()
    index.build(corpus)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    first = index.search(queries, K)
    first_s = time.perf_counter() - t0
    res = index.search(queries, K)
    r = recall(res.indices, gt)
    report(phase="B", spec="RAE64,HNSW32,Rerank4", n=corpus.shape[0],
           dim=dim, recall_at_10=r, distance_evals=res.distance_evals,
           evals_frac=res.distance_evals / n,
           pallas_hop=res.stats.get("pallas_hop"),
           beam_hops=res.stats.get("beam_hops"), build_s=build_s,
           first_search_s=first_s, search_s=res.latency_s)
    check(res.stats.get("pallas_hop") == 1.0,
          "phase B: the batched traversal did not run the Pallas hop")
    check(np.array_equal(first.indices, res.indices),
          "phase B: repeated search answered differently")
    check(r >= 0.9, f"phase B: recall@10 {r:.4f} < 0.9")
    check(res.distance_evals < 0.10 * n,
          f"phase B: {res.distance_evals:.0f} evals/query >= 10% of corpus")


def four_chips(seed: int) -> None:
    from repro import api
    from repro.data import synthetic
    from repro.launch.mesh import make_host_mesh
    from repro.models.common import MeshCtx

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
                                   f"{len(jax.devices())}")
    n, dim = 4_000_000, 768
    t0 = time.perf_counter()
    corpus = synthetic.embedding_corpus(n, dim, n_clusters=64,
                                        intrinsic=dim // 4, seed=seed)
    queries = perturbed_rows(corpus, 64, seed + 1)
    data_s = time.perf_counter() - t0

    index = api.index_factory("Shard4,Flat",
                              ctx=MeshCtx(mesh=make_host_mesh()))
    t0 = time.perf_counter()
    index.build(corpus)
    build_s = time.perf_counter() - t0
    peak = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    check(max(peak) < corpus.nbytes / 2,
          f"four chips: a device peaked at {max(peak)} bytes — the "
          f"{corpus.nbytes}-byte corpus was staged on one chip")
    t0 = time.perf_counter()
    first = index.search(queries, K)
    first_s = time.perf_counter() - t0
    res = index.search(queries, K)
    t0 = time.perf_counter()
    ref = brute_force(corpus, queries, K)
    ref_s = time.perf_counter() - t0
    match = float((res.indices == ref).all(1).mean())
    report(phase="four-chips", spec="Shard4,Flat", n=corpus.shape[0], dim=dim,
           corpus_bytes=corpus.nbytes, device_peak_bytes=peak,
           query_rows_matching_numpy=match, data_s=data_s, build_s=build_s,
           first_search_s=first_s, search_s=res.latency_s,
           numpy_reference_s=ref_s)
    check(np.array_equal(first.indices, res.indices),
          "four chips: repeated search answered differently")
    check(match == 1.0, "four chips: ids differ from the numpy brute force")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh-sharded exact scan")
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    report(phase="device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()), jax=jax.__version__)
    if args.four_chips:
        four_chips(args.seed)
    else:
        phase_a(args.seed)
        phase_b(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
