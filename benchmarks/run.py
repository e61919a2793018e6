"""Benchmark harness: one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows. Full-scale variants of the
paper tables live in table1_knn.py / table2_time.py / fig1_weight_decay.py
/ table3_quant.py / table4_graph.py / table5_serve.py (separate CLIs);
this harness runs CPU-budget versions of each so ``python -m
benchmarks.run`` finishes in minutes and covers every artifact.

Machine-readable output: every run also writes ``results/BENCH_run.json``
(and each table CLI writes its own ``results/BENCH_<name>.json`` via
:func:`write_bench`) with a stable schema — ``{bench, schema_version,
created_unix, config, rows}`` — so the perf trajectory (recall, QPS,
bytes/vector, wall-clock) is diffable across PRs. Every ``write_bench``
also refreshes ``results/BENCH_summary.json``, the cross-bench aggregate
(:func:`write_summary`) that merges all per-bench files under one schema
version, so one file answers "what did every bench last measure".
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

#: Bump when the shape of BENCH_*.json / BENCH_summary.json changes;
#: scripts/check_bench.py and any cross-PR trajectory tooling key on it.
BENCH_SCHEMA_VERSION = 1

ROWS: list[dict] = []


def write_bench(name: str, rows: list[dict], config: dict | None = None,
                results_dir: str = "results") -> str:
    """Write ``results/BENCH_<name>.json``: the one machine-readable schema
    every benchmark emits. ``rows`` are flat dicts (recall/qps/bytes
    keys where applicable); ``config`` records the knobs that produced
    them. Also re-aggregates ``BENCH_summary.json`` so the summary can
    never go stale relative to the file that just changed."""
    os.makedirs(results_dir, exist_ok=True)
    path = os.path.join(results_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump({"bench": name, "schema_version": BENCH_SCHEMA_VERSION,
                   "created_unix": time.time(),
                   "config": config or {}, "rows": rows}, f, indent=1)
    print(f"# wrote {path} ({len(rows)} rows)")
    write_summary(results_dir)
    return path


def write_summary(results_dir: str = "results") -> str:
    """Merge every ``results/BENCH_*.json`` into ``BENCH_summary.json``:
    ``{bench: {schema_version, created_unix, config, rows}}`` keyed by
    bench name, discovered by glob (no hardcoded bench list — a new table
    CLI shows up here for free). Files without a ``rows`` key (foreign or
    pre-schema artifacts) are skipped rather than fatal."""
    benches: dict[str, dict] = {}
    for fn in sorted(os.listdir(results_dir)):
        if (not fn.startswith("BENCH_") or not fn.endswith(".json")
                or fn == "BENCH_summary.json"):
            continue
        try:
            with open(os.path.join(results_dir, fn)) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if "rows" not in data:
            continue
        name = data.get("bench", fn[len("BENCH_"):-len(".json")])
        benches[name] = {
            "schema_version": data.get("schema_version", 0),
            "created_unix": data.get("created_unix"),
            "config": data.get("config", {}),
            "rows": data["rows"],
        }
    path = os.path.join(results_dir, "BENCH_summary.json")
    with open(path, "w") as f:
        json.dump({"bench": "summary",
                   "schema_version": BENCH_SCHEMA_VERSION,
                   "created_unix": time.time(),
                   "benches": benches}, f, indent=1)
    return path


def emit(name: str, us: float, derived: str = "", **extra):
    """One benchmark data point. ``extra`` keys (recall, qps,
    bytes_per_vector, ...) land verbatim in BENCH_run.json."""
    row = {"name": name, "us_per_call": us, "derived": derived}
    if us > 0:
        row["qps"] = 1e6 / us
    row.update(extra)
    ROWS.append(row)
    print(f"{name},{us:.2f},{derived}", flush=True)


def _timeit(fn, *args, warmup=2, iters=5):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kernels():
    import jax

    from repro.kernels import l2_topk, rae_encode

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (256, 768))
    db = jax.random.normal(jax.random.PRNGKey(1), (65536, 768))

    fused = jax.jit(lambda a, b: l2_topk(a, b, 10, impl="ref"))
    us = _timeit(fused, q, db)
    emit("l2_topk_ref_256x65536x768", us,
         f"{2*256*65536*768/us*1e6/1e12:.2f}TFLOPs_eff")

    w = jax.random.normal(jax.random.PRNGKey(2), (768, 128)) * 0.05
    enc = jax.jit(lambda a: rae_encode(a, w, impl="ref"))
    us = _timeit(enc, db)
    emit("rae_encode_65536x768to128", us,
         f"{65536*768*128*2/us*1e6/1e12:.2f}TFLOPs_eff")

    # reduced-space scan speedup (the paper's payoff): 768d vs 128d corpus
    dbr = enc(db)
    qr = jax.jit(lambda a: rae_encode(a, w, impl="ref"))(q)
    red = jax.jit(lambda a, b: l2_topk(a, b, 10, impl="ref"))
    us_red = _timeit(red, qr, dbr)
    emit("l2_topk_reduced_256x65536x128", us_red,
         f"speedup_vs_full={_timeit(fused, q, db)/us_red:.2f}x")


def bench_rae_train():
    from repro.configs import RAEConfig
    from repro.core import trainer
    from repro.data import synthetic

    data = synthetic.paper_dataset("imdb_like", 2000)
    cfg = RAEConfig(in_dim=768, out_dim=384, steps=200)
    t0 = time.perf_counter()
    res = trainer.train(cfg, data, log_every=10**9)
    us = (time.perf_counter() - t0) / cfg.steps * 1e6
    emit("rae_train_step_768to384_b128", us,
         f"loss={res.history[-1]['loss']:.3f}")


def bench_two_stage_search():
    import jax
    import jax.numpy as jnp

    from repro.configs import RAEConfig
    from repro.core import trainer
    from repro.data import synthetic
    from repro.models.common import NULL_CTX
    from repro.search import (encode_corpus, recall_vs_exact, search,
                              two_stage_search)

    data = synthetic.embedding_corpus(32768, 512, n_clusters=16,
                                      intrinsic=128, seed=0)
    cfg = RAEConfig(in_dim=512, out_dim=128, steps=600, weight_decay=0.3)
    res = trainer.train(cfg, data, log_every=10**9)
    db = jnp.asarray(data)
    db_red = encode_corpus(res.params, db, NULL_CTX)
    q = db[:128] + 0.01

    exact = jax.jit(lambda a: search(a, db, 10, NULL_CTX))
    ts = jax.jit(lambda a: two_stage_search(a, db, db_red, res.params, 10,
                                            NULL_CTX, rerank_factor=4))
    us_exact = _timeit(exact, q)
    us_ts = _timeit(ts, q)
    recall = recall_vs_exact(q, db, db_red, res.params, 10, NULL_CTX, 4)
    emit("search_exact_128q_32k_512d", us_exact, "")
    emit("search_two_stage_128q_32k_512to128d", us_ts,
         f"recall@10={recall:.4f};speedup={us_exact/us_ts:.2f}x")


def bench_ivf():
    import jax.numpy as jnp

    from repro.data import synthetic
    from repro.search import ivf

    corpus = jnp.asarray(synthetic.embedding_corpus(32768, 128,
                                                    n_clusters=16,
                                                    intrinsic=48, seed=1))
    t0 = time.perf_counter()
    idx = ivf.build(corpus, n_cells=64, kmeans_iters=6)
    build_s = time.perf_counter() - t0
    q = corpus[:128] + 0.01
    import jax

    srch = jax.jit(lambda a: ivf.search(idx, a, 10, nprobe=8))
    us = _timeit(srch, q)
    rec = ivf.recall_vs_exact(idx, corpus, q, 10, 8)
    emit("ivf_search_128q_32k_nprobe8", us,
         f"recall@10={rec:.3f};build={build_s:.1f}s;scan_frac={8/64:.2f}")


def bench_quant_quick():
    """CPU-budget slice of table3_quant: the quantized tier's
    memory-vs-recall-vs-QPS rows (also writes BENCH_quant.json)."""
    from .table3_quant import run

    rows = run(quick=True)
    for r in rows:
        emit(f"table3.{r['space']}.{r['spec']}",
             r["latency_ms_p50"] * 1e3,
             f"recall@{r['k']}={r['recall_at_k']};"
             f"bytes={r['bytes_per_vector']:.0f}",
             recall=r["recall_at_k"], qps=r["qps"],
             bytes_per_vector=r["bytes_per_vector"],
             build_s=r["build_s"])


def bench_graph_quick():
    """CPU-budget slice of table4_graph: the graph tier's
    recall-vs-QPS-vs-visited-fraction rows (also writes BENCH_graph.json)."""
    from .table4_graph import run

    rows = run(quick=True)
    for r in rows:
        emit(f"table4.{r['space']}.{r['spec']}",
             r["latency_ms_p50"] * 1e3,
             f"recall@{r['k']}={r['recall_at_k']};"
             f"evals={r['distance_evals']:.0f};"
             f"visited={r['visited_frac']:.1%}",
             recall=r["recall_at_k"], qps=r["qps"],
             distance_evals=r["distance_evals"],
             visited_frac=r["visited_frac"], build_s=r["build_s"])


def bench_serve_quick():
    """CPU-budget slice of table5_serve: micro-batched engine QPS vs the
    sequential q=1 loop (also writes BENCH_serve.json)."""
    from .table5_serve import run

    rows = run(quick=True)
    for r in rows:
        emit(f"table5.{r['spec']}", r["latency_ms_p50"] * 1e3,
             f"recall@{r['k']}={r['recall_at_k']};"
             f"speedup={r['speedup']}x;"
             f"batch={r['batch_size_mean']}",
             recall=r["recall_at_k"], qps=r["engine_qps"],
             seq_qps=r["seq_qps"], speedup=r["speedup"],
             batch_size_mean=r["batch_size_mean"], build_s=r["build_s"])


def bench_autotune_quick():
    """CPU-budget slice of table8_autotune: recall-SLO-tuned operating
    points vs hand-picked defaults (also writes BENCH_autotune.json)."""
    from .table8_autotune import run

    rows = run(quick=True)
    for r in rows:
        emit(f"table8.{r['spec']}.slo{r['target_recall']}",
             0.0,
             f"recall={r['recall_holdout']};"
             f"evals_ratio={r['evals_ratio']};"
             f"escalated={r['escalation_rate']:.1%}",
             recall_holdout=r["recall_holdout"],
             tuned_distance_evals=r["tuned_distance_evals"],
             default_distance_evals=r["default_distance_evals"],
             evals_ratio=r["evals_ratio"],
             escalation_rate=r["escalation_rate"])


def bench_table1_quick():
    from .table1_knn import run

    rows = run(n=2048, rae_steps=900, datasets=("imdb_like",),
               methods=("pca", "rae"), quick=True)
    for r in rows:
        emit(f"table1.{r['dataset']}.m{r['m']}.{r['method']}.{r['metric']}",
             r["train_s"] * 1e6, f"top5={r['top5']}")


def bench_fig1_quick():
    from .fig1_weight_decay import run

    rows = run(n=1500, m=256, steps=600,
               lambdas=(0.0, 1e-2, 1e-1, 1.0, 10.0))
    best = max(rows, key=lambda r: r["acc@5"])
    for r in rows:
        emit(f"fig1.lambda{r['weight_decay']}", 0.0,
             f"acc5={r['acc@5']};kappa={r['kappa']:.2f}")
    emit("fig1.best_lambda", 0.0,
         f"lambda={best['weight_decay']};acc5={best['acc@5']};"
         f"kappa={best['kappa']:.2f}")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    bench_kernels()
    bench_rae_train()
    bench_two_stage_search()
    bench_ivf()
    bench_quant_quick()
    bench_graph_quick()
    bench_serve_quick()
    bench_autotune_quick()
    bench_fig1_quick()
    bench_table1_quick()
    wall = time.time() - t0
    os.makedirs("results", exist_ok=True)
    json.dump(ROWS, open("results/bench.json", "w"), indent=1)  # legacy path
    write_bench("run", ROWS, config={"wall_clock_s": round(wall, 1)})
    print(f"# total {wall:.1f}s -> results/bench.json")


if __name__ == "__main__":
    main()
