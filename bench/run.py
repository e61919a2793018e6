"""Run one benchmark cell on the chip and print its result line.

    python -m bench.run --workload sift1m.open-k10 --seed 7 --seconds 10 --trace 0

One process: make the deployment's data on the device from the seed its
configuration fixes, build the index through the program's public entry
points (``repro.api.index_factory`` -> ``build`` ->
``repro.serve.SearchEngine``), warm the cell's shapes, drive the cell's
traffic, drawn from ``--seed``, for ``--seconds``, then free the
program's state and compare every answer of the window with the
exact search of ``bench/reference.py``. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), and last ``checks``,
each compared number beside its limit.

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, ``bench/traffic/<traffic>.json`` and one reader
``bench/metrics/<metric>.py`` per per-layer metric. There is no CPU
path: without as many TPU chips as the cell asks for it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from . import check, gen, load, reference, trace  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: warm requests sent through the serving path before the window, as
#: multiples of the engine's largest batch
WARM_BATCHES = 4


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(bench: dict, workload: str, root: Path = ROOT,
            bench_dir: Path = BENCH_DIR) -> dict:
    """The cell named ``workload``: its entry, configuration, traffic,
    end-to-end metrics and per-layer metric readers."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[cell["config"]]["file"]) as f:
        cfg = json.load(f)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": cfg,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": per_layer,
        "readers": {m["name"]: load_reader(m["name"], bench_dir)
                    for m in per_layer},
    }


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------
def chip_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; ``NoChip`` where there are fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    device_peaks(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program, so
    that only a checkout's first run of a cell compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits do not compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


def device_peaks(kind: str) -> dict[str, float]:
    """The chip's published peaks (``bench/peaks.json``); a device that is
    not in the table is an error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no count, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def program_seed(seed: int) -> int:
    return seed % (2 ** 31 - 1)


class GcPauses:
    """Python's garbage-collection pauses while it is on: how many, by
    generation, and their total and longest milliseconds."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.total_ms = 0.0
        self.max_ms = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        ms = (time.perf_counter() - self._t0) * 1e3
        self.n[info["generation"]] += 1
        self.total_ms += ms
        self.max_ms = max(self.max_ms, ms)

    def close(self) -> dict:
        gc.callbacks.remove(self._on)
        return {"collections_by_gen": self.n, "total_ms": self.total_ms,
                "max_ms": self.max_ms}


def build_service(cfg: dict, corpus: np.ndarray, k: int, seed: int,
                  devices: list):
    """Build the configured stack and a started, warmed ``SearchEngine``
    through the program's public entry points."""
    from repro import api
    from repro.serve import SearchEngine

    kw = {}
    if len(devices) > 1:
        from repro.launch.mesh import make_host_mesh
        from repro.models.common import MeshCtx

        kw["ctx"] = MeshCtx(mesh=make_host_mesh())
    seeded = {"reducer_kw": dict(cfg.get("reducer_kw", {})),
              "index_kw": dict(cfg.get("index_kw", {}))}
    for group in cfg.get("seeded", []):
        # the deployment's one fitted index, the same in every run
        seeded[group]["seed"] = program_seed(int(cfg["build_seed"]))
    index = api.index_factory(cfg["index"], metric=cfg["metric"],
                              reducer_kw=seeded["reducer_kw"],
                              index_kw=seeded["index_kw"], **kw)
    if cfg.get("reducer_fit_rows"):
        index.reducer.fit(corpus[:cfg["reducer_fit_rows"]])
    index.build(corpus)
    engine = SearchEngine(index, **cfg["engine"]).start()
    engine.warmup(ks=(k,), seed=program_seed(seed))
    return engine


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def _percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if x.size else float("nan")


def end_to_end(w: load.Window, seconds: float, recall: float,
               setup_s: float) -> dict[str, float]:
    due, done = np.asarray(w.due), np.asarray(w.done)
    ok = np.isfinite(done)
    # a failed or unanswered request waited until the window was closed
    latency = np.where(ok, done, w.closed) - due
    in_window = ok & (done >= w.start) & (done <= w.end)
    return {
        "qps": float(in_window.sum() / seconds),
        "p50_ms": _percentile(latency, 50) * 1e3,
        "p99_ms": _percentile(latency, 99) * 1e3,
        "recall_at_k": recall,
        "setup_s": setup_s,
    }


def spread_detail(w: load.Window, slices: int = 10) -> dict:
    """Where in the window the latency went, for reading a noisy run:
    the p99 of each tenth of the window (by due time), the slowest
    request and when it was due, the index's own batch time, and the
    batches that took the index over 50 ms, with when they ended."""
    due, done = np.asarray(w.due), np.asarray(w.done)
    latency = np.where(np.isfinite(done), done, w.closed) - due
    edges = np.linspace(w.start, w.end, slices + 1)
    part = np.clip(np.searchsorted(edges, due, side="right") - 1,
                   0, slices - 1)
    idx = np.asarray(w.index_latency)
    # one entry per batch: its requests share the batch's latency
    slow = {}
    for lat, t in zip(idx, np.asarray(w.done)):
        if np.isfinite(lat) and lat > 0.05:
            slow[float(lat)] = round(float(t - w.start), 3)
    idx = idx[np.isfinite(idx)]
    worst = int(np.argmax(latency)) if latency.size else 0
    return {
        "batches_over_50ms": [[round(k * 1e3, 1), v] for k, v in
                              sorted(slow.items(), reverse=True)[:8]],
        "p99_ms_by_tenth": [round(_percentile(latency[part == i], 99) * 1e3,
                                  3) for i in range(slices)],
        "max_ms": _percentile(latency, 100) * 1e3,
        "max_at_s": float(due[worst] - w.start) if latency.size else 0.0,
        "index_ms_p50_p99_max": [_percentile(idx, q) * 1e3
                                 for q in (50, 99, 100)],
    }


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             devices: list, service=build_service, log=print) -> dict:
    """Set up, drive the window, free the program, check the answers.
    ``service(cfg, corpus, k, seed, devices)`` returns a started, warmed
    engine; the control and the tests put other services in its place."""
    import jax

    cfg, traffic = cell["config"], cell["traffic"]
    k = int(traffic["k"])
    spec = gen.DataSpec.from_config(cfg)
    # the deployment's one dataset; --seed draws only the traffic
    data = gen.Corpus(spec, int(cfg["data_seed"]))
    corpus = data.host_rows(devices)
    pool = data.queries(devices[0])
    engine = service(cfg, corpus, k, seed, devices)
    load.warm_traffic(engine, traffic, pool,
                      WARM_BATCHES * engine.max_batch, seed)
    before = engine.stats()
    # the set-up's objects live as long as the service: a full collection
    # in the window would scan them all again (tens of ms, once per run,
    # at a point set by the set-up), so they are collected once here and
    # left out of later collections, as a long-running server's are
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    compiles = CompileCounter()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        # host TraceMe events and the harness's spans, but no per-call
        # Python tracing: it would slow the host the trace is measuring
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            w = load.run_window(engine, traffic, pool, seconds, seed)
    finally:
        if traced:
            jax.profiler.stop_trace()
    n_compiles = compiles.n
    gc_pauses = pauses.close()
    gc.unfreeze()
    setup_s = w.start - PROCESS_START
    after = engine.stats()
    peak = memory_peak(devices)
    engine.stop()
    del engine
    gc.collect()

    reduced = None
    if traced:
        reduced = trace.reduce(trace.load_events(tdir))
        shutil.rmtree(tdir, ignore_errors=True)

    # the reference, once the program's state is freed
    need, where = np.unique(np.asarray(w.pool_idx, np.int64),
                            return_inverse=True)
    shards = reference.shard_blocks(data.block, spec.rows, spec.block_rows,
                                    devices)
    _, truth_ids = reference.exact_topk(pool[need], shards, k)
    del shards
    truth = truth_ids[where]
    found = check.numbers(w, pool, truth, corpus, k, seed)
    failed = int(np.sum(~np.isfinite(np.asarray(w.done))))
    ok, checks = check.verdict(found, failed, cfg["correct"])

    e2e = end_to_end(w, seconds, found["recall"], setup_s)
    run = {"window": w, "seconds": seconds, "engine_before": before,
           "engine_after": after, "trace": reduced}
    if traced:
        metrics = {}
        for m in cell["per_layer"]:
            v = cell["readers"][m["name"]](run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": len(w.due), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks

    late = np.asarray(w.sent) - np.asarray(w.due)
    log(json.dumps({"workload": cell["name"], "seed": seed,
                    "generator_late_p99_ms": _percentile(late, 99) * 1e3,
                    "compiles_in_window": n_compiles,
                    "end_to_end": e2e, "numbers": found,
                    "spread": spread_detail(w), "gc": gc_pauses,
                    "errors": w.errors[:5]}))
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(load_benchmark(), args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache()
    try:
        devices = chip_devices(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices,
                      log=lambda line: print(line, file=sys.stderr,
                                             flush=True))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
