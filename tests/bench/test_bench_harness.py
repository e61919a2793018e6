"""The benchmark harness (``bench/``) on the CPU at a tiny size.

Nothing here measures speed: these tests check that every cell resolves
by name, that a run's last line has the contract's keys, that traffic
is a pure function of the seed, that the reference is exact, and that
the trace reduction reads a recorded trace.
"""
from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, gen, load, reference, run, trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def tiny_cell(config: str = "sift1m-rae64-ivf256",
              traffic: str = "sift1m.open-k10", **mix) -> dict:
    """A cell of ``config`` under ``traffic`` (their files, found by name
    as a BENCHMARK.json entry would find them), cut to a size the CPU
    runs in seconds."""
    bench = copy.deepcopy(BENCH)
    path = f"bench/configs/{config}.json"
    chips = json.loads((ROOT / path).read_text())["chips"]
    if config not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({"name": config, "source": "test",
                                 "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny", "config": config,
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.get("workloads", []).append("tiny")
    cell = run.resolve(bench, "tiny")
    cfg = cell["config"]
    cfg.update(rows=3000, query_pool=64)
    cfg["data"]["block_rows"] = 1024
    if "reducer_fit_rows" in cfg:
        cfg["reducer_fit_rows"] = 1000
        cfg["reducer_kw"]["steps"] = 20
    cfg["index"] = re.sub(r"IVF\d+", "IVF16", cfg["index"])
    if cell["traffic"]["loop"] == "open":
        cell["traffic"]["rate_qps"] = 40
    else:
        cell["traffic"]["clients"] = 8
    cell["traffic"].update(mix)
    return cell


def cpu_run(cell: dict, traced: bool = False, seed: int = 2 ** 31 + 3,
            **kw) -> dict:
    import jax

    return run.run_cell(cell, seed, 1.0, traced,
                        jax.devices()[:cell["chips"]], log=lambda _: None,
                        **kw)


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_workload_resolves_by_name(workload):
    cell = run.resolve(BENCH, workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in BENCH["configs"]
                     if c["name"] == entry["config"])
    assert cell["config"]["name"] == entry["config"]
    assert cfg_entry["file"] == f"bench/configs/{entry['config']}.json"
    assert cell["config"]["chips"] == entry["chips"] == cell["chips"]
    assert cell["traffic"]["loop"] in ("open", "closed")
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"] and set(cell["readers"]) == {
        m["name"] for m in cell["per_layer"]}
    # a per-layer metric moves an end-to-end metric that its cell reports
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    assert all(callable(r) for r in cell["readers"].values())
    assert set(cell["config"]["correct"]) <= {"score_err", "rank_gap",
                                              "recall_miss"}


def test_benchmark_json_keeps_the_contract_shape():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["paths"] == ["bench", "tests/bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + WORKLOADS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert set(c["reduced"]) == set(
            json.loads((ROOT / c["file"]).read_text())["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(WORKLOADS) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_a_new_cell_config_mix_and_metric_need_only_new_files(tmp_path):
    """Adding a configuration, a traffic mix and a per-layer metric is new
    files plus new BENCHMARK.json entries; no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = copy.deepcopy(BENCH)
    cfg = json.loads((ROOT / "bench/configs/sift1m-rae64-ivf256.json")
                     .read_text())
    cfg.update(name="tiny-flat", index="Flat", rows=2048, query_pool=32,
               seeded=[], reducer_kw={}, index_kw={}, reducer_fit_rows=0,
               correct={"rank_gap": 1e-4})
    cfg["data"]["block_rows"] = 1024
    (tmp_path / "bench/configs/tiny-flat.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/tiny.closed2.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "k": 5, "draw": "uniform"}))
    (tmp_path / "bench/metrics/engine.batches.py").write_text(
        "def read(run):\n"
        "    return run['engine_after']['batches'] - "
        "run['engine_before']['batches']\n")
    bench["configs"].append({"name": "tiny-flat", "source": "test",
                             "file": "bench/configs/tiny-flat.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.closed2", "config": "tiny-flat",
                               "traffic": "tiny.closed2", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "engine.batches", "unit": "batches",
                               "better": "lower", "source": "program_counter",
                               "layer": "serve/engine.py SearchEngine "
                                        "scheduler",
                               "moves": "qps", "workloads": ["tiny.closed2"]})
    cell = run.resolve(bench, "tiny.closed2", root=tmp_path,
                       bench_dir=tmp_path / "bench")
    assert cell["config"]["index"] == "Flat"
    assert cell["traffic"]["clients"] == 2
    assert "engine.batches" in cell["readers"]
    old = run.resolve(bench, "sift1m.open-k10", root=tmp_path,
                      bench_dir=tmp_path / "bench")
    assert "engine.batches" not in old["readers"]
    res = cpu_run(cell, traced=True)
    assert res["correct"] is True
    assert res["metrics"]["engine.batches"]["value"] > 0


def test_peaks_table_is_keyed_by_device_kind():
    assert run.device_peaks("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        run.device_peaks("cpu")


# ---------------------------------------------------------------------------
# a whole run on the CPU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("traced", [False, True])
def test_result_line_has_exactly_the_contract_keys(traced):
    cell = tiny_cell()
    res = cpu_run(cell, traced=traced)
    keys = RESULT_KEYS + (["breakdown"] if traced else []) + ["checks"]
    assert list(res) == keys
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 40
    want = cell["per_layer"] if traced else cell["end_to_end"]
    got = set(res["metrics"])
    if traced:  # the CPU trace has no device plane: device.idle is absent
        assert got == {m["name"] for m in want} - {"device.idle",
                                                   "device.idle.open"}
    else:
        assert got == {m["name"] for m in want}
    for name, m in res["metrics"].items():
        assert np.isfinite(m["value"]) and m["unit"]
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if traced:
        assert set(res["device"]) >= {"busy_s", "window_s"}
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(res["checks"]) == {"failed"} | set(cell["config"]["correct"])
    json.dumps(res, allow_nan=False)


def test_closed_loop_runs_and_fills_batches():
    cell = tiny_cell(traffic="sift1m.closed64-k10")
    res = cpu_run(cell, traced=True)
    assert res["correct"] is True
    assert res["metrics"]["engine.batch_fill"]["value"] > 1.0
    assert res["metrics"]["stage1.evals_per_query"]["value"] > 0


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "bench.run", "--workload",
           "sift1m.open-k10", "--seed", str(2 ** 31 + 9), "--seconds", "1"]
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr
    # a checkout holding only the benchmark's own files
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 17, 2 ** 40 + 1])
def test_open_schedule_and_draw_are_fixed_by_the_seed(seed):
    a = load.open_schedule(800.0, 10.0, seed)
    b = load.open_schedule(800.0, 10.0, seed)
    other = load.open_schedule(800.0, 10.0, seed + 1)
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(other) == 8000
    assert not np.array_equal(a, other)
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0) and a[-1] < 10.0
    np.testing.assert_array_equal(load.query_draw(10_000, 500, seed),
                                  load.query_draw(10_000, 500, seed))
    assert not np.array_equal(load.query_draw(10_000, 500, seed),
                              load.query_draw(10_000, 500, seed + 1))


@pytest.mark.parametrize("mix", [
    {"draw": "zipf", "zipf_s": 1.0},
    {"profile": [[0.5, 2.0], [0.5, 0.0]]},
])
def test_later_mixes_are_data_only(mix):
    """A Zipf draw and on/off bursts are parameters of the one generator,
    so such a mix is a new data file and nothing else."""
    traffic = {"loop": "open", "arrivals": "poisson", "rate_qps": 400,
               "k": 10, "draw": "uniform", **mix}
    due = load.open_schedule(400.0, 5.0, 3, traffic.get("profile"))
    draw = load.mix_draw(traffic, 10_000, len(due), 3)
    np.testing.assert_array_equal(
        draw, load.mix_draw(traffic, 10_000, len(due), 3))
    assert len(due) == 2000 and np.all(np.diff(due) >= 0)
    assert due[0] == 0.0 and due[-1] < 5.0
    if "profile" in mix:  # every request falls in an "on" half second
        assert np.all(np.mod(due, 1.0) <= 0.5 + 1e-9)
    else:  # the most popular query is asked far more than 1 in 10,000
        assert np.bincount(draw).max() > 0.05 * len(draw)


@pytest.mark.parametrize("n", [999, 1000, 2500])
def test_every_seed_asks_the_same_queries_in_passes(n):
    """``"passes"`` asks each query of the pool once a pass, so two seeds
    ask the same queries as often, in another order."""
    a = load.query_draw(1000, n, 2 ** 31 + 21, "passes")
    b = load.query_draw(1000, n, 2 ** 31 + 22, "passes")
    assert len(a) == len(b) == n and not np.array_equal(a, b)
    for draw in (a, b):
        full = n // 1000
        for p in range(full):
            np.testing.assert_array_equal(
                np.sort(draw[p * 1000:(p + 1) * 1000]), np.arange(1000))
        assert np.bincount(draw, minlength=1000).max() <= full + 1
    np.testing.assert_array_equal(
        np.sort(a[:full * 1000]), np.sort(b[:full * 1000]))


def test_every_seed_offers_the_same_gaps():
    a = np.diff(load.open_schedule(300.0, 5.0, 1), append=5.0)
    b = np.diff(load.open_schedule(300.0, 5.0, 2), append=5.0)
    np.testing.assert_allclose(np.sort(a), np.sort(b), rtol=1e-9, atol=1e-12)


# ---------------------------------------------------------------------------
# data and reference
# ---------------------------------------------------------------------------
def brute_force_f64(x: np.ndarray, q: np.ndarray, k: int) -> np.ndarray:
    d = ((q[:, None, :].astype(np.float64)
          - x[None, :, :].astype(np.float64)) ** 2).sum(-1)
    ids = np.broadcast_to(np.arange(x.shape[0]), d.shape)
    return np.lexsort((ids, d), axis=1)[:, :k]


@pytest.mark.parametrize("kind", ["gaussian", "integer_ties"])
def test_reference_agrees_with_float64_brute_force(kind):
    import jax

    rng = np.random.default_rng(5)
    if kind == "gaussian":
        x = rng.standard_normal((1000, 24)).astype(np.float32)
        q = rng.standard_normal((37, 24)).astype(np.float32)
    else:  # small integers: exact in float32, and many equal distances
        x = rng.integers(-2, 3, (1000, 6)).astype(np.float32)
        q = rng.integers(-2, 3, (37, 6)).astype(np.float32)
    dev = jax.devices()[0]
    shards = reference.shards_from_array(x, 128, [dev, dev, dev])
    d, ids = reference.exact_topk(q, shards, 15, q_chunk=16)
    np.testing.assert_array_equal(ids, brute_force_f64(x, q, 15))
    np.testing.assert_allclose(d, reference.rescore(x, q, ids), rtol=1e-4,
                               atol=1e-4)


def test_generated_blocks_repeat_and_queries_are_held_out():
    import jax

    spec = gen.DataSpec(rows=2500, dim=16, query_pool=20, n_clusters=4,
                        intrinsic=8, normalize=True, block_rows=1024)
    a, b = gen.Corpus(spec, 2 ** 31 + 5), gen.Corpus(spec, 2 ** 31 + 5)
    rows = a.host_rows()
    assert rows.shape == (2500, 16)
    np.testing.assert_array_equal(rows, b.host_rows())
    np.testing.assert_array_equal(np.asarray(a.block(2))[:2500 - 2048],
                                  rows[2048:])
    np.testing.assert_allclose(np.linalg.norm(rows, axis=1), 1.0, rtol=1e-5)
    q = a.queries()
    assert q.shape == (20, 16)
    assert not np.isin(q, rows).all(axis=1).any()
    other = gen.Corpus(spec, 2 ** 31 + 6).host_rows()
    assert not np.array_equal(rows, other)
    assert jax.devices()  # made on the default device


def test_recall_and_checks():
    truth = np.array([[1, 2, 3], [4, 5, 6]])
    rec = check.recall_per_request([np.array([3, 2, 9]), None], truth, 3)
    np.testing.assert_allclose(rec, [2 / 3, 0.0])
    ok, checks = check.verdict({"score_err": 1e-7}, 0, {"score_err": 1e-5})
    assert ok and list(checks) == ["failed", "score_err"]
    ok, _ = check.verdict({"score_err": 1e-7}, 1, {"score_err": 1e-5})
    assert not ok
    ok, _ = check.verdict({"score_err": float("inf")}, 0, {"score_err": 1.0})
    assert not ok


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_trace_reduction_by_hand():
    s = 1_000_000_000
    events = {
        "device": {"/device:TPU:0": [["fusion.1", s + 0, s + 100],
                                     ["fusion.2", s + 50, s + 150],
                                     ["copy", s + 400, s + 500]],
                   "/device:TPU:1": [["fusion.1", s + 0, s + 300]]},
        "host": [["python", trace.WINDOW_SPAN, s + 0, s + 1000],
                 ["engine", "PjitFunction(probe)", s + 140, s + 420]],
    }
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0 busy 150 + 100, device 1 busy 300: mean 275 ns
    assert r["busy_s"] == pytest.approx(275e-9)
    assert r["idle"] == pytest.approx(1 - 0.275)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert r["idle_gaps"][0] == ["host idle", pytest.approx(500e-9)]
    assert r["idle_gaps"][1] == ["PjitFunction(probe)",
                                 pytest.approx(250e-9)]
    # without the window span there is no window to measure idle over
    events["host"] = events["host"][1:]
    with pytest.raises(ValueError, match="window"):
        trace.reduce(events)


RECORDED = Path(__file__).with_name("data") / "small.xplane.pb"


def test_trace_reduction_of_a_recorded_trace():
    """A profiler trace recorded with JAX 0.9.0 on the CPU backend: three
    rounds of two jitted ops inside the window span, 2 ms of host sleep
    between rounds. The CPU backend writes no device plane, so the device
    was busy for none of the span's 0.521189615 s."""
    events = trace.load_events(str(RECORDED))
    assert events["device"] == {}
    names = {e[1] for e in events["host"]}
    assert trace.WINDOW_SPAN in names and "PjitFunction(<lambda>)" in names
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(0.521189615, rel=1e-12)
    assert r["busy_s"] == 0.0 and r["idle"] == 1.0
    assert r["device_ops"] == [] and r["idle_gaps"] == []
