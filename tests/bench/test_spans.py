"""``bench/spans.py``: the program's spans and named programs in a trace.

The reduction is checked on hand-made events whose every number is worked
out below, and the command's traced run on the CPU at a tiny size.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_bench_harness import tiny_cell  # noqa: E402

from bench import spans, trace  # noqa: E402

B = 1_000_000_000  # a trace's clock does not start at 0


def _events() -> dict:
    def at(*iv):
        return [B + t for t in iv]

    host = [["main", trace.WINDOW_SPAN, *at(0, 1000)]] + [
        ["ex", name, *at(s, e)] for name, s, e in [
            ("engine.batch", 100, 600),
            ("index.search", 110, 500),
            ("twostage.encode", 120, 200),
            ("twostage.stage1", 210, 400),
            ("ivf.probe", 250, 400),
            ("twostage.rerank", 410, 490),
            ("engine.scatter", 510, 590),
            ("engine.batch", 900, 1200),   # runs past the window's end
            ("index.search", 910, 990)]]
    return {
        "host": host,
        "modules": {
            "/device:TPU:0": [["rae_encode", *at(125, 195)],
                              ["ivf_probe", *at(255, 395)],
                              ["rerank_candidates", *at(415, 485)],
                              ["flat_scan", *at(940, 1060)]],
            "/device:TPU:1": [["flat_scan", *at(0, 160)],
                              ["flat_scan", *at(890, 930)]],
        },
        "ops": {
            "/device:TPU:0": [
                [*at(130, 190), "jit(rae_encode)/dot_general"],
                [*at(260, 300), "jit(ivf_probe)/shard_scan/x"],
                [*at(300, 390), "jit(ivf_probe)/gather"],
                [*at(420, 480), "jit(rerank_candidates)/reduce"],
                [*at(950, 1050), "jit(flat_scan)/topk_merge/all_gather"]],
            "/device:TPU:1": [
                [*at(0, 100), "jit(flat_scan)/shard_map/shard_scan/dot"],
                [*at(50, 150), "jit(flat_scan)/shard_map/shard_scan/top_k"],
                [*at(900, 920), "jit(flat_scan)/shard_map/topk_merge/sort"]],
        },
    }


def test_reduction_by_hand():
    r = spans.reduce(_events())
    ns = pytest.approx
    assert r["window_s"] == ns(1000e-9)
    # device 0 is busy [130,190] [260,390] [420,480] [950,1000]: 300 of
    # the window's 1000; each span's idle is its length less that overlap,
    # its self time and self idle are less its children's
    want = {  # count, total, self, idle, self idle (ns)
        "engine.batch": (2, 500 + 100, 30 + 20, 250 + 50, 30 + 10),
        "index.search": (2, 390 + 80, 40 + 80, 140 + 40, 40 + 40),
        "twostage.encode": (1, 80, 80, 20, 20),
        "twostage.stage1": (1, 190, 40, 60, 40),
        "ivf.probe": (1, 150, 150, 20, 20),
        "twostage.rerank": (1, 80, 80, 20, 20),
        "engine.scatter": (1, 80, 80, 80, 80),
    }
    assert set(r["spans"]) == set(want)
    for name, (n, total, own, idle, own_idle) in want.items():
        assert r["spans"][name] == {
            "count": n, "total_s": ns(total * 1e-9), "self_s": ns(own * 1e-9),
            "idle_s": ns(idle * 1e-9), "self_idle_s": ns(own_idle * 1e-9)}
    assert r["idle_outside_s"] == ns(400e-9)   # 700 idle, 300 under spans
    by_span = spans.idle_by_span(r)
    assert sum(by_span.values()) == ns(700e-9)
    assert by_span["outside"] == ns(400e-9)
    # a program's busy time is its modules' overlap with the ops' union,
    # averaged over the two devices: flat_scan is 50 on device 0 (its
    # module clipped to the window) and 150 + 20 on device 1
    assert r["programs"] == {"rae_encode": ns(30e-9), "ivf_probe": ns(65e-9),
                             "rerank_candidates": ns(30e-9),
                             "flat_scan": ns(110e-9)}
    # a scope by its ops' framework names: shard_scan 40 + 150, topk_merge
    # 50 (clipped) + 20, over two devices
    assert r["scopes"] == {"shard_scan": ns(95e-9),
                           "topk_merge": ns(35e-9)}
    # per batch (2) or per index.search span (2), in ms
    assert spans.layers(r) == {"index.host_ms": ns(90e-6),
                               "encode.device_ms": ns(15e-6),
                               "stage1.device_ms": ns(32.5e-6),
                               "rerank.device_ms": ns(15e-6),
                               "merge.device_ms": ns(17.5e-6)}


def test_without_a_device_plane_idle_is_unknown():
    events = _events()
    events["ops"], events["modules"] = {}, {}
    r = spans.reduce(events)
    assert r["spans"]["engine.batch"]["count"] == 2
    assert r["spans"]["engine.batch"]["self_s"] == pytest.approx(50e-9)
    assert r["spans"]["engine.batch"]["idle_s"] is None
    assert r["idle_outside_s"] is None and r["programs"] == {}
    assert spans.layers(r) == {} and spans.idle_by_span(r) == {}
    events["host"] = events["host"][1:]
    with pytest.raises(ValueError, match="window"):
        spans.reduce(events)


@pytest.mark.parametrize("module,name", [("jit_ivf_probe(12)", "ivf_probe"),
                                         ("jit_rae_encode", "rae_encode"),
                                         ("fusion.3", "fusion.3")])
def test_program_names(module, name):
    assert spans.program_name(module) == name


def test_traced_run_reads_every_span_of_the_serving_path():
    cell = tiny_cell()
    res, red = spans.traced_run(cell, 2 ** 31 + 3, 1.0,
                                jax.devices()[:1], log=lambda _: None)
    assert res["correct"] is True and "breakdown" in res
    got = red["spans"]
    assert set(spans.SPANS) - {"sharded.scan", "sharded.merge"} <= set(got)
    batches = got["engine.batch"]["count"]
    assert batches >= 1
    for name in ("index.search", "engine.scatter", "twostage.encode",
                 "twostage.stage1", "twostage.rerank", "ivf.probe",
                 "ivf.count"):
        assert got[name]["count"] == batches, name
    assert got["engine.batch"]["total_s"] >= got["index.search"]["total_s"]
    assert red["programs"] == {}   # the CPU backend writes no device plane
    assert spans.layers(red) == {}
    assert trace.load_events is not None and \
        trace.load_events.__module__ == "bench.trace"


def test_framework_names_come_from_the_event_metadata(tmp_path):
    """An op's scope path rides in its event metadata's ``tf_op`` stat,
    often as an interned string (a reference to a stat metadata entry's
    name); other stats holding a path are not it."""
    space = spans._xspace_message()()
    dev = space.planes.add(name="/device:TPU:0")
    for sid, name in [(7, "tf_op"), (8, "long_name"),
                      (9, "jit(flat_scan)/shard_map/topk_merge/all_gather")]:
        entry = dev.stat_metadata.add(key=sid)
        entry.value.name = name
    gather = dev.event_metadata.add(key=3).value
    gather.name = "%all-gather.1 = f32[16,400] all-gather(%x)"
    gather.stats.add(metadata_id=8, str_value="%all-gather.1 = f32[16,400]")
    gather.stats.add(metadata_id=7, ref_value=9)
    fusion = dev.event_metadata.add(key=4).value
    fusion.name = "%fusion.2"
    fusion.stats.add(metadata_id=8, str_value="a/b")
    fusion.stats.add(metadata_id=7, str_value="jit(flat_scan)/shard_scan/dot")
    copy = dev.event_metadata.add(key=5).value
    copy.name = "%copy.1"
    copy.stats.add(metadata_id=8, str_value="no path here")
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "engine.batch"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert spans.framework_names(str(path)) == {"/device:TPU:0": {
        "%all-gather.1 = f32[16,400] all-gather(%x)":
            "jit(flat_scan)/shard_map/topk_merge/all_gather",
        "%fusion.2": "jit(flat_scan)/shard_scan/dot",
        "%copy.1": ""}}
