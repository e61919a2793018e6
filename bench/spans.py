"""The program's spans and named device programs in a profiler trace.

    python -m bench.spans --workload sift1m.open-k10 --seed 7 --seconds 20

The serving path opens ``jax.profiler.TraceAnnotation`` spans at each
layer's boundary (``SPANS``) and names each jitted program on its hot
path (``rae_encode``, ``ivf_probe``, ``rerank_candidates``, ``flat_scan``;
``shard_scan`` and ``topk_merge`` are scopes inside the mesh scan). This
module reduces a trace to, for each span name: how many, their total and
self time, and the time device 0 sat idle inside them (all of it, and the
part under no child span); for each device program: its busy time, the
union of its ``XLA Ops`` inside its ``XLA Modules`` events; for each scope:
the busy time of the ops whose framework name holds it. Busy times are
averaged over the devices traced, as ``bench/trace.py`` averages.

Run as a command, it makes one traced run of a cell exactly as
``python -m bench.run --trace 1`` does, prints its result line, then one
line with the reduction and the per-layer numbers it gives (``layers``):

* ``index.host_ms`` — device-0 idle inside an ``index.search`` span, per span;
* ``encode.device_ms``, ``stage1.device_ms``, ``rerank.device_ms``,
  ``merge.device_ms`` — busy time of ``rae_encode``; of ``ivf_probe`` or
  the ``shard_scan`` scope; of ``rerank_candidates``; of the
  ``topk_merge`` scope; each per batch (``engine.batch`` spans).

``bench/run.py`` hands its readers only ``bench/trace.py``'s summary, so
no reader of ``BENCHMARK.json`` reads these yet; this command reads them
from the same trace before the harness removes it.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict

from . import trace

SPANS = ("engine.batch", "index.search", "engine.scatter",
         "twostage.encode", "twostage.stage1", "twostage.rerank",
         "ivf.probe", "ivf.count", "sharded.scan", "sharded.merge")
SCOPES = ("shard_scan", "topk_merge")
MODULES_LINE = "XLA Modules"
#: the stat of an ``XLA Ops`` event's metadata that carries the op's
#: framework name, its ``jax.named_scope`` path (on the TPU:
#: ``jit(flat_scan)/.../shard_scan/dot_general:``)
NAME_STAT = "tf_op"


def _xplane(path: str) -> str:
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def program_name(module: str) -> str:
    """``jit_ivf_probe(12)`` -> ``ivf_probe``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def _xspace_message():
    """The few fields of the profiler's ``XSpace`` proto this module reads
    (field numbers of tsl/profiler/protobuf/xplane.proto): planes, their
    event and stat metadata, and a stat's string or interned value.
    ``jax.profiler.ProfileData`` gives an event's own stats but not its
    metadata's, which is where the device's op names keep their scope."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                            package="bench_xspace",
                                            syntax="proto3")
    shapes = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, None), ("event_metadata", 4, "EventEntry"),
                   ("stat_metadata", 5, "StatEntry")],
        "EventEntry": [("key", 1, int), ("value", 2, "XEventMetadata")],
        "StatEntry": [("key", 1, int), ("value", 2, "XStatMetadata")],
        "XEventMetadata": [("name", 2, None), ("stats", 5, "XStat")],
        "XStatMetadata": [("name", 2, None)],
        "XStat": [("metadata_id", 1, int), ("str_value", 5, None),
                  ("ref_value", 7, "uint64")],
    }
    for msg, fields in shapes.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind in fields:
            field = m.field.add(name=name, number=number)
            if kind is None:
                field.type, field.label = f.TYPE_STRING, f.LABEL_OPTIONAL
            elif kind is int:
                field.type, field.label = f.TYPE_INT64, f.LABEL_OPTIONAL
            elif kind == "uint64":
                field.type, field.label = f.TYPE_UINT64, f.LABEL_OPTIONAL
            else:  # a map's value is one message, other messages repeat
                field.type = f.TYPE_MESSAGE
                field.label = (f.LABEL_OPTIONAL if name == "value"
                               else f.LABEL_REPEATED)
                field.type_name = f".bench_xspace.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def framework_names(path: str) -> dict[str, dict[str, str]]:
    """Device plane -> event name -> the framework name its metadata
    carries (``""`` where it has none)."""
    with open(_xplane(path), "rb") as fh:
        space = _xspace_message().FromString(fh.read())
    out: dict[str, dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            md = entry.value
            name = ""
            for st in md.stats:
                if stat_names.get(st.metadata_id) == NAME_STAT:
                    name = (stat_names.get(st.ref_value, "") if st.ref_value
                            else st.str_value)
            names.setdefault(md.name, name)
    return out


def load_events(path: str) -> dict:
    """Events of an ``.xplane.pb`` file (or the newest under a directory):
    ``host`` lists ``[thread, name, start_ns, end_ns]`` of the window span
    and the program's spans; ``modules`` maps a device plane to its
    ``[program, start_ns, end_ns]``; ``ops`` maps it to its ``[start_ns,
    end_ns, framework_name]``."""
    from jax.profiler import ProfileData

    path = _xplane(path)
    data = ProfileData.from_file(path)
    scoped = framework_names(path)
    keep = set(SPANS) | {trace.WINDOW_SPAN}
    out: dict = {"host": [], "modules": {}, "ops": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out["modules"].setdefault(plane.name, []).extend(
                        [program_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns] for e in line.events)
                elif line.name == trace.OPS_LINE:
                    names = scoped.get(plane.name, {})
                    out["ops"].setdefault(plane.name, []).extend(
                        [e.start_ns, e.start_ns + e.duration_ns,
                         names.get(e.name, "")] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [line.name, e.name, e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events if e.name in keep)
    return out


class _Covered:
    """Length of [s, e] covered by merged, sorted intervals."""

    def __init__(self, merged: list[tuple[float, float]]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.prefix = [0.0]
        for s, e in merged:
            self.prefix.append(self.prefix[-1] + e - s)

    def _upto(self, t: float) -> float:
        """Covered length of (-inf, t]."""
        j = bisect.bisect_right(self.starts, t)
        if j == 0:
            return 0.0
        return self.prefix[j - 1] + min(t, self.ends[j - 1]) - \
            self.starts[j - 1]

    def __call__(self, s: float, e: float) -> float:
        return self._upto(e) - self._upto(s) if e > s else 0.0


def _in_scope(name: str, scope: str) -> bool:
    return scope in name.split("/")


def reduce(events: dict) -> dict:
    """``spans``: name -> count, total_s, self_s, idle_s, self_idle_s;
    ``programs``: name -> busy_s; ``scopes``: name -> busy_s;
    ``idle_outside_s``: device-0 idle under no span. Idle is None where the
    trace has no device plane."""
    windows = [(s, e) for _, name, s, e in events["host"]
               if name == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} host "
                         "span: the measured window is unknown")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    planes = sorted(events["ops"])
    n_dev = max(1, len(planes))
    programs: dict[str, float] = defaultdict(float)
    scopes: dict[str, float] = {}
    idle_at = None
    for i, plane in enumerate(planes):
        ops = events["ops"][plane]
        busy = trace._union([(s, e) for s, e, _ in ops], lo, hi)
        covered = _Covered(busy)
        if i == 0:
            idle_at = covered
        for name, s, e in events["modules"].get(plane, []):
            programs[name] += covered(max(s, lo), min(e, hi)) / n_dev / 1e9
        for scope in SCOPES:
            mine = trace._union([(s, e) for s, e, fw in ops
                                 if _in_scope(fw, scope)], lo, hi)
            if mine:
                scopes[scope] = scopes.get(scope, 0.0) + sum(
                    e - s for s, e in mine) / n_dev / 1e9

    def idle(s: float, e: float) -> float:
        return 0.0 if idle_at is None else (e - s) - idle_at(s, e)

    spans: dict[str, dict] = {}
    top: list[tuple[float, float]] = []   # spans under no other span
    by_thread: dict[str, list] = defaultdict(list)
    for thread, name, s, e in events["host"]:
        s, e = max(s, lo), min(e, hi)
        if name != trace.WINDOW_SPAN and e > s:
            by_thread[thread].append((s, -e, name))
    for evs in by_thread.values():
        # spans nest by time on a thread: sorted by start, longest first,
        # each one's parent is the innermost open span that holds it
        stack: list[tuple[float, str]] = []
        for s, neg_e, name in sorted(evs):
            e = -neg_e
            while stack and stack[-1][0] <= s:
                stack.pop()
            dur, gap = (e - s) / 1e9, idle(s, e) / 1e9
            st = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0, "idle_s": 0.0,
                                         "self_idle_s": 0.0})
            st["count"] += 1
            st["total_s"] += dur
            st["self_s"] += dur
            st["idle_s"] += gap
            st["self_idle_s"] += gap
            if stack:
                parent = spans[stack[-1][1]]
                parent["self_s"] -= dur
                parent["self_idle_s"] -= gap
            else:
                top.append((s, e))
            stack.append((e, name))
    outside = (idle(lo, hi) - sum(idle(s, e) for s, e in
                                  trace._union(top, lo, hi))) / 1e9
    if idle_at is None:
        outside = None
        for st in spans.values():
            st["idle_s"] = st["self_idle_s"] = None
    return {"window_s": (hi - lo) / 1e9, "spans": spans,
            "programs": dict(programs), "scopes": scopes,
            "idle_outside_s": outside}


def layers(red: dict) -> dict[str, float]:
    """The per-layer numbers of a reduction, in ms (module doc); a number
    whose source the trace lacks is left out."""
    spans, progs, scopes = red["spans"], red["programs"], red["scopes"]
    batches = spans.get("engine.batch", {}).get("count", 0)
    out = {}
    search = spans.get("index.search")
    if search and search["count"] and search["idle_s"] is not None:
        out["index.host_ms"] = search["idle_s"] / search["count"] * 1e3
    if not batches or not progs:
        return out
    stage1 = progs.get("ivf_probe", scopes.get("shard_scan"))
    for name, busy in (("encode.device_ms", progs.get("rae_encode")),
                       ("stage1.device_ms", stage1),
                       ("rerank.device_ms", progs.get("rerank_candidates")),
                       ("merge.device_ms", scopes.get("topk_merge"))):
        if busy is not None:
            out[name] = busy / batches * 1e3
    return out


def idle_by_span(red: dict) -> dict[str, float]:
    """Device-0 idle seconds, each counted once under the innermost span
    that holds it (``outside``: under none)."""
    out = {name: st["self_idle_s"] for name, st in red["spans"].items()
           if st["self_idle_s"] is not None}
    if red["idle_outside_s"] is not None:
        out["outside"] = red["idle_outside_s"]
    return out


def traced_run(cell: dict, seed: int, seconds: float, devices: list,
               log=print) -> tuple[dict, dict]:
    """``bench.run``'s traced run of ``cell`` and the reduction of its
    trace, read before the harness removes the trace."""
    from . import run

    kept = {}
    harness_load = trace.load_events

    def load_and_reduce(path):
        kept["red"] = reduce(load_events(path))
        return harness_load(path)

    trace.load_events = load_and_reduce
    try:
        result = run.run_cell(cell, seed, seconds, True, devices, log=log)
    finally:
        trace.load_events = harness_load
    return result, kept["red"]


def main(argv: list[str] | None = None) -> int:
    from . import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_benchmark(), args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_compile_cache()
    try:
        devices = run.chip_devices(cell["chips"])
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result, red = traced_run(cell, args.seed, args.seconds, devices,
                             log=lambda line: print(line, file=sys.stderr,
                                                    flush=True))
    print(json.dumps(result), flush=True)
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "layers": layers(red), "idle_by_span": idle_by_span(red),
                      **red}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
