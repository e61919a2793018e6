"""Readings that set the limits of ``correct``: the control and faults.

    python -m bench.control --workload cohere768.open-k100 --mode control \
        --seeds 11,12,13 --seconds 3

Each seed is one whole run of the cell (data, service, window, checks)
in this one process, with the service swapped:

* ``control`` — the reference put in the program's place, computed one
  precision below the configuration's (``high``, three bf16 passes, for
  float32 at ``highest``);
* ``stage1`` — the program, with every stage-1 candidate id moved to the
  next row (an answer altered where stage 1 produces it);
* ``altered`` — the program, with each batch's first answer id moved to
  the next row (an answer altered where the index produces it);
* ``half_batch`` — the program, with the second half of each batch
  answered by the first half's rows;
* ``program`` — the program as it is (the lower readings).

The last line is a JSON list of each seed's compared numbers. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from . import gen, reference, run


class ReferenceIndex:
    """``bench/reference.py``'s exact search behind the interface
    ``SearchEngine`` drives (``search``, ``fingerprint``, ``dim``...)."""

    kind = "reference"

    def __init__(self, shards, rows: int, dim: int, precision: str):
        self.shards = shards
        self.ntotal = rows
        self.dim = dim
        self.bytes_per_vector = 4.0 * dim
        self.precision = precision

    def _require_built(self) -> None:
        return None

    def fingerprint(self) -> str:
        return f"reference-{self.precision}-{self.ntotal}"

    def search(self, queries, k, alive=None, params=None):
        from repro.api import SearchResult

        t0 = time.perf_counter()
        d, i = reference.exact_topk(np.asarray(queries, np.float32),
                                    self.shards, k,
                                    precision=self.precision,
                                    q_chunk=len(queries))
        return SearchResult(scores=-d, indices=i,
                            latency_s=time.perf_counter() - t0,
                            stats={"distance_evals": float(self.ntotal)})


def control_service(precision: str = "high"):
    """The reference, at ``precision``, behind a started, warmed engine."""
    def service(cfg, corpus, k, seed, devices):
        from repro.serve import SearchEngine

        spec = gen.DataSpec.from_config(cfg)
        data = gen.Corpus(spec, int(cfg["data_seed"]))
        shards = reference.shard_blocks(data.block, spec.rows,
                                        spec.block_rows, devices)
        index = ReferenceIndex(shards, spec.rows, spec.dim, precision)
        engine = SearchEngine(index, **cfg["engine"]).start()
        engine.warmup(ks=(k,), seed=run.program_seed(seed))
        return engine
    return service


def _next_row(ids: np.ndarray, n: int) -> np.ndarray:
    return np.where(ids >= 0, (ids + 1) % n, ids)


def fault_service(fault: str):
    """The program, built as usual, with one fault planted in its timed
    path: ``stage1``, ``altered`` or ``half_batch`` (see module doc)."""
    def service(cfg, corpus, k, seed, devices):
        engine = run.build_service(cfg, corpus, k, seed, devices)
        target = engine.index.base if fault == "stage1" else engine.index
        inner = target.search
        n = engine.index.ntotal

        def search(queries, kk, alive=None, params=None):
            res = inner(queries, kk, alive=alive, params=params)
            ids = np.array(res.indices)
            if fault in ("stage1", "altered"):
                rows = slice(None) if fault == "stage1" else slice(0, 1)
                ids[rows] = _next_row(ids[rows], n)
                res.indices = ids
            elif fault == "half_batch":
                half = len(ids) // 2
                rest = len(ids) - half
                scores = np.array(res.scores)
                ids[rest:], scores[rest:] = ids[:half], scores[:half]
                res.indices, res.scores = ids, scores
            else:
                raise ValueError(f"unknown fault {fault!r}")
            return res

        target.search = search
        return engine
    return service


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=("program", "control", "stage1", "altered",
                             "half_batch"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_benchmark(), args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_compile_cache()
    devices = run.chip_devices(cell["chips"])
    service = {"program": run.build_service,
               "control": control_service()}.get(args.mode)
    if service is None:
        service = fault_service(args.mode)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           service=service,
                           log=lambda line: print(line, file=sys.stderr,
                                                  flush=True))
        out.append({"seed": seed, "correct": res["correct"],
                    "checks": res["checks"]})
        print(json.dumps(out[-1]), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
