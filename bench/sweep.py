"""Find a cell's knee: the highest open-loop rate the service sustains.

    python -m bench.sweep --workload sift1m.open-k10 --rates 400,800,1200 \
        --seconds 5 --seed 3

Builds the cell's service once, then offers each rate in turn (the
cell's own mix with ``rate_qps`` replaced) and prints one JSON line per
rate: offered and completed rate, p50/p99 from due time, how late the
generator ran and the batch fill. A rate is sustained while the
completed rate keeps up with the offered one and the tail stays bounded;
the cell's ``rate_qps`` is set once, by hand, at about 0.8x the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import gen, load, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_benchmark(), args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_compile_cache()
    devices = run.chip_devices(cell["chips"])
    cfg, traffic = cell["config"], dict(cell["traffic"])
    data = gen.Corpus(gen.DataSpec.from_config(cfg),
                      int(cfg["data_seed"]))
    corpus = data.host_rows(devices)
    pool = data.queries(devices[0])
    engine = run.build_service(cfg, corpus, int(traffic["k"]), args.seed,
                               devices)
    load.warm_traffic(engine, traffic, pool, 4 * engine.max_batch,
                      args.seed)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic.update(loop="open", rate_qps=rate)
            before = engine.stats()
            w = load.run_window(engine, traffic, pool, args.seconds,
                                args.seed)
            after = engine.stats()
            e2e = run.end_to_end(w, args.seconds, float("nan"), 0.0)
            late = np.asarray(w.sent) - np.asarray(w.due)
            batches = after["batches"] - before["batches"]
            print(json.dumps({
                "rate_qps": rate, "qps": e2e["qps"],
                "p50_ms": e2e["p50_ms"], "p99_ms": e2e["p99_ms"],
                "late_p99_ms": float(np.percentile(late, 99)) * 1e3,
                "failed": int(np.sum(~np.isfinite(np.asarray(w.done)))),
                "batch_fill": (after["requests"] - before["requests"])
                / max(batches, 1)}), flush=True)
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
