import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step / prefill /
decode_step / serve / retrieval+top-k) with production shardings on the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh, compiles it, and
records:
  * memory_analysis()  — per-device argument/output/temp bytes (fits check),
  * cost_analysis()    — per-device HLO FLOPs/bytes (scan bodies counted
                         once),
  * loop-adjusted collective traffic from the compiled HLO
    (launch/hlo_analysis.py),
  * sharding fallbacks (logical axes that degraded to replication).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax

from ..configs import ARCH_IDS, get_arch, get_shapes
from ..distributed.partitioning import default_rules
from ..models.common import MeshCtx
from ..models.registry import build_cell
from . import hlo_analysis
from .mesh import make_production_mesh


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> dict:
    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = MeshCtx(mesh=mesh, rules=default_rules(multi_pod=multi_pod))
    prog = build_cell(arch_id, shape_name, ctx)

    lowered = prog.lower(mesh)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = hlo_analysis.collective_bytes(text)
    counts = hlo_analysis.count_collectives(text)

    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_est_bytes": (ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes),
        },
        "cost": {
            "hlo_flops_per_device": ca.get("flops", 0.0),
            "hlo_bytes_per_device": ca.get("bytes accessed", 0.0),
        },
        "collectives_bytes": coll,
        "collectives_count": counts,
        "meta": prog.meta,
    }
    if verbose:
        gb = rec["memory"]["peak_est_bytes"] / 2**30
        print(f"  [OK] {arch_id} x {shape_name} x {rec['mesh']}: "
              f"peak ~{gb:.2f} GiB/dev, "
              f"flops/dev {rec['cost']['hlo_flops_per_device']:.3g}, "
              f"coll {coll.get('total', 0)/2**30:.3f} GiB/dev "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    return rec


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--append", action="store_true",
                    help="merge into an existing results file")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if r.get("ok")}

    n_fail = 0
    for arch_id in archs:
        shapes = [c.name for c in get_shapes(arch_id)]
        if args.shape != "all":
            shapes = [s for s in args.shape.split(",") if s in shapes]
        for shape_name in shapes:
            for mp in meshes:
                key = (arch_id, shape_name, "2x16x16" if mp else "16x16")
                if key in done:
                    continue
                try:
                    results.append(run_cell(arch_id, shape_name, mp))
                except Exception as e:  # noqa: BLE001 — record and continue
                    n_fail += 1
                    print(f"  [FAIL] {key}: {type(e).__name__}: {e}")
                    traceback.print_exc(limit=3)
                    results.append({"arch": arch_id, "shape": shape_name,
                                    "mesh": key[2], "ok": False,
                                    "error": f"{type(e).__name__}: {e}"})
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                json.dump(results, open(args.out, "w"), indent=1)
    ok = sum(1 for r in results if r.get("ok"))
    print(f"dry-run: {ok} ok / {len(results)} cells -> {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
