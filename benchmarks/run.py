"""Benchmark aggregator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.02] [--only fig12]
                                            [--json BENCH_ci.json]

Prints ``bench,name,us_per_call,derived`` CSV rows; ``--json`` also writes
the rows (plus failures and wall time) to a machine-readable file — CI
uploads it as the ``BENCH_*.json`` artifact on every push.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "bench_construction",    # Fig. 7
    "bench_partitions",      # Figs. 8-9
    "bench_pccp",            # Fig. 10
    "bench_io",              # Fig. 11
    "bench_running_time",    # Fig. 12
    "bench_dimensionality",  # Fig. 13
    "bench_datasize",        # Fig. 14
    "bench_approx",          # Fig. 15
    "bench_batch_search",    # fused batch pipeline vs vmapped per-query
    "bench_quantized",       # int8 tier: filter bytes moved + QPS vs fp32
    "bench_incremental",     # segmented insert/delete/compact vs rebuild
    "bench_dist_knn",        # shard-count scaling on the devices present
    "bench_retrieval",       # retrieval-service overhead (chaos: --chaos)
    "bench_kernels",         # kernel micro-benches
    "bench_recall_frontier",  # calibrated approx tier: recall-vs-QPS + ppl
    "bench_tiered",          # out-of-core tier: fetched bytes + wall ratio
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=None,
                    help="dataset scale factor (default: per-module)")
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (the CI bench artifact)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print("bench,name,us_per_call,derived")
    failures, all_rows, t_start = [], [], time.time()
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        t0 = time.time()
        try:
            rows = (mod.run(args.scale) if args.scale is not None
                    else mod.run())
        except Exception as e:  # noqa: BLE001 — keep the sweep going
            print(f"# {mod_name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failures.append(f"{mod_name}: {type(e).__name__}: {e}")
            continue
        for row in rows:
            print(row.csv())
        all_rows.extend(rows)
        print(f"# {mod_name}: {time.time() - t0:.1f}s", file=sys.stderr)

    if args.json:
        payload = {
            "scale": args.scale,
            "only": args.only,
            "wall_seconds": round(time.time() - t_start, 1),
            "failures": failures,
            "rows": [dataclasses.asdict(r) for r in all_rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json} ({len(all_rows)} rows)", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
