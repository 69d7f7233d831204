#!/usr/bin/env python3
"""The control of a cell's comparison: the reference in the program's place,
one precision below the configuration's.

    python3 bench/control.py --workload deep1m-ed-f32.svc32 --seeds 1,2,3

For each seed it makes the cell's data, answers the first ``--answers``
queries a run's window sends with the reference computed at the
configuration's ``control`` precision (bf16 below float32, int4 codes
below int8), and judges those answers as a run judges the program's.
Each seed prints one JSON line with the compared numbers; a sound
comparison reports ``"correct": false`` for every seed.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

from bench import check, spec  # noqa: E402


def control_checks(cell: spec.Cell, seed: int, answers: int) -> dict:
    import numpy as np

    from bench import data, reference

    cfg = cell.config
    ds = cfg["dataset"]
    rows, queries = data.generate(seed, ds["n"], ds["d"],
                                  cell.traffic["query_pool"], cfg["family"])
    rows, queries = np.asarray(rows), np.asarray(queries)
    k = int(cfg["k"])
    low = reference.Reference(rows, cfg["control"], cfg["family"])
    ids, dists = low.topk(queries[:answers], k)
    del low
    got = [{"query": i, "quality": "exact", "ids": ids[i], "dists": dists[i]}
           for i in range(answers)]
    ref = reference.Reference(rows, cfg["storage"], cfg["family"])
    return check.compare(got, queries, ref, k, cfg["limits"], seed, answers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--answers", type=int, default=160,
                    help="answers judged per seed (a run judges its window's)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not cell.config.get("cpu_ok"):
        print(f"control: no TPU (found {dev.platform})", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        checks = control_checks(cell, seed, args.answers)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": cell.config["control"],
                          "correct": check.passed(checks),
                          "seconds": time.perf_counter() - t0,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
