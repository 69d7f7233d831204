"""A test-only tiny cell: the svc32 traffic on a 3,000 x 32 deployment
that the CPU builds and serves in seconds."""

import copy
import json
from pathlib import Path

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).resolve().parent / "data" / "tiny.json"


def tiny_cell(**config) -> spec.Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = spec.load_config(TINY)
    cfg.update(config)
    traffic = copy.deepcopy(spec.load_traffic(ROOT, "svc32"))
    traffic["grace_s"] = 2.0
    return spec.Cell(root=ROOT, name="tiny.svc32", chips=1,
                     config=cfg, traffic=traffic,
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])
