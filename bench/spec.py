"""Find a cell's configuration, traffic, driver and metric readers by name.

Nothing here knows a particular cell: ``BENCHMARK.json`` names the cell's
configuration and traffic mix, and each lives in a file of its own under
``bench/``.  A later cell, mix or metric is added as files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path


class SpecError(RuntimeError):
    """The cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    chips: int
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<mix>.json
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not valid JSON: {e}") from e


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    root = Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = load_config(root / configs[w["config"]]["file"])
    traffic = load_traffic(root, w["traffic"])
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic,
                end_to_end=[m for m in bench.get("end_to_end", [])
                            if _applies(m, workload)],
                per_layer=[m for m in bench.get("per_layer", [])
                           if _applies(m, workload)])


def load_config(path: Path) -> dict:
    cfg = _read_json(Path(path))
    for key in ("dataset", "family", "storage", "build", "k", "limits",
                "control"):
        if key not in cfg:
            raise SpecError(f"config {path} lacks {key!r}")
    return cfg


def load_traffic(root: Path, mix: str) -> dict:
    traffic = _read_json(Path(root) / "bench" / "traffic" / f"{mix}.json")
    if "driver" not in traffic:
        raise SpecError(f"traffic {mix!r} names no driver")
    return traffic


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_driver(root: Path, name: str):
    """``bench/drivers/<name>.py``: ``setup``, ``window``, ``replay``,
    ``release``."""
    return _load_module(Path(root) / "bench" / "drivers" / f"{name}.py",
                        f"bench_driver_{name}")


def load_metric(root: Path, name: str):
    """``bench/metrics/<name>.py``, whose ``read(run)`` returns the value
    or None where the run holds nothing to read."""
    module = _load_module(Path(root) / "bench" / "metrics" / f"{name}.py",
                          "bench_metric_" + name.replace(".", "_"))
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"metric reader {name!r} has no read(run)")
    return module
