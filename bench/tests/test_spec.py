"""A configuration, a traffic mix and a metric that exist only as files
are found by name, and a run reports the new metric."""

import json
import shutil

import pytest

from bench import harness, spec
from bench.tests.cells import ROOT, TINY

NEW_METRIC = '''"""Requests the window sent per client (a test-only reader)."""


def read(run):
    return len(run["window"]["requests"]) / 4
'''


@pytest.fixture()
def new_root(tmp_path):
    """A checkout whose cell, configuration, mix and metric are all new
    files; the drivers are the benchmark's own."""
    shutil.copytree(ROOT / "bench" / "drivers", tmp_path / "bench" / "drivers")
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / "bench" / sub).mkdir(parents=True)
    shutil.copy(TINY, tmp_path / "bench" / "configs" / "toy-ed.json")
    mix = json.loads((ROOT / "bench" / "traffic" / "svc32.json").read_text())
    mix.update(clients=4, grace_s=2.0)
    (tmp_path / "bench" / "traffic" / "four.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "client.requests.py").write_text(
        NEW_METRIC)
    for name in ("qps", "setup_s"):
        shutil.copy(ROOT / "bench" / "metrics" / f"{name}.py",
                    tmp_path / "bench" / "metrics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-ed", "file": "bench/configs/toy-ed.json"}],
        "workloads": [{"name": "toy-ed.four", "config": "toy-ed",
                       "traffic": "four", "chips": 1}],
        "end_to_end": [
            {"name": "qps", "unit": "queries/s"},
            {"name": "setup_s", "unit": "s"},
            {"name": "client.requests", "unit": "requests",
             "workloads": ["toy-ed.four"]},
            {"name": "p95_ms", "unit": "ms", "workloads": ["other.cell"]}],
        "per_layer": []}))
    return tmp_path


def test_new_files_are_found_by_name(new_root):
    cell = spec.load_cell(new_root, "toy-ed.four")
    assert cell.config["dataset"]["n"] == 3000
    assert cell.traffic["clients"] == 4
    assert [m["name"] for m in cell.end_to_end] == ["qps", "setup_s",
                                                    "client.requests"]
    reader = spec.load_metric(new_root, "client.requests")
    assert reader.read({"window": {"requests": [0] * 8}}) == 2.0
    result, _ = harness.run(cell, 4, 1.0, False)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"qps", "setup_s", "client.requests"}


def test_missing_files_are_named(new_root):
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell(new_root, "absent.cell")
    (new_root / "bench" / "traffic" / "four.json").unlink()
    with pytest.raises(spec.SpecError, match="four.json"):
        spec.load_cell(new_root, "toy-ed.four")
    with pytest.raises(spec.SpecError, match="nothing.py"):
        spec.load_metric(new_root, "nothing")
