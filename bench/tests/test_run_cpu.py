"""A whole run of the tiny cell on the CPU: the svc32 loop, the check and
a result line of the contract's schema."""

import json
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.cells import ROOT, tiny_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_result_line(trace):
    cell = tiny_cell()
    result, lines = harness.run(cell, 2**33 + 5, 1.5, trace)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 32
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    if trace:
        names = {"service.rows_per_launch", "search.launches_per_batch",
                 "prune.budget_share"}
        assert names <= set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert line["metrics"]["service.rows_per_launch"]["value"] > 1
    else:
        assert {"qps", "p50_ms", "p95_ms", "setup_s"} <= set(line["metrics"])
        assert (line["metrics"]["p50_ms"]["value"]
                <= line["metrics"]["p95_ms"]["value"])
    checks = line["checks"]
    assert set(checks) == {"missing", "not_exact", "bad_ids", "rank_gap",
                           "dist_err"}
    assert lines == [f"check {n} {c['value']} limit {c['limit']}"
                     for n, c in checks.items()]


def _run(args, cwd):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=120,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


ARGS = ["--workload", "deep1m-ed-f32.svc32", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_exits_nonzero_without_result():
    out = _run(ARGS, ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr or "TPU chip" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(ARGS, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
