"""chip_smoke.py's own logic, on the CPU: it refuses to run without a TPU
or with a non-Pallas kernel override, and its answer and kernel checks
accept and reject what they should."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_result_line(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "ok" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_cpu_without_a_result(smoke, capsys, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    assert smoke.main([]) != 0
    assert _no_result_line(capsys.readouterr().out)


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_refuses_non_pallas_kernels(smoke, capsys, monkeypatch, impl):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    assert smoke.main([]) != 0
    assert _no_result_line(capsys.readouterr().out)


def _ref(k=3):
    ids = np.array([[5, 1, 9, 4], [2, 7, 3, 8]])
    dists = np.array([[0.0, 1.0, 2.0, 2.0 + 1e-9], [0.5, 1.5, 2.5, 9.0]])
    return {"ids": ids, "dists": dists, "scale": np.array([10.0, 10.0])}


def test_check_answers_accepts_reordering_and_kth_tie(smoke):
    ref = _ref()
    ids = np.array([[1, 5, 4], [7, 2, 3]])     # row 0: k-th tie swapped in
    dists = np.array([[1.0, 0.0, 2.0], [1.5, 0.5, 2.5]])
    smoke.check_answers("t", ids, dists, ref)


@pytest.mark.parametrize("ids,dists", [
    ([[5, 1, 8], [2, 7, 3]], [[0.0, 1.0, 2.0], [0.5, 1.5, 2.5]]),  # wrong id
    ([[5, 1, 9], [2, 7, 3]], [[0.0, 1.0, 2.0], [0.5, 1.5, 2.6]]),  # far dist
    ([[5, 1, 9], [2, 7, 8]], [[0.0, 1.0, 2.0], [0.5, 1.5, 9.0]]),  # not a tie
])
def test_check_answers_rejects(smoke, ids, dists):
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_answers("t", np.array(ids), np.array(dists), _ref())


def test_kernels_in_reads_pallas_custom_calls(smoke):
    text = "\n".join([
        '  %bregman_ub_matrix.7 = f32[64,4096]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", backend_config={}',
        '  %fused = (f32[8]{0}, s32[8]{0}) custom-call(%b), '
        'custom_call_target="tpu_custom_call"',
        '  ROOT %bregman_refine_batch = f32[1]{0} custom-call(%c), '
        'custom_call_target="tpu_custom_call"',
        '  %sort.3 = f32[8]{0} custom-call(%d), custom_call_target="TopK"',
    ])
    assert smoke.kernels_in(text) == {"bregman_ub_matrix", "fused",
                                      "bregman_refine_batch"}
