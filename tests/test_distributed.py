"""Distributed-layer integration tests (8 forced host devices, subprocess).

The multi-device checks live in tests/dist_checks.py and run in a fresh
process because jax locks the device count at first backend init — the
main pytest session must keep its 1-device view (same isolation rule as
the dry-run).
"""

import os
import subprocess
import sys

import pytest


def _run_checks(*names):
    """tests/dist_checks.py in a fresh process (every check, or ``names``)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_KERNEL_IMPL", None)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    script = os.path.join(os.path.dirname(__file__), "dist_checks.py")
    out = subprocess.run([sys.executable, script, *names], env=env,
                         capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    assert "ALL DISTRIBUTED CHECKS PASSED" in out.stdout


@pytest.mark.timeout(600)
def test_distributed_checks_subprocess():
    _run_checks()


@pytest.mark.timeout(600)
def test_sharded_shared_pass_subprocess():
    """Four shards, the refine's shared pass against a forced gather and
    the single-host search (tests/dist_checks.check_sharded_shared_pass)."""
    _run_checks("check_sharded_shared_pass")


@pytest.mark.timeout(600)
def test_distributed_ladder_subprocess():
    """The sharded search ends exact within two launches of the one ladder
    (tests/dist_checks.check_distributed_ladder)."""
    _run_checks("check_distributed_ladder")
