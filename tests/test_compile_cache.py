"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
else to the fixed in-checkout path, and a second process finds it.

Each case runs in a fresh process: the cache directory is process-global
JAX config, and the test session must keep its own.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import json, sys
from repro.launch.compile_cache import CACHE_EVENTS, enable_compile_cache
import jax, jax.numpy as jnp
path = enable_compile_cache()
if sys.argv[1] == "compile":
    x = jnp.ones((64, 64))
    jax.jit(lambda a: jnp.sin(a) @ a.T + 1.0)(x).block_until_ready()
print(json.dumps({"path": path, "events": dict(CACHE_EVENTS)}))
"""


def _probe(mode: str, cache_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, mode], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=300)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_second_process_hits(tmp_path):
    cache = tmp_path / "jax-cache"
    first = _probe("compile", cache)
    assert first["path"] == str(cache)
    assert first["events"].get("hits", 0) == 0
    assert first["events"].get("misses", 0) >= 1
    assert any(cache.iterdir())
    second = _probe("compile", cache)
    assert second["events"].get("hits", 0) >= 1


def test_unset_env_uses_fixed_in_checkout_dir():
    got = _probe("path-only")
    assert got["path"] == str(ROOT / ".jax_cache")
    assert got["events"] == {}
