"""The search programs name their phases in the HLO they compile to.

Every instruction of ``knn_search_batch``'s program carries its phase
scope in its ``op_name`` metadata (``bp.filter``, ``bp.prune``,
``bp.refine`` with a nested ``gather``), and the sharded program adds
``bp.merge``: a profiler trace of either attributes device time to
phases through that metadata.
"""

import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import search
from repro.core.index import build_index

N, D, M, K = 256, 16, 4, 5
PHASES = ("bp.filter", "bp.prune", "bp.refine")
GATHER = re.compile(r"bp\.refine/(.+/)?gather")
ROOT = Path(__file__).resolve().parents[1]


def op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.fixture(scope="module", params=["f32", "int8"])
def index(request):
    rng = np.random.default_rng(0)
    data = rng.random((N, D)).astype(np.float32) + 0.1
    return build_index(data, "exponential", m=M, num_clusters=8, seed=0,
                       quantize=request.param == "int8")


@pytest.mark.parametrize("program", ["exact", "approx"])
@pytest.mark.parametrize("chunked", [False, True])
def test_search_program_names_its_phases(index, program, chunked,
                                         monkeypatch):
    """Both refine layouts keep the gather in its scope: one gather of all
    queries, and the query chunks of a gather over the byte cap.  Both
    budgets stay under ``dense_refine``'s threshold, so the refine gathers."""
    ys = np.asarray(index.rows_view())[:4] + 0.05
    budget = N // 8 if chunked else 16
    assert not search.dense_refine(4, budget, index.n)
    if chunked:                         # two queries per refine chunk
        monkeypatch.setattr(search, "REFINE_GATHER_BYTES",
                            2 * budget * D * index.data.dtype.itemsize)
    br = search.resolve_block_rows(None, index.n, q=4, storage=index.storage)
    if program == "exact":
        lowered = search._knn_search_batch_jit.lower(index, ys, K, budget, br)
    else:
        lowered = search._knn_search_batch_approx_jit.lower(
            index, ys, K, budget, np.float32(0.9), br)
    names = op_names(lowered.compile().as_text())
    for phase in PHASES:
        assert any(f"/{phase}/" in n for n in names), phase
    gathers = [n for n in names if GATHER.search(n)]
    assert gathers
    assert any("/while/" in n for n in gathers) == chunked
    assert not any("bp.merge" in n for n in names)


# A while nested anywhere inside the prune scan's body: the binary search
# that once routed ranks to rows, run once per block.
NESTED_WHILE = re.compile(r"bp\.prune/(.+/)?while/body/(.+/)?while(/|$)")


def test_prune_scan_routes_without_a_search_loop(index):
    """The exact batch program's ``bp.prune`` scan body holds no loop and
    no ``searchsorted``: a per-block rank search cannot come back."""
    ys = np.asarray(index.rows_view())[:4] + 0.05
    br = search.resolve_block_rows(None, index.n, q=4, storage=index.storage)
    text = search._knn_search_batch_jit.lower(
        index, ys, K, 16, br).compile().as_text()
    prune = [n for n in op_names(text) if "/bp.prune/" in n]
    assert any("bp.prune/while/body/" in n for n in prune)
    assert not [n for n in prune if "searchsorted" in n]
    assert not [n for n in prune if NESTED_WHILE.search(n)]


SHARDED = textwrap.dedent("""
    import json, re
    import numpy as np
    from repro.core.index import build_index
    from repro.dist import knn as dknn
    from repro.dist.sharding import make_mesh

    rng = np.random.default_rng(0)
    data = rng.random((256, 16)).astype(np.float32) + 0.1
    forest = build_index(data, "exponential", m=4, num_clusters=8, seed=0)
    mesh = make_mesh((4,), ("data",))
    sharded = dknn.shard_index(forest, mesh)
    f = sharded.forest
    qv = dknn.query_subview(f.partition, data[:4] + 0.05)
    prog = dknn._dist_knn_program(mesh, "data", f.family_name, f.partition,
                                  f.num_clusters, f.storage, 5, 32, 64,
                                  False)
    arrs = {k: getattr(f, k) for k in dknn.point_fields(f)
            + dknn.REPLICATED_FIELDS}
    text = prog.lower(arrs, qv.y, qv.sub).compile().as_text()
    print(json.dumps(sorted(set(re.findall(r'op_name="([^"]*)"', text)))))
""")


def test_sharded_program_names_its_merge():
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    import json

    names = json.loads(out.stdout.strip().splitlines()[-1])
    for phase in PHASES + ("bp.merge",):
        assert any(f"/{phase}/" in n for n in names), phase
    assert any(GATHER.search(n) for n in names)
    assert jax.devices()[0].platform == "cpu"
