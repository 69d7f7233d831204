"""distributed_knn on a 1x1 mesh must be BIT-identical to the fused
single-host pipeline — the decomposability contract of dist/knn.py.

A 1-device mesh runs the full SPMD program (shard_map, bound exchange,
k-way merge) with every collective a no-op, so any numeric divergence
from ``knn_search_batch`` is a sharding bug, not float noise.  Multi-
device behaviour is covered by tests/dist_checks.py (subprocess, forced
8-device backend — the device-count isolation rule).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bregman import family_names, get_family
from repro.core.index import build_index, pad_points, slice_points
from repro.core import search
from repro.dist import knn as dknn
from repro.dist.sharding import make_mesh

FAMILIES = family_names()
N, D, M, K = 256, 16, 4, 6


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


def _setup(family, num_queries=5):
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(0), (N, D)))
    queries = jnp.asarray(
        np.asarray(fam.sample(jax.random.PRNGKey(1), (num_queries, D))))
    forest = build_index(data, family, m=M, num_clusters=16, seed=0)
    return forest, queries


def _assert_bitwise(res, ref):
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    np.testing.assert_array_equal(np.asarray(res.exact),
                                  np.asarray(ref.exact))
    np.testing.assert_array_equal(np.asarray(res.num_candidates),
                                  np.asarray(ref.num_candidates))


@pytest.mark.parametrize("family", FAMILIES)
def test_exact_mode_bit_identical(mesh, family):
    forest, queries = _setup(family)
    sharded = dknn.shard_index(forest, mesh)
    yv = dknn.query_subview(forest.partition, queries)
    for budget in (N, N // 2):
        res = dknn.distributed_knn(sharded, yv, family=family, k=K,
                                   budget=budget, mesh=mesh,
                                   stop_retry=lambda: True)
        ref = search.knn_search_batch(forest, queries, K, budget)
        _assert_bitwise(res, ref)


@pytest.mark.parametrize("family", FAMILIES)
def test_approx_mode_bit_identical(mesh, family):
    forest, queries = _setup(family)
    sharded = dknn.shard_index(forest, mesh)
    yv = dknn.query_subview(forest.partition, queries)
    res = dknn.distributed_knn(sharded, yv, family=family, k=K, budget=N,
                               mesh=mesh, approx_p=0.9,
                               stop_retry=lambda: True)
    ref = search.knn_search_batch_approx(forest, queries, K, N,
                                         jnp.float32(0.9))
    _assert_bitwise(res, ref)


def test_budget_overflow_retry_keeps_exact_truthful(mesh):
    """Start below the union size: the per-shard retry must converge to an
    exact result (never report exact=True while capped), and a capped run
    must report exact=False."""
    family = "itakura_saito"          # unions routinely exceed tiny budgets
    forest, queries = _setup(family)
    sharded = dknn.shard_index(forest, mesh)
    yv = dknn.query_subview(forest.partition, queries)

    capped = dknn.distributed_knn(sharded, yv, family=family, k=K, budget=K,
                                  mesh=mesh, stop_retry=lambda: True)
    assert not bool(jnp.all(capped.exact)), \
        "test needs an overflowing budget; shrink it"
    # truthful under the cap: the overflowing rows are flagged, not faked
    assert int(jnp.max(capped.num_candidates)) > K

    res = dknn.distributed_knn(sharded, yv, family=family, k=K, budget=K,
                               mesh=mesh)
    assert bool(jnp.all(res.exact))
    ids_oracle, dists_oracle = search.brute_force_knn(
        forest.data, queries, K, forest.family)
    np.testing.assert_allclose(
        np.sort(np.asarray(res.dists), axis=1),
        np.sort(np.asarray(dists_oracle), axis=1), rtol=1e-5, atol=1e-5)
    # retrying host wrappers agree with each other too (same budget rule)
    ref = search.knn_batch(forest, queries, K, budget=K)
    _assert_bitwise(res, ref)


def test_query_subview_matches_partition_gather():
    forest, queries = _setup("shannon", num_queries=3)
    yv = dknn.query_subview(forest.partition, queries)
    assert yv.y.shape == queries.shape
    np.testing.assert_array_equal(np.asarray(yv.sub),
                                  np.asarray(forest.partition.gather(queries)))


def test_pad_and_slice_points_roundtrip():
    """pad_points rows are search-inert; slice_points mirrors the shard view."""
    forest, queries = _setup("squared_euclidean")
    padded = pad_points(forest, 3)        # 256 -> 258
    assert padded.n % 3 == 0
    res = search.knn_search_batch(padded, queries, K, N)
    ref = search.knn_search_batch(forest, queries, K, N)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    assert int(jnp.min(padded.point_ids)) == -1
    local = slice_points(padded, 0, padded.n // 3)
    assert local.n == padded.n // 3
    np.testing.assert_array_equal(np.asarray(local.data),
                                  np.asarray(padded.data)[: padded.n // 3])
