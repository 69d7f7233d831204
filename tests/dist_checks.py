"""Distributed-layer assertions, run under 8 forced host devices.

Invoked by tests/test_distributed.py in a subprocess so the main pytest
session keeps its single-device view (per the dry-run isolation rule).
Exits non-zero on any failure.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.bregman import get_family  # noqa: E402
from repro.core.index import build_index  # noqa: E402
from repro.core import search  # noqa: E402
from repro.dist import knn as dknn  # noqa: E402
from repro.dist.sharding import make_mesh  # noqa: E402
from repro.dist.collective_matmul import (  # noqa: E402
    ag_matmul, ag_matmul_reference, matmul_rs)
from repro.dist.compression import (  # noqa: E402
    compressed_psum_mean, init_ef_state, compressed_grad_allreduce)
from repro.dist.pipeline import pipeline_apply  # noqa: E402


def check_distributed_knn():
    for mesh_shape, axes in [
        ((2, 4), ("data", "model")),
        ((2, 2, 2), ("pod", "data", "model")),
    ]:
        mesh = make_mesh(mesh_shape, axes)
        family = "itakura_saito"
        fam = get_family(family)
        n, d, m, k = 512, 16, 4, 6
        data = np.asarray(fam.sample(jax.random.PRNGKey(0), (n, d)))
        queries = np.asarray(fam.sample(jax.random.PRNGKey(1), (4, d)))
        forest = build_index(data, family, m=m, num_clusters=16, seed=0)
        sharded = dknn.shard_index(forest, mesh)
        y_sub = dknn.query_subview(forest.partition, jnp.asarray(queries))
        ids, dists, exact, ncand = dknn.distributed_knn(
            sharded, y_sub, family=family, k=k, budget=n // 2, mesh=mesh)
        assert bool(jnp.all(exact)), "distributed knn overflowed budget"
        for qi in range(queries.shape[0]):
            ref = search.knn(forest, queries[qi], k)
            np.testing.assert_allclose(
                np.sort(np.asarray(dists[qi])),
                np.sort(np.asarray(ref.dists)), rtol=2e-3, atol=2e-3)
            got_ids = set(np.asarray(ids[qi]).tolist())
            want_ids = set(np.asarray(ref.ids).tolist())
            # allow distance ties to swap ids; distances already matched
            assert len(got_ids & want_ids) >= k - 1, (got_ids, want_ids)
        print(f"  knn ok on mesh {dict(zip(axes, mesh_shape, strict=True))} "
              f"(candidates={np.asarray(ncand).tolist()})")


def check_sharded_shared_pass():
    """Each shard's refine takes the shared pass where the rule says so for
    its own rows (budget = local_n, and just under, at and over the
    threshold), on shards whose rows start at a nonzero offset: its
    answers equal a forced per-query gather's (bit for bit on the
    reference backend, to rounding in the Pallas interpreter) and, at
    budget = local_n, the single-host search's."""
    mesh = make_mesh((4,), ("data",))
    family = "exponential"
    fam = get_family(family)
    n, d, q, k = 1200, 24, 5, 7
    local_n = n // 4                        # 300: a ragged last row tile
    data = np.asarray(fam.sample(jax.random.PRNGKey(0), (n, d)))
    queries = jnp.asarray(fam.sample(jax.random.PRNGKey(1), (q, d)))
    threshold = -(-local_n // q)            # one pass: q * budget >= local_n
    shared_calls = []
    shared_dists = search._shared_dists

    def counted(*args):                     # traced once per program
        shared_calls.append(1)
        return shared_dists(*args)

    search._shared_dists = counted
    for impl in ("ref", "interpret"):
        os.environ["REPRO_KERNEL_IMPL"] = impl
        for storage in ("f32", "int8"):
            forest = build_index(data, family, m=4, num_clusters=16, seed=0,
                                 quantize=storage == "int8")
            sharded = dknn.shard_index(forest, mesh)
            assert sharded.local_n == local_n
            yv = dknn.query_subview(forest.partition, queries)
            for budget in (local_n, threshold - 1, threshold, threshold + 1):
                dense = search.dense_refine(q, budget, local_n)
                runs = {}
                for path in ("rule", "gather"):
                    dknn._dist_knn_program.cache_clear()
                    if path == "gather":
                        dknn.dense_refine = lambda *a: False
                    del shared_calls[:]
                    runs[path] = dknn.distributed_knn(
                        sharded, yv, family=family, k=k, budget=budget,
                        mesh=mesh, max_doublings=0)
                    dknn.dense_refine = search.dense_refine
                    assert bool(shared_calls) == (path == "rule" and dense), (
                        impl, storage, budget, path)
                got, want = runs["rule"], runs["gather"]
                for f in ("ids", "exact", "num_candidates"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(got, f)),
                        np.asarray(getattr(want, f)))
                if impl == "ref":
                    np.testing.assert_array_equal(np.asarray(got.dists),
                                                  np.asarray(want.dists))
                else:
                    np.testing.assert_allclose(np.asarray(got.dists),
                                               np.asarray(want.dists),
                                               rtol=1e-5, atol=1e-4)
                if budget == local_n:
                    one = search.knn_search_batch(forest, queries, k, n)
                    assert bool(jnp.all(got.exact))
                    np.testing.assert_array_equal(np.asarray(got.ids),
                                                  np.asarray(one.ids))
                    np.testing.assert_allclose(np.asarray(got.dists),
                                               np.asarray(one.dists),
                                               rtol=1e-5, atol=1e-4)
            print(f"  sharded shared pass ok ({impl}, {storage})")
    search._shared_dists = shared_dists
    os.environ.pop("REPRO_KERNEL_IMPL")


def check_collective_matmul():
    mesh = make_mesh((8,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)

    # the ring loop's output is value-replicated (every chunk visits every
    # device) but that cannot be statically inferred -> check_vma=False
    fused = jax.jit(jax.shard_map(
        lambda xl, w_: ag_matmul(xl, w_, "model"),
        mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
        check_vma=False))
    ref = jax.jit(jax.shard_map(
        lambda xl, w_: ag_matmul_reference(xl, w_, "model"),
        mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(fused(x, w)), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused(x, w)), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)

    # reduce-scatter dual: x k-sharded, w k-sharded -> rows scattered
    xk = jax.random.normal(jax.random.PRNGKey(2), (16, 64), jnp.float32)
    wk = jax.random.normal(jax.random.PRNGKey(3), (64, 8), jnp.float32)
    rs = jax.jit(jax.shard_map(
        lambda a, b: matmul_rs(a, b, "model"),
        mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
        out_specs=P("model", None)))
    np.testing.assert_allclose(np.asarray(rs(xk, wk)), np.asarray(xk @ wk),
                               rtol=1e-4, atol=1e-4)
    print("  collective matmul ok")


def check_compression():
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 128), jnp.float32)

    def body(gl, res):
        return compressed_psum_mean(gl, "data", res)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data")))
    res = jnp.zeros_like(g)
    mean_est, res = fn(g, res)
    true_mean = jnp.mean(g, axis=0, keepdims=True)
    err0 = float(jnp.max(jnp.abs(mean_est - true_mean)))
    assert err0 < 0.05, err0  # int8 quantization error bound

    # error feedback: the *accumulated* applied update converges to the true
    # mean direction — residual stays bounded, applied sum tracks t * mean.
    applied = jnp.zeros_like(true_mean)
    for _t in range(1, 6):
        mean_est, res = fn(g, res)
        applied = applied + mean_est[:1]
    drift = float(jnp.max(jnp.abs(applied / 5 - true_mean)))
    assert drift < err0 + 1e-6, (drift, err0)
    assert float(jnp.max(jnp.abs(res))) < 0.1
    print(f"  compression ok (one-shot err {err0:.4f}, EF drift {drift:.4f})")

    # tree API smoke
    ef = init_ef_state({"a": g[0], "b": g[0] * 2})
    def tree_body(gl, ef_res):
        means, new_ef = compressed_grad_allreduce(
            {"a": gl, "b": gl * 2}, "data",
            type(ef)(residual={"a": ef_res["a"], "b": ef_res["b"]}))
        return means["a"], new_ef.residual["a"]
    fn2 = jax.jit(jax.shard_map(
        tree_body, mesh=mesh,
        in_specs=(P("data"), {"a": P("data"), "b": P("data")}),
        out_specs=(P("data"), P("data"))))
    m, _ = fn2(g, {"a": jnp.zeros_like(g), "b": jnp.zeros_like(g)})
    np.testing.assert_allclose(np.asarray(m[:1]), np.asarray(true_mean),
                               atol=0.05)


def check_pipeline():
    mesh = make_mesh((4,), ("stage",))
    p, n_micro, dim = 4, 6, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (p, dim, dim), jnp.float32) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, 8, dim))
    got = pipeline_apply(stage_fn, mesh, "stage", ws, xs)
    want = xs
    for s in range(p):
        want = jax.vmap(lambda x, s=s: stage_fn(ws[s], x))(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("  pipeline ok")


CHECKS = (check_collective_matmul, check_compression, check_pipeline,
          check_distributed_knn)


if __name__ == "__main__":
    # python tests/dist_checks.py [check_name ...]: the named checks only
    # (any of this file's check_* functions), else every one in CHECKS.
    assert jax.device_count() == 8, jax.device_count()
    print("distributed checks on", jax.device_count(), "devices")
    for name in sys.argv[1:] or [c.__name__ for c in CHECKS]:
        globals()[name]()
    print("ALL DISTRIBUTED CHECKS PASSED")
