"""Shard-count scaling of the distributed fused pipeline (dist/knn.py).

Runs in the benchmark's own process on the devices that exist: meshes of
1, 2, 4, ... shards are carved from ``jax.devices()``, so a one-device
host measures the 1-shard program only.  A child process could not share
the chip with a parent that has touched JAX; the forced-multi-device CPU
rehearsal of the same path lives in tests/dist_checks.py.  Queries per
second per shard count show how the per-shard filter/prune/refine cost
amortizes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bregman import get_family
from repro.core.index import build_index
from repro.dist import knn as dknn
from repro.dist.sharding import make_mesh

from .common import Row


def run(scale: float = 1.0):
    n, d, m, k, q = max(512, int(8192 * scale)), 64, 8, 10, 64
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(0), (n, d), scale=1.0))
    ys = jnp.asarray(np.asarray(
        fam.sample(jax.random.PRNGKey(1), (q, d), scale=1.0)))
    forest = build_index(data, "squared_euclidean", m=m, num_clusters=64,
                         seed=0)
    budget = max(2 * k, n // 16)
    yv = dknn.query_subview(forest.partition, ys)
    records = []
    shards = 1
    while shards <= len(jax.devices()):
        mesh = make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
        sharded = dknn.shard_index(forest, mesh)

        def once():
            return jax.block_until_ready(dknn.distributed_knn(
                sharded, yv, family="squared_euclidean", k=k,
                budget=budget, mesh=mesh).ids)

        once()                                   # compile + warm
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            once()
            times.append(time.perf_counter() - t0)
        records.append((shards, float(np.median(times) * 1e6)))
        shards *= 2
    base_us = records[0][1]
    return [Row("dist_knn", f"shards{s}", us,
                {"n": n, "qps": round(q / (us / 1e6), 1),
                 "vs_1shard": round(base_us / us, 2),
                 "platform": jax.devices()[0].platform})
            for s, us in records]


if __name__ == "__main__":
    for row in run(0.25):
        print(row.csv())
