#!/usr/bin/env python3
"""Bring-up smoke of the BrePartition search and retrieval path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on a four-chip host

One chip: Deep at its published 1,000,000 x 256 under the exponential
distance (``PAPER_DATASETS["deep"]``, generated from ``--seed``) is built
twice, as an fp32 and as an int8 index, through ``build_index``.  64 of its
rows are the queries (the paper's protocol draws its queries from the
dataset).  ``knn_batch`` answers them exactly on each index, and a
``RetrievalService`` answers the same rows as a few dozen requests
(``register_tenant`` -> ``warm`` -> ``submit`` -> ``run_until_drained``).
Every answer is checked against a plain reference: the elementwise fp32
D_f of ``family.distance`` against every stored row, then top-k.

Four chips: the fp32 index is sharded point-major over a 4-chip ``data``
mesh (``shard_index``) and ``distributed_knn`` is checked against
single-chip ``knn_search_batch`` and the same reference.  Nothing else runs.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
when ``REPRO_KERNEL_IMPL`` asks for anything but the Pallas kernels, when
a launched program lacks the filter, prune or refine kernel, and when any
answer disagrees with the reference.  Times it prints are one-off bring-up
readings, not benchmark results.  Its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

K = 10
NUM_QUERIES = 64
# Passed to build_index in place of the default for n = 1M (8192).  An
# unfused (n, C) f32 distance matrix would take 32 GB at 8192 and 4 GB at
# 1024; a v5e compile of k-means fuses it into the argmin at either size.
NUM_CLUSTERS = 1024
SERVICE_REQUESTS = 32           # 2 query rows each: two 32-row microbatches
SERVICE_BUCKET = 32
# Distances must agree with the reference to this relative tolerance of
# |distance| + the query's term scale (see term_scale).
DIST_RTOL = 1e-5
# The kernels every retrieval program must launch: filter, prune, refine.
KERNELS = {"f32": ("bregman_ub_matrix", "bregman_filter_prune",
                   "bregman_refine_batch"),
           "int8": ("bregman_ub_matrix_quant", "bregman_filter_prune_quant",
                    "bregman_refine_batch_quant")}


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Data, reference and checks
# ---------------------------------------------------------------------------

def load_deep(seed: int, scale: float = 1.0):
    """Deep's rows from ``seed`` and NUM_QUERIES of them as queries."""
    import numpy as np
    from repro.data.pipeline import PAPER_DATASETS, make_vectors

    spec = PAPER_DATASETS["deep"]
    t0 = time.perf_counter()
    data = make_vectors(spec, scale=scale, seed=seed)
    rng = np.random.default_rng(seed + 1)
    ys = data[rng.choice(data.shape[0], NUM_QUERIES, replace=False)]
    say(f"data: deep {data.shape[0]} x {data.shape[1]} {spec.measure} "
        f"(paper M={spec.paper_m}), generated in "
        f"{time.perf_counter() - t0} s")
    return spec, data, ys


def reference_knn(rows, point_ids, ys, family, k: int):
    """Plain top-(k+1) by the elementwise fp32 D_f over every row.

    ``rows`` are the stored rows in index order and ``point_ids`` their
    ids.  One query at a time (``lax.map``), so an (n,) distance row is
    the largest thing it holds.  The table goes in as an argument: closed
    over, it would be baked into the program as a gigabyte constant.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def scan(rows, point_ids, ys):
        def one(y):
            neg, idx = jax.lax.top_k(-family.distance(rows, y[None, :]),
                                     k + 1)
            return jnp.take(point_ids, idx), -neg
        return jax.lax.map(one, ys)

    ids, dists = scan(rows, point_ids, jnp.asarray(ys))
    return np.asarray(ids), np.asarray(dists)


def term_scale(ys, family):
    """Per-query magnitude of the refine's terms, sum |f(y)| + |y f'(y)|.

    Refine computes f(x) - x.f'(y) + c_y; near the query those terms are
    this large, and their cancellation leaves an absolute error on this
    scale even where the distance itself is near 0.
    """
    import jax.numpy as jnp
    import numpy as np

    y = jnp.asarray(ys)
    return np.asarray(jnp.sum(jnp.abs(family.phi(y))
                              + jnp.abs(y * family.phi_prime(y)), axis=-1))


def check_answers(what: str, ids, dists, ref) -> None:
    """Ids equal the reference's k ids; distances within DIST_RTOL.

    Order among equal-within-tolerance distances may differ, and the k-th
    id may be the reference's (k+1)-th when those two tie within tolerance.
    """
    import numpy as np

    ref_ids, ref_d = ref["ids"], ref["dists"]
    ids, dists = np.asarray(ids), np.asarray(dists)
    k = ids.shape[1]
    scale = np.abs(ref_d[:, :k]) + ref["scale"][:, None]
    tol = DIST_RTOL * scale
    bad, ties = [], 0
    for i in range(ids.shape[0]):
        got, want = set(ids[i].tolist()), set(ref_ids[i, :k].tolist())
        if got != want:
            tie = (got ^ want == {ref_ids[i, k - 1], ref_ids[i, k]}
                   and ref_d[i, k] - ref_d[i, k - 1] <= tol[i, -1])
            if not tie:
                bad.append(f"query {i}: ids {sorted(got)} != {sorted(want)}")
                continue
            ties += 1
        err = np.abs(np.sort(dists[i]) - ref_d[i, :k])
        if not np.all(err <= tol[i]):
            bad.append(f"query {i}: dist error {err.max()} > tol {tol[i]}")
    rel = np.max(np.abs(np.sort(dists, axis=1) - ref_d[:, :k]) / scale)
    require(not bad, f"{what}: {len(bad)} queries disagree with the "
            "reference: " + "; ".join(bad[:4]))
    say(f"{what}: {ids.shape[0]} queries match the reference "
        f"(max scaled distance error {rel}, k-th ties {ties})")


def kernels_in(compiled_text: str) -> set:
    """Names of the Pallas kernels a compiled TPU program launches."""
    pat = re.compile(r"^\s*(?:ROOT\s+)?%([A-Za-z_0-9]+?)(?:\.\d+)?\s*=")
    return {m.group(1) for line in compiled_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [pat.match(line)] if m}


def require_kernels(what: str, compiled, storage: str) -> None:
    found = kernels_in(compiled.as_text())
    missing = set(KERNELS[storage]) - found
    require(not missing, f"{what}: program lacks kernels {sorted(missing)} "
            f"(found {sorted(found)})")
    say(f"{what}: tpu_custom_call kernels {sorted(found)}")


def batch_program(index, ys, k: int, budget: int):
    """The compiled program ``knn_search_batch`` launches for these args."""
    from repro.core import search

    br = search.resolve_block_rows(None, index.n, q=ys.shape[0],
                                   storage=index.storage)
    return search._knn_search_batch_jit.lower(
        index, ys, k, budget, br,
        search.resolve_env_block_rows(None)).compile()


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

class _BuildLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.records = []

    def emit(self, record):
        if hasattr(record, "build_seconds"):
            self.records.append(record.build_seconds)


def build(data, measure: str, quantize: bool, seed: int):
    from repro.core.index import build_index

    log = _BuildLog()
    logger = logging.getLogger("repro.core.index")
    logger.addHandler(log)
    logger.setLevel(logging.INFO)
    try:
        index = build_index(data, measure, num_clusters=NUM_CLUSTERS,
                            quantize=quantize, seed=seed)
    finally:
        logger.removeHandler(log)
    s = log.records[-1]
    say(f"build {index.storage}: n={index.n} d={index.d} M={index.m} "
        f"num_clusters={NUM_CLUSTERS} | host cost model {s['cost_model']} s,"
        f" host PCCP {s['pccp']} s, device k-means {s['kmeans']} s, "
        f"build_index total {s['total']} s")
    return index


def reference_for(index, ys) -> dict:
    t0 = time.perf_counter()
    ids, dists = reference_knn(index.rows_view(), index.point_ids, ys,
                               index.family, K)
    say(f"reference {index.storage}: elementwise D_f over {index.n} rows in "
        f"{time.perf_counter() - t0} s")
    return {"ids": ids, "dists": dists,
            "scale": term_scale(ys, index.family)}


def smoke_knn_batch(index, ys, ref):
    """``knn_batch`` exact on one index: compile, 3 timed batches, check."""
    import jax
    import numpy as np
    from repro.core import search

    t0 = time.perf_counter()
    res, stats = search.knn_batch(index, ys, K, return_stats=True)
    jax.block_until_ready(res)
    first = time.perf_counter() - t0
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        res, stats = search.knn_batch(index, ys, K, return_stats=True)
        jax.block_until_ready(res)
        times.append(time.perf_counter() - t0)
    cand = np.asarray(res.num_candidates)
    say(f"knn_batch {index.storage}: {stats}")
    say(f"knn_batch {index.storage}: first call (compile + run) {first} s;"
        f" query wall time per {ys.shape[0]}-query batch {times} s; mean "
        f"candidates {cand.mean()} of n={index.n}")
    require(bool(np.all(np.asarray(res.exact))),
            f"knn_batch {index.storage}: a row is not exact")
    check_answers(f"knn_batch {index.storage}", res.ids, res.dists, ref)
    budgets = {search.default_budget(index, K), stats.budget_final}
    for b in sorted(budgets):
        require_kernels(f"knn_batch {index.storage} budget={b}",
                        batch_program(index, jax.numpy.asarray(ys), K, b),
                        index.storage)
    return stats


def smoke_service(index, ys, ref):
    """The retrieval service's normal entry points on the same index."""
    import numpy as np
    from repro.serve.retrieval import (QUALITY_EXACT, RetrievalService,
                                       ServiceConfig)

    # Deadlines and the launch timeout sit far above a launch's cost, so
    # the ladder has no reason to degrade: anything but "exact" is a fault.
    svc = RetrievalService(ServiceConfig(
        max_batch=SERVICE_BUCKET, default_deadline_s=600.0,
        launch_timeout_s=None))
    t0 = time.perf_counter()
    tenant = svc.register_tenant("deep", index)
    warm = svc.warm("deep", shapes=[(SERVICE_BUCKET, K)])
    say(f"service: register + warm {warm['programs']} in "
        f"{time.perf_counter() - t0} s")
    per = ys.shape[0] // SERVICE_REQUESTS
    t0 = time.perf_counter()
    tickets = [svc.submit("deep", ys[i * per:(i + 1) * per], K)
               for i in range(SERVICE_REQUESTS)]
    svc.run_until_drained()
    wall = time.perf_counter() - t0
    st = svc.stats()
    say(f"service: {SERVICE_REQUESTS} requests x {per} rows drained in "
        f"{wall} s; counters { {c: st[c] for c in ('launches', 'escalations', 'launch_failures', 'launch_timeouts', 'deadline_sheds', 'breaker_sheds', 'exact', 'approx', 'partial', 'shed')} }")
    require(st["launch_failures"] == 0 and st["shed"] == 0
            and st["launch_timeouts"] == 0,
            f"service: launch failures or sheds: {st}")
    ids, dists, budgets = [], [], set()
    for t in tickets:
        r = t.response
        require(t.done and r.quality == QUALITY_EXACT,
                f"service: request {r.uid} answered {r.quality!r} "
                f"({r.shed_reason}, {r.error})")
        ids.append(r.ids)
        dists.append(r.dists)
        budgets.add(r.meta["budget"])
    check_answers("service", np.concatenate(ids), np.concatenate(dists),
                  ref)
    block = np.asarray(ys[:SERVICE_BUCKET])
    for b in sorted(budgets):
        require_kernels(f"service budget={b}",
                        batch_program(tenant.index, block, K, b),
                        index.storage)


def smoke_one_chip(seed: int, scale: float = 1.0) -> None:
    import jax

    spec, data, ys = load_deep(seed, scale)
    index = build(data, spec.measure, False, seed)
    ref = reference_for(index, ys)
    smoke_knn_batch(index, ys, ref)
    smoke_service(index, ys, ref)
    del index
    index = build(data, spec.measure, True, seed)
    smoke_knn_batch(index, ys, reference_for(index, ys))
    say(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


def smoke_sharded(seed: int, chips: int, scale: float = 1.0) -> None:
    """``distributed_knn`` over a ``chips``-device data mesh vs one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import search
    from repro.dist import knn as dknn
    from repro.dist.sharding import make_mesh

    spec, data, ys = load_deep(seed, scale)
    index = build(data, spec.measure, False, seed)
    ref = reference_for(index, ys)
    mesh = make_mesh((chips,), ("data",), devices=jax.devices()[:chips])
    sharded = dknn.shard_index(index, mesh)
    for f in ("data", "alpha", "alpha_min_pt"):
        shards = getattr(sharded.forest, f).addressable_shards
        devs = {s.device for s in shards}
        require(len(devs) == chips and all(
            s.data.shape[0] == sharded.local_n for s in shards),
            f"shard_index: {f} spans {len(devs)} devices, want {chips}")
    say(f"shard_index: point-major arrays span {chips} devices "
        f"({sorted(d.id for d in devs)}), {sharded.local_n} rows each")
    qv = dknn.query_subview(index.partition, ys)
    t0 = time.perf_counter()
    res = dknn.distributed_knn(sharded, qv, family=index.family_name, k=K,
                               budget=None)
    jax.block_until_ready(res)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = dknn.distributed_knn(sharded, qv, family=index.family_name, k=K,
                               budget=None)
    jax.block_until_ready(res)
    say(f"distributed_knn: first call {first} s, query wall time per "
        f"{ys.shape[0]}-query batch {time.perf_counter() - t0} s, mean "
        f"candidates {float(np.mean(np.asarray(res.num_candidates)))}")
    require(bool(np.all(np.asarray(res.exact))),
            "distributed_knn: a row is not exact")
    check_answers("distributed_knn", res.ids, res.dists, ref)
    b0 = search.resolve_budget(None, sharded.local_n, K)
    prog = dknn._dist_knn_program(
        mesh, "data", index.family_name, index.partition,
        index.num_clusters, index.storage, K, b0,
        search.resolve_block_rows(None, sharded.global_live_n,
                                  q=ys.shape[0], storage=index.storage),
        False)
    arrs = {f: getattr(sharded.forest, f)
            for f in dknn.point_fields(sharded.forest)
            + dknn.REPLICATED_FIELDS}
    require_kernels(f"distributed_knn budget={b0}",
                    prog.lower(arrs, qv.y, qv.sub).compile(), index.storage)
    one = search.knn_search_batch(index, jnp.asarray(ys), K, index.n)
    require(bool(np.all(np.asarray(one.exact))),
            "knn_search_batch: a row is not exact")
    require(np.array_equal(np.sort(np.asarray(one.ids), 1),
                           np.sort(np.asarray(res.ids), 1)),
            "distributed_knn ids differ from single-chip knn_search_batch")
    check_answers("knn_search_batch (single chip)", one.ids, one.dists, ref)
    say("distributed_knn: ids equal single-chip knn_search_batch")
    for d in jax.devices()[:chips]:
        say(f"peak_bytes_in_use device {d.id}: {peak_bytes(d)}")


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded distributed_knn path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no program at {SRC / 'repro'}", file=sys.stderr)
        return 2
    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl and impl != "pallas":
        print(f"chip_smoke: REPRO_KERNEL_IMPL={impl!r}; only the Pallas "
              "kernels may run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import CACHE_EVENTS, enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
              "nothing measured", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    say(f"device_kind: {dev.device_kind} ({len(devices)} device(s)); "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            smoke_one_chip(args.seed)
        else:
            smoke_sharded(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    say(f"compile cache: {dict(CACHE_EVENTS)}; smoke wall time "
        f"{time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
