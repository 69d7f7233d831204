"""Sharded BrePartition search: the fused pipeline as one SPMD program.

The partition-filter-refinement framework decomposes over disjoint point
blocks: subspace UB totals are per-point (one row of the filter matmul),
the Theorem-3 corner test is per-point, and exact refinement distances are
per-point.  So a ``BallForest`` split point-major across a ``data`` mesh
axis runs the entire fused pipeline of ``core/search.py`` *locally* per
shard, and only two tiny collectives touch the wire per query block:

1. **Bound exchange** — each shard's local k smallest UB totals (plus the
   corresponding P-tuples) are all-gathered (``p * k`` scalars + tuples
   per query) and merged, so every shard prunes against the GLOBAL Alg.-4
   bound ``qb``, not a loose local one.  Using a subset's k-th UB would
   still be *correct* (it is an upper bound on the global k-th), but the
   global bound keeps per-shard candidate unions small.
2. **Top-k merge** — each shard refines its own candidates exactly and the
   per-shard (q, k) results are merged with one k-way all-gather + top-k.

Exactness survives sharding for the same reason (decomposability): each
shard's local top-k is exact over its points whenever its union fits its
budget, and the merge of exact local top-ks is the exact global top-k.
``exact`` is the AND over shards; the host wrapper retries overflowing
blocks with a grown budget exactly like ``knn_batch``, topping out at the
per-shard point count (where the union always fits), so the flag is
truthful without any brute-force escape hatch.

The per-shard phases are the REUSED batched-pipeline helpers
(``_batch_filter_topk`` / ``_stream_prune_compact`` / ``_refine_batch``) —
one implementation of the math, two launch shapes.  The prune+compact is
the same streaming scan as the single-host path: per-shard peak memory is
O(block_rows * q + q * budget), never O(local_n * q), and the block-level
corner-envelope gate skips dead (block, query) tiles per shard.  The
envelope tables (``env_alpha_min``/``env_sqrt_gamma_max``) are GLOBAL and
replicated (they ride ``REPLICATED_FIELDS``); each shard addresses its
own slice with ``axis_index * local_n``, so envelope rows straddling a
shard boundary are simply read by both neighbors — an envelope over a
superset of rows is still a dominator, so the skip stays loss-free at any
alignment.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bounds
from repro.core.bregman import get_family
from repro.core.calibrate import resolve_p_guarantee
from repro.core.index import (BallForest, REPLICATED_FIELDS, pad_points,
                              point_fields, refresh_envelopes)
from repro.core.quantize import ub_slack
from repro.core.search import (SearchResult, _batch_filter_topk,
                               _cdf_shrink, _refine_batch,
                               _stream_prune_compact, _tuple_rows,
                               dense_refine, resolve_block_rows,
                               resolve_budget, run_ladder,
                               validate_p_guarantee)
from repro.core.transform import Partition, q_transform_views
from . import sharding as shd

Array = jax.Array

_QS_FIELDS = ("qconst", "sqrt_delta", "grad", "c_y")


class LaunchTimeout(TimeoutError):
    """A distributed launch blocked past its ``launch_timeout_s``.

    Raised AFTER the launch completes (an in-flight XLA program cannot be
    preempted), so the timeout is cooperative: it bounds how long a slow
    shard can silently inflate tail latency before the caller learns about
    it.  serve/retrieval.py treats it as a circuit-breaker failure and
    degrades the tenant rather than retrying blindly.  The completed
    result rides on the exception (:attr:`result`, :attr:`elapsed_s`) so
    callers that still meet their deadline may choose to use it.
    """

    def __init__(self, msg: str, result=None, elapsed_s: float = 0.0):
        super().__init__(msg)
        self.result = result
        self.elapsed_s = elapsed_s


class QueryView(NamedTuple):
    """A query block plus its pre-gathered per-subspace view.

    The O(q*d) gather is query preprocessing — done once on the host by
    :func:`query_subview` — while ``y`` (original dim order) feeds the
    refine constants.  Both are replicated to every shard.
    """

    y: Array        # (q, d) original dim order
    sub: Array      # (q, M, w) subspace view (partition.gather(y))


def query_subview(partition: Partition, ys: Array) -> QueryView:
    """Pre-gather a (q, d) query block's subspace view for the shards."""
    ys = jnp.asarray(ys, jnp.float32)
    if ys.ndim != 2:
        raise ValueError(f"expected (q, d) queries, got {ys.shape}")
    return QueryView(y=ys, sub=partition.gather(ys))


@dataclasses.dataclass(frozen=True)
class ShardedForest:
    """A BallForest laid out point-major across one mesh axis.

    ``forest`` is the padded index with point-major arrays device_put over
    ``mesh[axis]`` and the per-cluster/sample arrays replicated; ``global_n``
    is the real (pre-padding) point count and ``live_n`` the count of
    non-tombstoned points (== ``global_n`` unless the shard came from a
    mutable SegmentedForest with deletions).
    """

    forest: BallForest
    mesh: Mesh
    axis: str
    global_n: int
    live_n: int | None = None

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def local_n(self) -> int:
        return self.forest.n // self.num_shards

    @property
    def global_live_n(self) -> int:
        return self.global_n if self.live_n is None else self.live_n


def shard_index(forest, mesh: Mesh, axis: str = "data") -> ShardedForest:
    """Split an index point-major across ``mesh[axis]``.

    ``forest`` is a BallForest or a mutable SegmentedForest
    (core/segments.py) — the latter is snapshotted to its one-BallForest
    view, so each shard's slice carries its share of the append segments
    and tombstones and the per-shard fused pipeline needs no new code.
    Points are padded to a multiple of the axis size with search-inert
    rows (core/index.pad_points), then every point-major array is
    device_put with spec ``P(axis)`` and everything else replicated.

    A mutating index does NOT auto-reshard: re-call after insert/delete
    (the snapshot is immutable, exactly like a filesystem LSM level).
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    live_n = getattr(forest, "live_n", None)
    view = getattr(forest, "view", None)
    if callable(view):
        forest = view()
    if forest.env_alpha_min is None:
        # Hand-assembled forest without envelope tables: derive them here
        # so every shard program can rely on the replicated global tables.
        forest = refresh_envelopes(forest)
    padded = pad_points(forest, int(mesh.shape[axis]))

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    placed = dataclasses.replace(
        padded,
        **{f: put(getattr(padded, f), P(axis)) for f in point_fields(padded)},
        **{f: put(getattr(padded, f), P()) for f in REPLICATED_FIELDS
           if getattr(padded, f) is not None})
    return ShardedForest(forest=placed, mesh=mesh, axis=axis,
                         global_n=forest.n, live_n=live_n)


@functools.lru_cache(maxsize=128)
def _dist_knn_program(mesh: Mesh, axis: str, family_name: str,
                      partition: Partition, num_clusters: int, storage: str,
                      k: int, budget: int, block_rows: int, approx: bool):
    """One jitted SPMD program per (mesh x index-static x k/budget) cell."""
    fam = get_family(family_name)

    def per_shard(arrs: dict, qs: dict, p_guarantee):
        # arrs carries exactly the dynamic BallForest fields; the statics
        # come from the program cell, so this IS the local shard's index.
        local = BallForest(family_name, partition, num_clusters,
                           storage=storage, **arrs)
        # The phases run under the single-host pipeline's scopes
        # (bp.filter, bp.prune, bp.refine); the k-way merge under bp.merge.
        # ---- local filter + GLOBAL Alg.-4 bound via the k-way exchange ----
        with jax.named_scope("bp.filter"):
            vals, idx = _batch_filter_topk(local, qs, k, block_rows)
            tup = _tuple_rows(local, idx)               # decoded in int8 tier
            a_k, g_k = tup["alpha"], tup["sqrt_gamma"]  # (q, k, M)
            vals_g = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
            a_g = jax.lax.all_gather(a_k, axis, axis=1, tiled=True)
            g_g = jax.lax.all_gather(g_k, axis, axis=1, tiled=True)
            if storage == "int8":
                # Ship each local top-k row's stat scales with its tuple:
                # the global bound must carry the rounding slack of
                # whichever shard's rows set the global k-th UB
                # (docs/quantization.md).
                sa_g = jax.lax.all_gather(
                    jnp.take(local.alpha_scale, idx), axis, axis=1,
                    tiled=True)
                sg_g = jax.lax.all_gather(
                    jnp.take(local.sg_scale, idx), axis, axis=1, tiled=True)
            neg, sel = jax.lax.top_k(-vals_g, k)        # global k smallest
            kth = sel[:, -1:, None]                     # (q, 1, 1)
            m = a_g.shape[-1]

            def take_kth(t):
                return jnp.take_along_axis(
                    t, jnp.broadcast_to(kth, kth.shape[:1] + (1, m)),
                    axis=1)[:, 0]
            kth_tuple = {"alpha": take_kth(a_g), "sqrt_gamma": take_kth(g_g)}
            qb = bounds.ub_components(kth_tuple, qs)    # (q, M)
            if storage == "int8":
                a_s = jnp.max(jnp.take_along_axis(sa_g, sel, axis=1),
                              axis=-1)
                g_s = jnp.max(jnp.take_along_axis(sg_g, sel, axis=1),
                              axis=-1)
                qb = qb + ub_slack(a_s, g_s, qs["sqrt_delta"])
            if approx:                                  # §8 shrink, batched
                sqrt_term = kth_tuple["sqrt_gamma"] * qs["sqrt_delta"]
                kappa_i = qb - sqrt_term
                c = _cdf_shrink(local.beta_samples, jnp.sum(sqrt_term, -1),
                                jnp.sum(kappa_i, -1), p_guarantee)
                qb = kappa_i + c[:, None] * sqrt_term

        # ---- local streaming prune + compact + refine (reused phases) ----
        # The replicated envelope tables are GLOBAL; this shard's rows
        # start at axis_index * local_n of the padded global layout.
        with jax.named_scope("bp.prune"):
            offset = jax.lax.axis_index(axis).astype(jnp.int32) * local.n
            sel_c, valid, ncand, _, _, _ = _stream_prune_compact(
                local, qs, qb, budget, block_rows, row_offset=offset,
                row_mask=dense_refine(qb.shape[0], budget, local.n))
        with jax.named_scope("bp.refine"):
            ids, dists = _refine_batch(local, qs, sel_c, valid, k)

        # ---- k-way merge + exactness/union-size reductions ----
        with jax.named_scope("bp.merge"):
            ids_g = jax.lax.all_gather(ids, axis, axis=1, tiled=True)
            d_g = jax.lax.all_gather(dists, axis, axis=1, tiled=True)
            negd, pos = jax.lax.top_k(-d_g, k)
            overflowed = jax.lax.psum((ncand > budget).astype(jnp.int32),
                                      axis)
            return (jnp.take_along_axis(ids_g, pos, axis=1), -negd,
                    overflowed == 0, jax.lax.psum(ncand, axis),
                    jax.lax.pmax(ncand, axis))

    arr_specs = {**{f: P(axis) for f in point_fields(storage)},
                 **{f: P() for f in REPLICATED_FIELDS}}
    qs_specs = {f: P() for f in _QS_FIELDS}
    in_specs = (arr_specs, qs_specs, P()) if approx else (arr_specs, qs_specs)
    body = shd.shard_map(
        per_shard if approx else (lambda arrs, qs: per_shard(arrs, qs, None)),
        mesh=mesh, in_specs=in_specs, out_specs=P(), check=False)

    def program(arrs, y, sub, *p_guarantee):
        q = q_transform_views(sub, partition.subspace_mask(), fam)
        q.update(bounds.query_refine_constants(y, fam))
        qs = {f: q[f] for f in _QS_FIELDS}
        return body(arrs, qs, *p_guarantee)

    return jax.jit(program)


def distributed_knn(sharded: ShardedForest, queries, *, family: str, k: int,
                    budget: int, mesh: Mesh | None = None,
                    approx_p: float | None = None,
                    target_recall: float | None = None,
                    block_rows: int | None = None,
                    launch_timeout_s: float | None = None,
                    launch_hook=None, stop_retry=None,
                    clock=time.monotonic) -> SearchResult:
    """Batched kNN over a sharded index — the distributed ``knn_batch``.

    ``queries`` is a (q, d) block or a prebuilt :class:`QueryView`;
    ``budget`` is the PER-SHARD refine budget (clamped to the shard size);
    ``block_rows`` tunes the per-shard streaming scans exactly like the
    single-host pipeline (``core.search.resolve_block_rows``).
    Returns the usual ``(ids, dists, exact, num_candidates)`` with
    ``num_candidates`` the global Theorem-3 union size per query.  On
    overflow the whole block retries through ``core.search.run_ladder``
    with ``n = local_n``: the next budget is fitted to the largest
    per-shard union, and the ladder ends exact within two launches (at
    worst at ``budget == local_n``, where the union always fits), so
    exact mode stays exact and ``exact`` is always truthful.

    **Robustness wiring** (serve/retrieval.py): every retry is its own
    blocking LAUNCH.  ``launch_hook(elapsed_s)`` observes each launch's
    wall time (feeding the service's cost model); ``launch_timeout_s``
    raises :class:`LaunchTimeout` — carrying the completed result — when
    a launch blocks longer than that (a cooperative, post-hoc timeout: a
    running XLA program cannot be preempted, so this bounds DETECTION
    latency, not the launch itself).  ``stop_retry`` (no-arg -> bool) is
    consulted before each ADDITIONAL launch, exactly like
    ``core.search.knn_batch``: True returns the budget-capped partial
    result (overflowed queries keep ``exact=False``) instead of retrying
    past a deadline.  ``clock`` is injectable for deterministic tests.

    ``target_recall`` (mutually exclusive with ``approx_p``) runs the
    approximate mode at a CALIBRATED shrink: the fitted recall curve
    (carried on the sharded forest — it rides shard_index's
    ``dataclasses.replace``) is inverted ON THE HOST before the launch,
    so the SPMD program sees only the resolved ``p_guarantee`` scalar and
    stays bit-identical to the single-host calibrated path.
    """
    mesh = mesh or sharded.mesh
    forest = sharded.forest
    if target_recall is not None:
        if approx_p is not None:
            raise ValueError("pass at most one of approx_p / target_recall")
        approx_p, _ = resolve_p_guarantee(forest, target_recall)
    validate_p_guarantee(approx_p)
    if family != forest.family_name:
        raise ValueError(
            f"family {family!r} does not match index {forest.family_name!r}")
    if k > sharded.global_live_n:
        raise ValueError(
            f"k={k} exceeds live index size n={sharded.global_live_n}")
    qv = (queries if isinstance(queries, QueryView)
          else query_subview(forest.partition, queries))
    local_n = sharded.local_n
    block_rows = resolve_block_rows(block_rows, sharded.global_live_n,
                                    q=qv.y.shape[0],
                                    storage=forest.storage)
    # Per-shard budget: the global knob resolved against the LOCAL row
    # count (each shard refines its own candidate slots).
    b = resolve_budget(budget, local_n, k)
    arrs = {f: getattr(forest, f)
            for f in point_fields(forest) + REPLICATED_FIELDS}
    extra = () if approx_p is None else (jnp.float32(approx_p),)

    def launch(b, attempt):
        prog = _dist_knn_program(mesh, sharded.axis, forest.family_name,
                                 forest.partition, forest.num_clusters,
                                 forest.storage, k, b,
                                 block_rows, approx_p is not None)
        t0 = clock()
        out = jax.block_until_ready(prog(arrs, qv.y, qv.sub, *extra))
        elapsed = clock() - t0
        if launch_hook is not None:
            launch_hook(elapsed)
        ids, dists, exact, ncand, need = out
        res = SearchResult(ids=ids, dists=dists, exact=exact,
                           num_candidates=ncand)
        if launch_timeout_s is not None and elapsed > launch_timeout_s:
            raise LaunchTimeout(
                f"distributed_knn launch (budget={b}, attempt={attempt}) "
                f"blocked {elapsed:.3f}s > launch_timeout_s="
                f"{launch_timeout_s:.3f}s", result=res, elapsed_s=elapsed)
        return res, int(jnp.max(need))

    res, *_ = run_ladder(launch, local_n, k, b, stop_retry=stop_retry)
    return res
