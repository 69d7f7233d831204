"""Exact and approximate kNN search (paper Alg. 6 + §8) on a BallForest.

TPU execution model: everything after the query transform is one jit'd
program with static shapes.  The dynamic-size candidate set of the paper is
realized as a static ``budget``-sized selection with an exactness flag
(DESIGN.md §6, item 5); :func:`knn_search` is the jit core and
:func:`knn` is the host wrapper that doubles the budget on overflow, so
results are ALWAYS exact for the exact mode.

Pipeline per query (Alg. 6):
  1. Q-transform (O(d)).
  2. UB filter over all points — matmul form (kernels/bregman_ub).
  3. tau = k-th smallest UB; per-subspace bounds qb (Alg. 4).
  4. Ball pruning per subspace (tuple-space LB, DESIGN §3.3); candidate mask
     = union over subspaces (Theorem 3).
  5. Refine selected candidates with exact D_f (kernels/bregman_dist),
     global top-k.

Batched pipeline (:func:`knn_search_batch`): a (q, d) query block runs the
same five phases end-to-end as ONE jitted program instead of a vmap of the
single-query core, with three structural differences that make it the
serving fast path:

  * **Filter** — the q per-query UB passes collapse onto a single
    (n, M) x (M, q) ``bregman_ub_matrix`` call (the MXU matmul form), and
    the per-column k smallest UBs are extracted by a *streaming* tiled
    k-selection: a ``lax.scan`` over ``block_rows``-sized row blocks merges
    each block's (bn, q) UB tile into a running (q, k) best set, so the
    (n, q) f32 UB matrix never materializes.
  * **Prune + compact** — a second scan over the SAME row blocks
    (:func:`_stream_prune_compact`) runs the whole post-filter pipeline
    in one streaming pass.  Each block is first tested at BLOCK
    granularity against the index's precomputed corner envelopes
    (``env_alpha_min``/``env_sqrt_gamma_max``, core/index.py — the
    tightest alpha_min / loosest sqrt_gamma_max over each
    ENV_BLOCK_ROWS-row group): an envelope dominates every row it covers,
    so a block no query admits is SKIPPED outright (``lax.cond``) without
    touching its per-point tile.  Surviving blocks run the fused
    Theorem-3 per-point admit kernel (kernels/ops.bregman_prune_block —
    corner recompute, compare, and mask emit in one VMEM-resident pass),
    sort each query's admitted rows of the block into row order, and
    write them into their static (q, budget) candidate slots at the
    running member count carried across blocks.  The historical (n, q)
    bool mask, (q, n) int32 cumsum, and per-query binary searches are
    gone: peak intermediate memory is O(block_rows * q + q * budget),
    independent of n (guarded by the hlo-analysis regression test in
    tests/test_stream_memory.py).  Slot order is index order, not UB
    order; when the union overflows the budget the overflowing queries
    are flagged ``exact=False`` and the host wrapper retries, exactly
    like the single-query path.  (:func:`knn_search_batch_reference`
    keeps the materialized mask/cumsum implementation as the bit-parity
    oracle for tests and benchmarks.)

Refinement then runs ONE batched kernel call over all queries' candidate
rows (kernels/bregman_dist.bregman_refine_batch) with per-query grad/c_y
tiles.  A launch whose candidate slots would gather at least the table's
rows (:func:`dense_refine`, e.g. every budget-n launch) instead reads each
stored row once for all its queries, in the kernel's shared-rows form.
The §8 approximate mode's CDF shrink is vectorized over the batch.
:func:`knn_batch` is the host wrapper: an iterative, capped
budget-doubling loop shared by the whole batch.

Every public entry point also accepts the mutable
:class:`~repro.core.segments.SegmentedForest`: it is snapshotted to its
one-BallForest view (``_as_forest``), whose tombstoned rows are
search-inert in the filter, prune, and refine phases by construction.

Storage tiers: all paths run unchanged on the int8 BallForest
(``build_index(quantize=True)``).  The filter streams int8 codes through
the quantized UB kernel and inflates the Alg.-4 bounds by the stat
rounding slack (:func:`_qb_slack`), the prune decodes directed-rounded
(conservative) corner codes, and the refine runs the fused
dequantize+refine kernel on the surviving candidate rows — exact results
over the decoded point set at ~4x lower filter traffic
(docs/quantization.md).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .bregman import get_family, validate_rows
from .calibrate import resolve_p_guarantee
from .index import BallForest, ENV_BLOCK_ROWS
from .transform import q_transform
from . import bounds
from . import quantize as qz

Array = jax.Array

NEG_BIG = -1e30
POS_BIG = 1e30

logger = logging.getLogger(__name__)

# Default row-block size for the streaming batched pipeline; one block is
# the unit of VMEM residency (the TPU analogue of the paper's disk page,
# sized so the (block, q) UB tile plus the (block, q) admit tile stay
# on-chip).  Tunable end to end via the ``block_rows`` argument — see
# :func:`resolve_block_rows` for the tradeoff.
DEFAULT_BLOCK_ROWS = 4096


def resolve_block_rows(block_rows: int | None, n: int, *,
                       q: int | None = None,
                       storage: str | None = None) -> int:
    """Validate the ``block_rows`` tuning knob against an index of n rows.

    ``None`` means "pick for me": consult the checked-in autotuner table
    (launch/autotune.py) for this backend/shape, falling back to
    :data:`DEFAULT_BLOCK_ROWS` when no tuned entry applies.  ``q`` and
    ``storage`` sharpen the table lookup and are optional — callers that
    know the query-batch width and the index storage tier should pass
    them.  The value bounds BOTH streaming scans' working sets (filter
    merge and prune+compact), so it trades peak memory/VMEM residency
    against scan overhead: smaller blocks -> lower peak intermediate
    bytes (O(block_rows * q)) and finer-grained envelope skipping, larger
    blocks -> fewer scan steps and better MXU utilization per step.
    Values beyond ``n`` are legal (the layout clamps to one block);
    non-positive or non-integer values are programming errors and raise.

    The empty-index guard fires on BOTH knob paths: an empty index is an
    error regardless of whether the caller tuned the knob.
    """
    if n < 1:
        raise ValueError(f"cannot search an empty index (n={n})")
    if block_rows is None:
        from repro.launch.autotune import lookup_block_rows
        tuned = lookup_block_rows(n, q, storage=storage)
        return tuned if tuned is not None else DEFAULT_BLOCK_ROWS
    if isinstance(block_rows, bool) or not isinstance(block_rows, int):
        raise ValueError(f"block_rows must be an int, got {block_rows!r}")
    if block_rows < 8:
        raise ValueError(
            f"block_rows={block_rows} is below the minimum tile of 8 rows")
    return block_rows


def resolve_env_block_rows(env_block_rows: int | None) -> int:
    """Validate the envelope-gate granularity knob.

    Envelope tables are STORED at :data:`~repro.core.index.ENV_BLOCK_ROWS`
    granularity; the gate can run at any coarser multiple by min/max-
    coarsening the tables on the fly (a coarser envelope is a strictly
    looser bound, so every admitted-row set is a superset and results are
    invariant — only the skip rate changes).  ``None`` means the storage
    granularity; the autotuner sweeps multiples.
    """
    if env_block_rows is None:
        return ENV_BLOCK_ROWS
    if (isinstance(env_block_rows, bool)
            or not isinstance(env_block_rows, int)):
        raise ValueError(
            f"env_block_rows must be an int, got {env_block_rows!r}")
    if env_block_rows < ENV_BLOCK_ROWS or env_block_rows % ENV_BLOCK_ROWS:
        raise ValueError(
            f"env_block_rows={env_block_rows} must be a positive multiple "
            f"of the storage granularity {ENV_BLOCK_ROWS}")
    return env_block_rows


class SearchResult(NamedTuple):
    ids: Array          # (k,) original point ids — (q, k) from the batch path
    dists: Array        # (k,) exact Bregman distances — (q, k) batched
    exact: Array        # () bool — candidate set fit in the budget; (q,) batched
    num_candidates: Array  # () int32 — Theorem-3 union size; (q,) batched


class BatchStats(NamedTuple):
    """Structured retry telemetry from :func:`knn_batch`.

    The budget-escalation path used to announce itself only through a log
    line; services and benchmarks alert on THESE counters instead of
    scraping logs (``escalations`` growing under load means the default
    budget is undersized; ``escalated_to_scan`` should be ~never).
    """

    escalations: int        # budget-growth retries taken (0 = first try fit)
    budget_final: int       # the budget the returned launch ran with
    escalated_to_scan: bool  # cap exhausted -> full linear-scan fallback
    stopped_early: bool      # a stop_retry deadline ended the ladder
    # One record per launch, in order: ``budget``, the largest Theorem-3
    # union ``num_candidates``, whether the refine read the table in shared
    # passes (``dense``, :func:`dense_refine`), and the host seconds spent
    # dispatching (``dispatch_s``) and waiting for the device (``wait_s``)
    # — the fields of the retrieval service's ``meta["launches"]``.
    launches: tuple = ()


def validate_queries(measure, q, *, mode: str = "raise"):
    """Admission gate: reject NaN / out-of-domain query rows up front.

    ``knn_search``/``knn_search_batch`` math silently returns garbage for a
    query outside the generator's open domain (a non-positive entry under
    Itakura-Saito/Burg/Shannon, any NaN/inf anywhere): the UB matmul and
    the refine kernel both produce NaNs that ``top_k`` resolves to
    arbitrary rows with ``exact=True``.  This host-side gate is one
    elementwise pass over the (q, d) block.  ``mode="raise"`` names the
    first offending row; ``mode="mask"`` returns a (q,) bool ``ok`` mask
    for callers that degrade per row instead of failing the whole block
    (serve/retrieval.py sheds exactly the flagged rows).  ``measure`` is a
    family name or :class:`~repro.core.bregman.BregmanFamily`.
    """
    return validate_rows(measure, q, mode=mode, what="query row")


def query_struct(y: Array, partition, family) -> dict:
    """Everything the pipeline needs about a query (or (q, d) block).

    Per-subspace triples (Alg. 3) plus the refine constants — the query
    representation of the single-query and batched paths.  The distributed
    path (dist/knn.py) builds the same dict from its pre-gathered subspace
    view via ``transform.q_transform_views`` + ``query_refine_constants``
    instead of calling this (the gather is hoisted to the host there).
    """
    q = q_transform(y, partition, family)
    q.update(bounds.query_refine_constants(y, family))
    return q


def _query_struct(index: BallForest, y: Array) -> dict:
    return query_struct(y, index.partition, index.family)


def _as_forest(index, k: int | None = None) -> BallForest:
    """Accept a BallForest or the mutable SegmentedForest (core/segments.py).

    A mutable index exposes ``view()`` — the cached one-BallForest snapshot
    over its sealed main + append segments — and ``live_n``; ``k`` is
    validated against the LIVE count when present, because tombstoned rows
    are physically in the snapshot but can never be returned (``index.n``
    alone would over-promise).
    """
    live_n = getattr(index, "live_n", None)
    if k is not None and live_n is not None and k > live_n:
        raise ValueError(f"k={k} exceeds live point count {live_n}")
    view = getattr(index, "view", None)
    return view() if callable(view) else index


def _tuple_rows(index: BallForest, idx: Array) -> dict:
    """Dequantized (alpha, sqrt_gamma) P-tuples at the given row indices.

    ``idx`` may be a scalar, (k,) or (q, k); fields come back with a
    trailing (M,) axis.  In the f32 tier this is a plain gather (bit-
    identical to reading the tables); in the int8 tier the gathered codes
    are decoded with their per-row affine — only the touched rows ever
    reach fp32.
    """
    a = jnp.take(index.alpha, idx, axis=0)
    g = jnp.take(index.sqrt_gamma, idx, axis=0)
    if index.storage == "int8":
        a = qz.dequantize_stats(a, jnp.take(index.alpha_scale, idx),
                                jnp.take(index.alpha_zp, idx))
        g = qz.dequantize_stats(g, jnp.take(index.sg_scale, idx),
                                jnp.take(index.sg_zp, idx))
    return {"alpha": a, "sqrt_gamma": g}


def _qb_slack(index: BallForest, idx: Array, sqrt_delta: Array):
    """Quantization slack for the Alg.-4 searching bounds (0 in f32).

    Admissibility (docs/quantization.md): among the k rows whose DECODED
    upper bounds are smallest, every row j satisfies
    ``UB_true(j) <= UB_hat(j) + eps_j`` with ``eps_j = sum_i (alpha_scale_j
    + sg_scale_j * sqrt_delta_i) / 2``, so the k-th smallest true distance
    is at most the k-th decoded UB plus ``max_j eps_j``.  The slack is
    distributed per subspace (componentwise max over the k rows) so the
    pigeonhole step of Theorem 3 still applies to the inflated ``qb``.

    ``idx`` is the filter's (…, k) top-k row indices; returns (…, M).
    """
    if index.storage != "int8":
        return jnp.zeros_like(sqrt_delta)
    a_s = jnp.max(jnp.take(index.alpha_scale, idx, axis=0), axis=-1)
    g_s = jnp.max(jnp.take(index.sg_scale, idx, axis=0), axis=-1)
    return qz.ub_slack(a_s, g_s, sqrt_delta)


def _corner_tables(index: BallForest) -> tuple[Array, Array]:
    """Full (n, M) fp32 corner tables (decoded in the int8 tier).

    The int8 corners were DIRECTED-rounded at build (alpha_min floored,
    sqrt_gamma_max ceiled), so the decoded values are conservative and the
    Theorem-3 admission below needs no slack term.
    """
    return qz.decoded_corner_tables(index)


def _corner_admit(amin_pt: Array, gmax_pt: Array, qconst: Array,
                  sqrt_delta: Array, qb: Array, sub_axis: int) -> Array:
    """THE Theorem-3 membership test, shared by every search path.

    Membership must be CLUSTER-granular: Theorem 3's pigeonhole argument
    bounds the per-subspace EXACT distance (D_i <= qb_i for some i), and
    the conservative cluster lower bound LB_c <= min_{x in c} D_i never
    prunes a cluster containing such a point.  (A per-point test on the
    Cauchy UPPER bound components is NOT valid — UB_i > qb_i for all i does
    not contradict D <= tau.)  The cluster corners are evaluated through
    the index's per-point view (``alpha_min_pt``/``sqrt_gamma_max_pt``,
    gathered once at build time from the gamma-bucketed corner stats —
    core/index.py), so the test is a pure broadcasted compare.  ``sub_axis``
    names the subspace axis of the broadcasted operands.
    """
    lb = amin_pt + qconst - gmax_pt * sqrt_delta
    return jnp.any(lb <= qb, axis=sub_axis)


def _candidate_mask(index: BallForest, q: dict, qb: Array) -> Array:
    """Theorem-3 union membership for one query. (n,) bool."""
    amin, gmax = _corner_tables(index)
    return _corner_admit(amin, gmax,
                         q["qconst"], q["sqrt_delta"], qb, sub_axis=-1)


def _refine(index: BallForest, q: dict, mask: Array, totals: Array,
            budget: int, k: int):
    """Exact distances for one query's union ``mask`` (n,): the q=1 batch
    slice.  ``budget`` slots hold its members first, by UB ``totals``; at
    ``budget`` = n the shared pass reads the whole table instead."""
    qs1 = {"grad": q["grad"][None], "c_y": q["c_y"][None]}
    if dense_refine(1, budget, index.n):
        ids, dists = _refine_batch(index, qs1, None, mask[None], k)
    else:
        priority = jnp.where(mask, POS_BIG - totals, NEG_BIG - totals)
        _, sel = jax.lax.top_k(priority, budget)
        valid = jnp.take(mask, sel)
        ids, dists = _refine_batch(index, qs1, sel[None], valid[None], k)
    return ids[0], dists[0]


def _single_filter(index: BallForest, q: dict, k: int):
    """Filter phase for one query: (totals (n,), top-k idx (k,), qb (M,)).

    f32 storage runs the original ub_filter; the int8 tier streams the
    codes through the quantized UB kernel and inflates the Alg.-4 bounds
    by the stat rounding slack (`_qb_slack`) so the downstream prune stays
    admissible over the decoded point set.
    """
    from repro.kernels import ops as kernel_ops
    if index.storage == "int8":
        totals = kernel_ops.bregman_ub_matrix_quant(
            index.alpha, index.alpha_scale, index.alpha_zp,
            index.sqrt_gamma, index.sg_scale, index.sg_zp,
            q["qconst"][None], q["sqrt_delta"][None])[:, 0]
        _, idx = jax.lax.top_k(-totals, k)
        qb = (bounds.ub_components(_tuple_rows(index, idx[-1]), q)
              + _qb_slack(index, idx, q["sqrt_delta"]))
    else:
        totals, comp_kth_fn = kernel_ops.bregman_ub_filter(
            index.alpha, index.sqrt_gamma, q["qconst"], q["sqrt_delta"])
        _, idx = jax.lax.top_k(-totals, k)
        qb = comp_kth_fn(idx[-1])
    return totals, idx, qb


@functools.partial(jax.jit, static_argnames=("k", "budget"))
def _knn_search_jit(index: BallForest, y: Array, k: int,
                    budget: int) -> SearchResult:
    """Exact kNN for one query (jit core, static budget)."""
    q = _query_struct(index, y)

    # ---- filter: total UB for every point (MXU matmul form) ----
    totals, _idx, qb = _single_filter(index, q, k)     # (M,) Alg. 4 bounds

    # ---- ball pruning + union (Theorem 3) ----
    mask = _candidate_mask(index, q, qb)
    num_candidates = jnp.sum(mask.astype(jnp.int32))

    # ---- static-budget selection: all union members first, by UB ----
    ids, dists = _refine(index, q, mask, totals, budget, k)
    exact = num_candidates <= budget
    return SearchResult(ids=ids, dists=dists, exact=exact,
                        num_candidates=num_candidates)


def knn_search(index, y: Array, k: int, budget: int,
               validate: bool = True) -> SearchResult:
    """Exact kNN for one query (static budget; accepts a mutable index)."""
    if getattr(index, "is_tiered_store", False):
        res = index.search(jnp.asarray(y, jnp.float32)[None, :], k, budget,
                           validate=validate)
        return SearchResult(ids=res.ids[0], dists=res.dists[0],
                            exact=res.exact[0],
                            num_candidates=res.num_candidates[0])
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    if validate:
        validate_queries(index.family, y)
    return _knn_search_jit(index, y, k, budget)


@functools.partial(jax.jit, static_argnames=("k", "budget"))
def _knn_search_approx_jit(
    index: BallForest, y: Array, k: int, budget: int, p_guarantee: Array
) -> SearchResult:
    """Approximate kNN with probability guarantee p (paper §8, Prop. 1).

    The Cauchy slack mu of the k-th bound is shrunk to c*mu with
    ``c = Psi^-1(p*Psi(mu) + (1-p)*Psi(-kappa)) / mu`` where Psi is the
    empirical CDF of the cross term beta_xy (index.beta_samples); each
    subspace bound's sqrt term is scaled by c.  In the int8 tier the
    quantization slack inflates ``qb`` BEFORE the shrink (matching the
    batched and distributed paths), so the probabilistic guarantee holds
    w.r.t. the decoded point set.
    """
    q = _query_struct(index, y)

    totals, idx, qb = _single_filter(index, q, k)
    kth = idx[-1]

    # Full-space kappa and mu of the k-th bound (paper §8 notation).
    sqrt_term = _tuple_rows(index, kth)["sqrt_gamma"] * q["sqrt_delta"]  # (M,)
    kappa_i = qb - sqrt_term                           # per-subspace kappa
    kappa = jnp.sum(kappa_i)
    mu = jnp.sum(sqrt_term)

    c = _cdf_shrink(index.beta_samples, mu, kappa, p_guarantee)
    qb_approx = kappa_i + c * sqrt_term                # shrunk bounds

    mask = _candidate_mask(index, q, qb_approx)
    num_candidates = jnp.sum(mask.astype(jnp.int32))
    ids, dists = _refine(index, q, mask, totals, budget, k)
    return SearchResult(ids=ids, dists=dists, exact=num_candidates <= budget,
                        num_candidates=num_candidates)


def knn_search_approx(index, y: Array, k: int, budget: int,
                      p_guarantee: Array,
                      validate: bool = True) -> SearchResult:
    """§8 approximate kNN for one query (accepts a mutable index)."""
    if getattr(index, "is_tiered_store", False):
        res = index.search(jnp.asarray(y, jnp.float32)[None, :], k, budget,
                           p_guarantee=p_guarantee, validate=validate)
        return SearchResult(ids=res.ids[0], dists=res.dists[0],
                            exact=res.exact[0],
                            num_candidates=res.num_candidates[0])
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    validate_p_guarantee(p_guarantee)
    if validate:
        validate_queries(index.family, y)
    return _knn_search_approx_jit(index, y, k, budget, p_guarantee)


def _cdf_shrink(samples: Array, mu: Array, kappa: Array, p: Array) -> Array:
    """§8 Prop.-1 shrink factor c from the empirical beta_xy CDF.

    Vectorized: ``mu``/``kappa`` may be scalars (single query) or (q,)
    batches; returns the same shape.
    """
    s = samples.shape[0]

    def cdf(t):
        return jnp.searchsorted(samples, t, side="right").astype(jnp.float32) / s

    def inv_cdf(u):
        pos = jnp.clip(u * (s - 1), 0.0, s - 1.0)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, s - 1)
        w = pos - lo.astype(jnp.float32)
        return samples[lo] * (1 - w) + samples[hi] * w

    target = p * cdf(mu) + (1.0 - p) * cdf(-kappa)
    return jnp.clip(inv_cdf(target) / jnp.maximum(mu, 1e-12), 0.0, 1.0)


# ---------------------------------------------------------------------------
# Batched pipeline (the serving fast path)
# ---------------------------------------------------------------------------

def _block_layout(n: int, block_rows: int) -> tuple[int, int]:
    """(block, num_blocks) covering n rows; block <= block_rows."""
    bn = max(8, min(block_rows, n))
    nb = -(-n // bn)
    return bn, nb


def _pad_blocks(arr: Array, bn: int, nb: int, fill: float = 0.0) -> Array:
    """Pad (n, M) rows up to nb*bn with ``fill`` and reshape to (nb, bn, M)."""
    pad = nb * bn - arr.shape[0]
    return jnp.pad(arr, ((0, pad), (0, 0)),
                   constant_values=fill).reshape(nb, bn, arr.shape[1])


def _pad_cols(arr: Array, bn: int, nb: int, fill: float = 0.0) -> Array:
    """Pad a per-row (n,) column up to nb*bn and reshape to (nb, bn)."""
    pad = nb * bn - arr.shape[0]
    return jnp.pad(arr, (0, pad), constant_values=fill).reshape(nb, bn)


def _filter_blocks(index: BallForest, bn: int, nb: int) -> tuple:
    """The (nb, bn, ...) filter-table blocks (alpha / sqrt_gamma + decode).

    Shared by the filter scan and the fused filter+prune scan so both read
    identically padded blocks (zero-padded; padded rows are masked by the
    global-index guard in the consumers).
    """
    if index.storage == "int8":
        return (_pad_blocks(index.alpha, bn, nb),
                _pad_blocks(index.sqrt_gamma, bn, nb),
                _pad_cols(index.alpha_scale, bn, nb),
                _pad_cols(index.alpha_zp, bn, nb),
                _pad_cols(index.sg_scale, bn, nb),
                _pad_cols(index.sg_zp, bn, nb))
    return (_pad_blocks(index.alpha, bn, nb),
            _pad_blocks(index.sqrt_gamma, bn, nb))


def _batch_filter_topk(index: BallForest, qs: dict, k: int,
                       block_rows: int) -> tuple[Array, Array]:
    """Streaming per-column k-selection over the (n, q) UB matrix.

    One UB-matrix kernel call per row block inside a scan; the carry is
    the running (q, k) smallest totals + their global row indices, so peak
    memory is O(block_rows * q) regardless of n.  Ties resolve to the lower
    row index (carry rows precede the block in the merge concat), matching
    ``lax.top_k`` over the full column.  The int8 tier streams code blocks
    plus their per-row decode scalars through the quantized kernel — the
    full-width (n, M) reads are 1-byte, the 4x traffic win of the tier.
    """
    from repro.kernels import ops as kernel_ops
    n = index.alpha.shape[0]
    q = qs["qconst"].shape[0]
    bn, nb = _block_layout(n, block_rows)
    offs = jnp.arange(nb, dtype=jnp.int32) * bn
    xs = _filter_blocks(index, bn, nb) + (offs,)

    def step(carry, blk):
        best_v, best_i = carry                          # (q, k) each
        if index.storage == "int8":
            a, sg, a_s, a_z, g_s, g_z, off = blk
            vals = kernel_ops.bregman_ub_matrix_quant(
                a, a_s, a_z, sg, g_s, g_z,
                qs["qconst"], qs["sqrt_delta"])         # (bn, q)
        else:
            a, sg, off = blk
            vals = kernel_ops.bregman_ub_matrix(
                a, sg, qs["qconst"], qs["sqrt_delta"])  # (bn, q)
        gidx = off + jnp.arange(bn, dtype=jnp.int32)
        vals = jnp.where((gidx < n)[:, None], vals, POS_BIG)
        cand_v = jnp.concatenate([best_v, vals.T], axis=1)          # (q, k+bn)
        cand_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(gidx[None, :], (q, bn))], axis=1)
        neg, sel = jax.lax.top_k(-cand_v, k)
        return (-neg, jnp.take_along_axis(cand_i, sel, axis=1)), None

    init = (jnp.full((q, k), POS_BIG, jnp.float32),
            jnp.zeros((q, k), jnp.int32))
    (vals, idx), _ = jax.lax.scan(step, init, xs)
    return vals, idx                                    # ascending along k


def _candidate_mask_batch(index: BallForest, qs: dict, qb: Array,
                          block_rows: int) -> Array:
    """Batched Theorem-3 union membership -> (n, q) bool.

    :func:`_corner_admit` broadcast over the query batch, chunked over row
    blocks so the (block, M, q) intermediate bounds peak memory.
    """
    n = index.alpha_min_pt.shape[0]
    q = qb.shape[0]
    bn, nb = _block_layout(n, block_rows)
    qc = qs["qconst"].T[None, :, :]                     # (1, M, q)
    sd = qs["sqrt_delta"].T[None, :, :]                 # (1, M, q)
    qbT = qb.T[None, :, :]                              # (1, M, q)
    blocks = _corner_blocks(index, bn, nb)

    if index.storage == "int8":
        def block_mask(blk):
            am_q, gm_q, a_s, a_z, g_s, g_z = blk
            amin = qz.dequantize_stats(am_q, a_s, a_z)  # (bn, M)
            gmax = qz.dequantize_stats(gm_q, g_s, g_z)
            return _corner_admit(amin[:, :, None], gmax[:, :, None],
                                 qc, sd, qbT, sub_axis=1)   # (bn, q)
    else:
        def block_mask(blk):
            amin, gmax = blk                            # (bn, M)
            return _corner_admit(amin[:, :, None], gmax[:, :, None],
                                 qc, sd, qbT, sub_axis=1)   # (bn, q)

    mask = jax.lax.map(block_mask, blocks)              # (nb, bn, q)
    return mask.reshape(nb * bn, q)[:n]


def _corner_blocks(index: BallForest, bn: int, nb: int) -> tuple:
    """The (nb, bn, ...) corner-table blocks both prune implementations scan.

    THE one definition of the inert-row padding for the prune phase: the
    f32 tier pads ``alpha_min_pt`` with +BIG directly, the int8 tier
    streams the corner CODES (1 byte/entry) with the PAD_CORNER sentinel
    riding in the padded rows' zero-point (zero scale, so a padded row
    decodes to +BIG and fails every admission).  Shared by the streamed
    scan and the materialized reference so the two pipelines can never
    disagree on what a padded row decodes to.
    """
    if index.storage == "int8":
        return (_pad_blocks(index.alpha_min_pt, bn, nb),
                _pad_blocks(index.sqrt_gamma_max_pt, bn, nb),
                _pad_cols(index.amin_scale, bn, nb),
                _pad_cols(index.amin_zp, bn, nb, fill=POS_BIG),
                _pad_cols(index.gmax_scale, bn, nb),
                _pad_cols(index.gmax_zp, bn, nb))
    return (_pad_blocks(index.alpha_min_pt, bn, nb, fill=POS_BIG),
            _pad_blocks(index.sqrt_gamma_max_pt, bn, nb))


def _compact_candidates(mask: Array, budget: int) -> tuple[Array, Array, Array]:
    """Compact each query's union members into ``budget`` slots.

    Slot s holds the s-th member in index order, found by binary search on
    the running member count (``searchsorted(cumsum, s+1)``): O(n) cumsum +
    O(budget log n) searches per query, with no full-n top_k and no scatter
    (XLA CPU serializes scatters).  Returns (sel (q, budget) row indices,
    valid (q, budget) bool, num_candidates (q,)).  Members beyond the
    budget are dropped in index order; callers must check
    ``num_candidates <= budget`` for exactness.
    """
    maskT = mask.T                                      # (q, n)
    q, n = maskT.shape
    csum = jnp.cumsum(maskT.astype(jnp.int32), axis=1)  # (q, n) nondecreasing
    num_candidates = csum[:, -1]
    targets = jnp.arange(1, budget + 1, dtype=jnp.int32)
    sel = jax.vmap(lambda c: jnp.searchsorted(c, targets, side="left"))(csum)
    sel = jnp.minimum(sel, n - 1).astype(jnp.int32)     # clamp empty slots
    valid = targets[None, :] <= jnp.minimum(num_candidates, budget)[:, None]
    return sel, valid, num_candidates


def _rows_by_rank(admit: Array, t_ranks: int) -> Array:
    """Each query's admitted rows of a (bn, q) 0/1 tile, ascending -> (q, T).

    Entry r of column j is the row of query j's (r+1)-th admitted row for
    every r below its admit count; later entries are unadmitted rows, so
    every entry lies in [0, bn).  One sort of the unique keys row (admitted)
    or bn + row (not) along the rows: a compare network with no per-element
    gather, where a binary search over the admit prefix-sum ran log2(bn)
    dependent gathers of the whole tile (on a v5e, 17.5 ms a 4096 x 32 block
    against 0.08 ms).
    """
    bn = admit.shape[0]
    row = jnp.arange(bn, dtype=jnp.int32)[None, :]
    key = jnp.where(admit.T > 0, row, bn + row)          # (q, bn)
    key = jax.lax.sort(key, dimension=1)[:, :t_ranks]
    return jnp.where(key >= bn, key - bn, key)


def _fill_block_slots(sel: Array, count: Array, admit: Array, off: Array,
                      budget: int) -> tuple[Array, Array]:
    """Route one block's admitted rows into their budget slots.

    A block fills the contiguous slot range [count, count+tot); the row of
    within-block member rank r comes from :func:`_rows_by_rank`, one sort
    along the block's rows.  The tiered store's pooled fill
    (``core/tiered._prune_pool``) keeps a binary search on the admit
    prefix-sum: it routes a pool of up to n rows in one call, where a sort
    would cost O(pn log^2 pn) against the search's O(budget log pn).  Only
    T = min(bn, budget) ranks can occur per block, so each query reads,
    updates and writes back one T-wide window of its slots, placed where
    the block's first slot falls (or flush with the budget's end).  The
    window is a dynamic slice, the rank -> slot shift
    a slice of the doubled rank table: per block the work is O(q * T),
    never O(q * budget), and there is no per-element gather or scatter.
    Factored out of the scan bodies so the fused and unfused paths share
    slot semantics by construction.
    """
    bn = admit.shape[0]
    tot = jnp.sum(admit, axis=0)                         # (q,)
    t_ranks = min(bn, budget)
    rows_for_rank = _rows_by_rank(admit, t_ranks)        # (q, T)
    lane = jnp.arange(t_ranks, dtype=jnp.int32)

    def one_query(sel_q, rows_q, count_q, tot_q):
        start = jnp.clip(count_q, 0, budget - t_ranks)
        shift = count_q - start                          # >= 0
        # window slot j holds member rank j - shift (0-based)
        rank = lane - shift
        fill = (rank >= 0) & (rank < tot_q)
        doubled = jnp.concatenate([rows_q, rows_q])
        rows_at = jax.lax.dynamic_slice(
            doubled, (t_ranks - jnp.minimum(shift, t_ranks),), (t_ranks,))
        window = jax.lax.dynamic_slice(sel_q, (start,), (t_ranks,))
        window = jnp.where(fill, off + rows_at, window)
        return jax.lax.dynamic_update_slice(sel_q, window, (start,))

    sel = jax.vmap(one_query)(sel, rows_for_rank, count, tot)
    return sel, count + tot


def _env_tables(index: BallForest, n: int, m: int, eb: int, win: int,
                sharded: bool) -> tuple[Array, Array]:
    """Envelope tables at gate granularity ``eb``, padded with inert rows.

    The tables are STORED at ENV_BLOCK_ROWS granularity; a coarser gate
    (eb a multiple of it) min/max-coarsens them on the fly.  Coarser
    envelopes are strictly looser bounds, so the admitted-block set only
    grows and results stay bit-identical — the knob trades gate precision
    (skip rate) against gate cost, which is what the autotuner sweeps.
    """
    env_a, env_g = index.env_alpha_min, index.env_sqrt_gamma_max
    if env_a is None:
        if sharded:
            # The sharded path must carry GLOBAL envelope tables
            # (shard_index refreshes them); a local-n-sized always-admit
            # fallback indexed at a global offset would silently skip
            # every block on shards past the first.
            raise ValueError(
                "sharded streaming prune needs envelope tables; pass the "
                "forest through shard_index/refresh_envelopes first")
        # Hand-built index without envelopes: a full-length always-admit
        # table keeps the scan structure with skipping disabled.  It must
        # cover EVERY block's window (not just block 0), or later blocks
        # would slice into the inert padding and be wrongly skipped.
        ne = max(-(-n // eb), 1)
        env_a = jnp.full((ne, m), -POS_BIG, jnp.float32)
        env_g = jnp.zeros((ne, m), jnp.float32)
    elif eb != ENV_BLOCK_ROWS:
        f = eb // ENV_BLOCK_ROWS
        ne = env_a.shape[0]
        pad = -ne % f
        env_a = jnp.min(jnp.pad(env_a, ((0, pad), (0, 0)),
                                constant_values=POS_BIG)
                        .reshape(-1, f, m), axis=1)
        env_g = jnp.max(jnp.pad(env_g, ((0, pad), (0, 0)))
                        .reshape(-1, f, m), axis=1)
    env_a = jnp.pad(env_a, ((0, win), (0, 0)), constant_values=POS_BIG)
    env_g = jnp.pad(env_g, ((0, win), (0, 0)))
    return env_a, env_g


def _stream_prune_compact(index: BallForest, qs: dict, qb: Array,
                          budget: int, block_rows: int,
                          row_offset: Array | None = None,
                          fused: bool = True,
                          env_block_rows: int | None = None,
                          with_tau: bool = False, row_mask: bool = False):
    """Streaming prune + compact: one scan, no (n, q) intermediates.

    A second ``lax.scan`` over the filter's ``block_rows`` blocks replaces
    :func:`_candidate_mask_batch` + :func:`_compact_candidates` (kept as
    the bit-parity reference).  Per block:

    1. **Envelope gate** — the corner-envelope rows covering the block
       run the Theorem-3 test at block granularity.  An envelope
       dominates every row it covers, so a block NO query admits is
       skipped via ``lax.cond`` — its per-point corner tile is never
       read, its admit kernel never runs.  The FUSED path evaluates the
       whole envelope table in one vectorized pass before the scan (one
       (ne, M, q) op + a prefix-sum, so the per-block gate is two gathers
       instead of per-step dynamic slices); the unfused path keeps the
       original per-step ``dynamic_slice`` window as the comparator.
       Both compute identical gate bits.
    2. **Per-point admit** — surviving blocks call one kernel: the fused
       path runs ``bregman_filter_prune_block`` (UB tile + Theorem-3
       admit in one VMEM-resident pass over the row block — the UB
       values never round-trip through HBM, and feed the ``tau_admit``
       telemetry when ``with_tau``); the unfused path runs the original
       ``bregman_prune_block``.  Both emit the same (block, q) int32
       admit tile.
    3. **Streaming compaction** — :func:`_fill_block_slots` routes the
       block's members into the budget slots carried across blocks: one
       sort along the block's rows lists each query's admitted rows in
       row order (:func:`_rows_by_rank`), and a T-wide window per query
       places them.  Slot order = index order, identical to the reference
       compaction, which keeps its own binary search as the oracle.

    ``row_offset`` maps local rows to GLOBAL envelope rows for the
    sharded path (dist/knn.py keeps the envelope tables replicated and
    passes ``axis_index * local_n``); single-host callers leave it None.
    ``env_block_rows`` coarsens the gate granularity (see
    :func:`resolve_env_block_rows`); results are invariant, skip rates
    are not.  Returns ``(sel (q, budget), valid (q, budget),
    num_candidates (q,), env_admitted (q,), blocks_run (), tau (q,))``:
    ``env_admitted`` counts, per query, the (block, query) tiles the
    envelope gate admitted — ``nb * q - sum(env_admitted)`` tiles were
    rejected at envelope level — while ``blocks_run`` counts the blocks
    whose per-point kernel actually executed (a block runs, for ALL its
    query columns, whenever ANY query admits it).  ``tau`` is the
    per-query min UB over admitted rows (+BIG when nothing admitted or
    ``with_tau`` is off — the fused kernel's UB output is only consumed,
    and on the jnp ref path only computed, when the caller asks).

    ``row_mask`` (a launch the refine's shared pass takes) fills no slots:
    each block writes which of its rows the slots would hold, its admitted
    rows whose running member count stays within the budget, and the scan
    returns ``sel`` None and ``valid`` as that (q, n) row mask.
    """
    from repro.kernels import ops as kernel_ops
    n = index.alpha_min_pt.shape[0]
    q, m = qb.shape
    bn, nb = _block_layout(n, block_rows)
    eb = resolve_env_block_rows(env_block_rows)
    offs = jnp.arange(nb, dtype=jnp.int32) * bn
    # A block of bn rows spans at most win = ceil(bn / eb) + 1 envelope
    # rows at any alignment.  Pad with inert rows (never admit) so every
    # window is in range: block starts lie below the covered row count,
    # hence window starts below the unpadded table length.
    win = -(-bn // eb) + 1
    env_a, env_g = _env_tables(index, n, m, eb, win,
                               sharded=row_offset is not None)
    qcT, sdT, qbT = qs["qconst"].T, qs["sqrt_delta"].T, qb.T   # (M, q)
    goffs = offs if row_offset is None else row_offset + offs  # (nb,)

    if fused:
        # Hoisted envelope gate: per-row admit over the whole (padded)
        # table in one op, then each block's OR-over-span via a prefix-sum
        # difference — bitwise the same gate as the windowed slice (same
        # per-row admit bits, same span), without nb dynamic slices.
        lb_env = (env_a[:, :, None] + qcT[None]
                  - env_g[:, :, None] * sdT[None])         # (nep, M, q)
        row_admit = jnp.any(lb_env <= qbT[None], axis=1)   # (nep, q)
        ecs = jnp.concatenate(
            [jnp.zeros((1, q), jnp.int32),
             jnp.cumsum(row_admit.astype(jnp.int32), axis=0)], axis=0)
        e0s = goffs // eb                                  # (nb,)
        e_his = (goffs + bn - 1) // eb
        env_admit_all = (jnp.take(ecs, e_his + 1, axis=0)
                         - jnp.take(ecs, e0s, axis=0)) > 0  # (nb, q)
        xs = (_filter_blocks(index, bn, nb)
              + _corner_blocks(index, bn, nb) + (offs, env_admit_all))
    else:
        xs = _corner_blocks(index, bn, nb) + (offs,)

    def gate_windowed(goff):
        e0 = goff // eb
        wa = jax.lax.dynamic_slice(env_a, (e0, 0), (win, env_a.shape[1]))
        wg = jax.lax.dynamic_slice(env_g, (e0, 0), (win, env_g.shape[1]))
        # The static window is sized for the worst misalignment; rows past
        # the block's actual envelope span (e.g. the whole +1 row when the
        # block is eb-aligned) are masked inert so they cannot loosen the
        # gate.
        e_hi = (goff + bn - 1) // eb
        in_span = (e0 + jnp.arange(win)) <= e_hi                # (win,)
        wa = jnp.where(in_span[:, None], wa, POS_BIG)
        wg = jnp.where(in_span[:, None], wg, 0.0)
        lb = wa[:, :, None] + qcT[None] - wg[:, :, None] * sdT[None]
        return jnp.any(lb <= qbT[None], axis=(0, 1))            # (q,)

    def step(carry, blk):
        sel, count, admitted, blocks_run, tau = carry
        if fused:
            off, env_admit = blk[-2], blk[-1]
        else:
            off = blk[-1]
            env_admit = gate_windowed(
                off if row_offset is None else row_offset + off)

        def run(args):
            sel, count, tau = args
            if fused:
                if index.storage == "int8":
                    (a, sg, a_s, a_z, g_s, g_z,
                     am, gm, am_s, am_z, gm_s, gm_z, _, _) = blk
                    ub, admit = kernel_ops.bregman_filter_prune_block_quant(
                        a, a_s, a_z, sg, g_s, g_z,
                        am, am_s, am_z, gm, gm_s, gm_z,
                        qs["qconst"], qs["sqrt_delta"], qb)      # (bn, q) x2
                else:
                    a, sg, am, gm, _, _ = blk
                    ub, admit = kernel_ops.bregman_filter_prune_block(
                        a, sg, am, gm,
                        qs["qconst"], qs["sqrt_delta"], qb)
            else:
                ub = None
                if index.storage == "int8":
                    am, gm, a_s, a_z, g_s, g_z, _ = blk
                    admit = kernel_ops.bregman_prune_block_quant(
                        am, a_s, a_z, gm, g_s, g_z,
                        qs["qconst"], qs["sqrt_delta"], qb)      # (bn, q)
                else:
                    am, gm, _ = blk
                    admit = kernel_ops.bregman_prune_block(
                        am, gm, qs["qconst"], qs["sqrt_delta"], qb)
            gidx = off + jnp.arange(bn, dtype=jnp.int32)
            admit = admit * (gidx < n).astype(jnp.int32)[:, None]
            if with_tau and ub is not None:
                tau = jnp.minimum(
                    tau, jnp.min(jnp.where(admit > 0, ub, POS_BIG), axis=0))
            if row_mask:
                # A member of running rank r lands in slot r - 1.
                rank = count + jnp.cumsum(admit, axis=0)        # (bn, q)
                return sel, rank[-1], tau, (admit > 0) & (rank <= budget)
            sel, count = _fill_block_slots(sel, count, admit, off, budget)
            return sel, count, tau

        def skip(args):
            return args + (jnp.zeros((bn, q), bool),) if row_mask else args

        any_admit = jnp.any(env_admit)
        sel, count, tau, *rows = jax.lax.cond(any_admit, run, skip,
                                              (sel, count, tau))
        return (sel, count, admitted + env_admit.astype(jnp.int32),
                blocks_run + any_admit.astype(jnp.int32), tau), (
                    rows[0] if row_mask else None)

    # Unfilled slots hold n-1, matching _compact_candidates' clamp, so the
    # two implementations agree bit-for-bit on every output.
    init = (jnp.full((q, 0 if row_mask else budget), n - 1, jnp.int32),
            jnp.zeros((q,), jnp.int32), jnp.zeros((q,), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.full((q,), POS_BIG, jnp.float32))
    (sel, count, admitted, blocks_run, tau), rows = jax.lax.scan(step, init,
                                                                 xs)
    if row_mask:
        in_slot = rows.reshape(nb * bn, q)[:n].T                 # (q, n)
        return None, in_slot, count, admitted, blocks_run, tau
    targets = jnp.arange(1, budget + 1, dtype=jnp.int32)
    valid = targets[None, :] <= jnp.minimum(count, budget)[:, None]
    return sel, valid, count, admitted, blocks_run, tau


def _refine_dists(index: BallForest, grad: Array, c_y: Array, sel: Array):
    """Exact distances of the candidate rows ``sel`` (q, budget) -> (q, budget).

    The int8 tier gathers candidate CODES (1 byte/coord) plus two decode
    scalars per row and runs the fused dequantize+refine kernel, so the
    full fp32 point table never exists — exact distances over the decoded
    point set, 4x less refine gather traffic.
    """
    from repro.kernels import ops as kernel_ops
    if index.storage == "int8":
        with jax.named_scope("gather"):
            codes = jnp.take(index.data, sel, axis=0)   # (q, budget, d) int8
            scale = jnp.take(index.data_scale, sel)     # (q, budget)
            zp = jnp.take(index.data_zp, sel)
        return kernel_ops.bregman_refine_batch_quant(
            codes, scale, zp, grad, c_y, index.family_name)
    with jax.named_scope("gather"):
        rows = jnp.take(index.data, sel, axis=0)        # (q, budget, d)
    return kernel_ops.bregman_refine_batch(rows, grad, c_y,
                                           index.family_name)


def _shared_dists(index: BallForest, grad: Array, c_y: Array) -> Array:
    """Exact distances of every stored row to each query -> (q, n).

    The kernel's shared-rows form reads each row once for all the queries:
    no candidate rows are gathered.
    """
    from repro.kernels import ops as kernel_ops
    if index.storage == "int8":
        return kernel_ops.bregman_refine_batch_quant(
            index.data, index.data_scale, index.data_zp, grad, c_y,
            index.family_name)
    return kernel_ops.bregman_refine_batch(index.data, grad, c_y,
                                           index.family_name)


# Largest candidate-row gather one refine step materializes, and largest
# (queries, n) float32 distance block of a shared pass.  A batch over the
# cap is refined in query chunks: an escalated budget near n would
# otherwise gather q copies of the table at once.  The int8 tier's per-row
# decode scalars ride as (budget, 1) columns, which a TPU pads to 128
# lanes, so its true gather footprint is several times the code bytes
# counted here; the cap leaves room for that.
REFINE_GATHER_BYTES = 1 << 28


def _shared_chunk(q: int, n: int) -> int:
    """Queries per shared pass: its (chunk, n) float32 block under the cap."""
    return max(1, min(q, REFINE_GATHER_BYTES // (4 * n)))


def dense_refine(q: int, slots: int, n: int) -> bool:
    """Whether a launch of ``q`` queries with ``slots`` candidate slots each
    (its resolved budget) over ``n`` stored rows refines by shared passes
    over the table.

    The one rule, a pure function of the shapes: the jitted refine and the
    host's per-launch ``dense`` records both call it.  The shared passes
    read ``ceil(q / chunk) * n`` rows; the gather reads ``q * slots``.  A
    sharded launch passes its shard's ``n``.
    """
    passes = -(-q // _shared_chunk(q, n))
    return passes * n <= q * slots


def _by_query_chunks(fn, chunk: int, *args):
    """``fn(*args)`` over query chunks of ``chunk`` rows (lax.map)."""
    q = args[0].shape[0]
    if chunk >= q:
        return fn(*args)
    nc = -(-q // chunk)

    def chunked(a):
        pad = ((0, nc * chunk - q),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, pad).reshape((nc, chunk) + a.shape[1:])

    out = jax.lax.map(lambda a: fn(*a), tuple(chunked(a) for a in args))
    return jax.tree.map(lambda o: o.reshape((nc * chunk,) + o.shape[2:])[:q],
                        out)


def _refine_batch(index: BallForest, qs: dict, sel: Array | None,
                  valid: Array, k: int):
    """Exact distances of each query's candidates, then its top-k.

    The candidates come as ``budget`` slots, rows ``sel`` (q, budget) with
    ``valid`` (q, budget), whose rows the refine gathers; or, for a launch
    that takes the shared pass (:func:`dense_refine`), as ``sel`` None and
    a (q, n) row mask ``valid`` of the same rows.  The shared pass reads the
    table once per query chunk and ranks the masked (q, n) distances: rows
    in slot order, so ties and the rows past the last candidate (the
    unfilled slots' row n-1) come out as the gather's.
    """
    q = valid.shape[0]
    grad, c_y = qs["grad"], qs["c_y"]
    if sel is None:
        def ranked(grad, c_y, mask):
            dist = jnp.where(mask, _shared_dists(index, grad, c_y), POS_BIG)
            neg, rows = jax.lax.top_k(-dist, k)
            return neg, jnp.where(jnp.take_along_axis(mask, rows, axis=1),
                                  rows, index.n - 1)

        neg, rows = _by_query_chunks(ranked, _shared_chunk(q, index.n),
                                     grad, c_y, valid)
    else:
        per_query = sel.shape[1] * index.d * index.data.dtype.itemsize
        chunk = max(1, min(q, REFINE_GATHER_BYTES // per_query))
        dist = _by_query_chunks(functools.partial(_refine_dists, index),
                                chunk, grad, c_y, sel)
        dist = jnp.where(valid, dist, POS_BIG)
        neg, pos = jax.lax.top_k(-dist, k)              # (q, k)
        rows = jnp.take_along_axis(sel, pos, axis=1)
    return jnp.take(index.point_ids, rows), -neg


def _knn_search_batch_core(index: BallForest, ys: Array, k: int, budget: int,
                           p_guarantee: Array | None, block_rows: int,
                           streaming: bool = True, with_stats: bool = False,
                           fused: bool = True,
                           env_block_rows: int | None = None):
    if k > index.n:
        # The streaming merge always has >= k columns, so without this guard
        # a too-large k would silently return sentinel rows as "exact".
        raise ValueError(f"k={k} exceeds index size n={index.n}")
    if budget < k:
        raise ValueError(f"budget={budget} must be >= k={k} (the refine "
                         "top-k needs at least k slots)")
    if ys.ndim != 2:
        raise ValueError(f"expected (q, d) queries, got {ys.shape}")
    # Each phase runs under a named scope (bp.filter, bp.prune, bp.refine):
    # the scope rides every HLO instruction's op_name metadata, so a
    # profiler trace attributes device time to phases.  Compile-time only.
    # ---- phase 1+2: one fused filter matmul + streaming k-selection ----
    # The k-th row's tuple sets qb; the full top-k indices feed the int8
    # tier's bound slack (max rounding error over the rows that could have
    # determined the k-th UB).
    with jax.named_scope("bp.filter"):
        qs = _query_struct(index, ys)                   # all fields (q, ...)
        _, idx = _batch_filter_topk(index, qs, k, block_rows)
        kth = idx[:, -1]                                # (q,)
        kth_tuple = _tuple_rows(index, kth)
        sqrt_term = kth_tuple["sqrt_gamma"] * qs["sqrt_delta"]   # (q, M)
        qb = (bounds.ub_components(kth_tuple, qs)       # (q, M) Alg. 4
              + _qb_slack(index, idx, qs["sqrt_delta"]))

        if p_guarantee is not None:                     # §8 shrink, batched
            kappa_i = qb - sqrt_term
            c = _cdf_shrink(index.beta_samples, jnp.sum(sqrt_term, -1),
                            jnp.sum(kappa_i, -1), p_guarantee)
            qb = kappa_i + c[:, None] * sqrt_term

    # ---- phase 3+4: streaming prune + compact (block-skip from envelopes),
    # then one batched refine ----
    shared = dense_refine(ys.shape[0], budget, index.n)
    with jax.named_scope("bp.prune"):
        if streaming:
            (sel, valid, num_candidates, env_admitted, blocks_run,
             tau) = _stream_prune_compact(index, qs, qb, budget, block_rows,
                                          fused=fused,
                                          env_block_rows=env_block_rows,
                                          with_tau=with_stats and fused,
                                          row_mask=shared)
        else:
            # Reference path: materialized (n, q) mask + (q, n) cumsum.
            mask = _candidate_mask_batch(index, qs, qb, block_rows)
            sel, valid, num_candidates = _compact_candidates(mask, budget)
            if shared:                  # the slots' rows, as a row mask
                member = mask.T.astype(jnp.int32)
                sel, valid = None, (member > 0) & (
                    jnp.cumsum(member, axis=1) <= budget)
            env_admitted = jnp.zeros((ys.shape[0],), jnp.int32)
            blocks_run = jnp.zeros((), jnp.int32)
            tau = jnp.full((ys.shape[0],), POS_BIG, jnp.float32)
    with jax.named_scope("bp.refine"):
        ids, dists = _refine_batch(index, qs, sel, valid, k)
    res = SearchResult(ids=ids, dists=dists,
                       exact=num_candidates <= budget,
                       num_candidates=num_candidates)
    return (res, env_admitted, blocks_run, tau) if with_stats else res


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows",
                                             "env_block_rows"))
def _knn_search_batch_jit(index: BallForest, ys: Array, k: int, budget: int,
                          block_rows: int,
                          env_block_rows: int | None = None) -> SearchResult:
    return _knn_search_batch_core(index, ys, k, budget, None, block_rows,
                                  env_block_rows=env_block_rows)


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows",
                                             "env_block_rows"))
def _knn_search_batch_unfused_jit(
    index: BallForest, ys: Array, k: int, budget: int, block_rows: int,
    env_block_rows: int | None = None,
) -> SearchResult:
    """The two-kernel streamed pipeline (separate UB + prune kernels,
    per-step envelope windows) — kept compiled as the fused path's A/B
    comparator for benchmarks and parity tests."""
    return _knn_search_batch_core(index, ys, k, budget, None, block_rows,
                                  fused=False, env_block_rows=env_block_rows)


def knn_search_batch(index, ys: Array, k: int, budget: int,
                     block_rows: int | None = None,
                     validate: bool = True,
                     env_block_rows: int | None = None) -> SearchResult:
    """Exact kNN for a (q, d) query block — one jitted program, (q, ...) fields."""
    if getattr(index, "is_tiered_store", False):
        # Out-of-core index (core/tiered.py): same pipeline, re-cut at the
        # host/device boundary — bit-identical results by contract.
        return index.search(ys, k, budget, block_rows=block_rows,
                            env_block_rows=env_block_rows,
                            validate=validate)
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    if validate:
        validate_queries(index.family, ys)
    br = resolve_block_rows(block_rows, index.n, q=ys.shape[0],
                            storage=index.storage)
    return _knn_search_batch_jit(index, ys, k, budget, br,
                                 resolve_env_block_rows(env_block_rows))


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows"))
def _knn_search_batch_approx_jit(
    index: BallForest, ys: Array, k: int, budget: int, p_guarantee: Array,
    block_rows: int,
) -> SearchResult:
    return _knn_search_batch_core(index, ys, k, budget, p_guarantee,
                                  block_rows)


def knn_search_batch_approx(
    index, ys: Array, k: int, budget: int, p_guarantee: Array | None = None,
    block_rows: int | None = None, validate: bool = True,
    target_recall: float | None = None,
) -> SearchResult:
    """§8 approximate kNN for a (q, d) block; CDF shrink vectorized over q.

    Exactly one of ``p_guarantee`` (the raw §8 knob) and ``target_recall``
    must be given.  ``target_recall`` inverts the index's fitted recall
    calibration (core/calibrate.py) on the host to pick the shrink level —
    the measured-recall contract; on an uncalibrated index it falls back
    to ``p_guarantee = target_recall`` with a one-time warning.
    """
    if getattr(index, "is_tiered_store", False):
        if (p_guarantee is None) == (target_recall is None):
            raise ValueError(
                "pass exactly one of p_guarantee / target_recall")
        return index.search(ys, k, budget, p_guarantee=p_guarantee,
                            target_recall=target_recall,
                            block_rows=block_rows, validate=validate)
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    if (p_guarantee is None) == (target_recall is None):
        raise ValueError(
            "pass exactly one of p_guarantee / target_recall")
    if target_recall is not None:
        p_guarantee, _ = resolve_p_guarantee(index, target_recall)
    validate_p_guarantee(p_guarantee)
    if validate:
        validate_queries(index.family, ys)
    br = resolve_block_rows(block_rows, index.n, q=ys.shape[0],
                            storage=index.storage)
    return _knn_search_batch_approx_jit(index, ys, k, budget,
                                        jnp.float32(p_guarantee), br)


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows"))
def _knn_search_batch_stats_jit(index: BallForest, ys: Array, k: int,
                                budget: int, block_rows: int):
    return _knn_search_batch_core(index, ys, k, budget, None, block_rows,
                                  with_stats=True)


def knn_search_batch_stats(index, ys: Array, k: int, budget: int,
                           block_rows: int | None = None,
                           ) -> tuple[SearchResult, dict]:
    """:func:`knn_search_batch` plus envelope block-skip telemetry.

    Returns ``(result, stats)`` with the streaming scan's shape
    (``num_blocks``, resolved ``block_rows``) and two distinct skip
    metrics — read them carefully when capacity planning:

    * ``block_skip_rate`` — fraction of (block, query) TILES the envelope
      gate rejected.  A rejected tile provably contributes no candidate,
      but its block's per-point kernel still runs (for all query columns)
      if ANY other query admits the block.
    * ``whole_block_skip_rate`` — fraction of BLOCKS whose per-point
      kernel never executed because every query rejected them; this is
      the fraction of per-point admit compute actually avoided.

    Same compiled pipeline as the plain entry point modulo the returned
    counters; meant for benchmarks and capacity planning, not the serving
    hot path.
    """
    if getattr(index, "is_tiered_store", False):
        raise TypeError(
            "knn_search_batch_stats runs the all-resident pipeline; a "
            "TieredPointStore reports its own telemetry via store.stats / "
            "store.cache_info(), or pass store.as_resident_forest()")
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    br = resolve_block_rows(block_rows, index.n, q=ys.shape[0],
                            storage=index.storage)
    res, env_admitted, blocks_run, tau = _knn_search_batch_stats_jit(
        index, ys, k, budget, br)
    bn, nb = _block_layout(index.n, br)
    tiles = nb * ys.shape[0]
    stats = {
        "block_rows": bn,
        "num_blocks": nb,
        "num_blocks_run": int(blocks_run),
        "env_admitted_tiles": int(jnp.sum(env_admitted)),
        "block_skip_rate": 1.0 - float(jnp.sum(env_admitted)) / tiles,
        "whole_block_skip_rate": 1.0 - int(blocks_run) / nb,
        # Tightest filter UB among admitted rows, per query — an upper
        # bound on the true kNN distance, a byproduct of the fused
        # kernel's VMEM-resident UB tile (no extra HBM traffic).
        "tau_admit": tau,
    }
    return res, stats


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows"))
def _knn_search_batch_ref_jit(index: BallForest, ys: Array, k: int,
                              budget: int, block_rows: int) -> SearchResult:
    return _knn_search_batch_core(index, ys, k, budget, None, block_rows,
                                  streaming=False)


@functools.partial(jax.jit, static_argnames=("k", "budget", "block_rows"))
def _knn_search_batch_ref_approx_jit(
    index: BallForest, ys: Array, k: int, budget: int, p_guarantee: Array,
    block_rows: int,
) -> SearchResult:
    return _knn_search_batch_core(index, ys, k, budget, p_guarantee,
                                  block_rows, streaming=False)


def knn_search_batch_reference(index, ys: Array, k: int, budget: int,
                               p_guarantee: Array | None = None,
                               block_rows: int | None = None) -> SearchResult:
    """The materialized mask/cumsum pipeline — the bit-parity oracle.

    Identical math to :func:`knn_search_batch` but pruning via the full
    (n, q) Theorem-3 mask and compaction via the (q, n) cumsum binary
    search (the pre-streaming implementation).  O(n * q) peak memory, so
    tests and benchmarks only; the streamed path must match it
    bit-for-bit on every output field.
    """
    if getattr(index, "is_tiered_store", False):
        raise TypeError(
            "knn_search_batch_reference materializes the full (n, q) mask "
            "on device — meaningless for an out-of-core store; pass "
            "store.as_resident_forest() to oracle against the same points")
    index = _as_forest(index, k)
    budget = resolve_budget(budget, index.n, k)
    validate_p_guarantee(p_guarantee)
    br = resolve_block_rows(block_rows, index.n)
    if p_guarantee is None:
        return _knn_search_batch_ref_jit(index, ys, k, budget, br)
    return _knn_search_batch_ref_approx_jit(index, ys, k, budget,
                                            jnp.float32(p_guarantee), br)


# ---------------------------------------------------------------------------
# Host wrappers (escape hatch: double the budget until the union fits)
# ---------------------------------------------------------------------------

MAX_BUDGET_DOUBLINGS = 8


def resolve_budget(budget, n: int, k: int) -> int:
    """THE refine-budget resolver: every public entry point routes its
    ``budget`` knob through here before first use (brelint knob-contract,
    docs/static_analysis.md).

    ``None`` picks the cost model's candidate estimate; an explicit
    budget must be an integer >= k (fewer slots can never hold the k
    results — the same contract the jit core enforces) and is clamped to
    ``n``: a pinned budget can outlive a compaction that shrank the index
    (serve/knnlm.py), and ``top_k(priority, budget)`` needs budget <= n.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"resolve_budget: empty index (n={n})")
    if k > n:
        # Diagnose the real error before the budget math trips over it
        # (same message as the jit core's trace-time guard).
        raise ValueError(f"k={k} exceeds index size n={n}")
    if budget is None:
        return int(min(n, max(4 * k, 64, n // 16)))
    if isinstance(budget, bool) or budget != int(budget):
        raise TypeError(f"budget must be an int or None, got {budget!r}")
    budget = int(budget)
    if budget < k:
        raise ValueError(f"budget={budget} must be >= k={k} (the refine "
                         "top-k needs at least k slots)")
    return min(budget, n)


def validate_p_guarantee(p) -> None:
    """Range-gate a raw §8 shrink probability (``p_guarantee`` /
    ``approx_p``) before it enters a jitted program.

    Only host scalars are checked — traced/jax values pass through
    untouched (there is no host value to compare, and coercing one would
    be exactly the host-op-under-trace defect brelint exists to catch);
    the calibration sweep and the jit cores feed those paths.
    """
    if p is None:
        return
    if isinstance(p, bool) or not isinstance(
            p, (int, float, np.floating, np.integer)):
        return
    v = float(p)
    if not 0.0 <= v <= 1.0:    # False for NaN too
        raise ValueError(f"p_guarantee must be within [0, 1], got {v}")


def default_budget(index: BallForest, k: int) -> int:
    """Initial refine budget ~ the cost model's candidate estimate."""
    return resolve_budget(None, index.n, k)


def fitted_budget_for_n(n: int, k: int, needed: int) -> int:
    """Smallest power-of-two budget (>= k, capped at ``n``) covering
    ``needed`` candidates.  The ONE sizing rule for overflow handling:
    retries (single-host AND per-shard — dist/knn.py passes the shard
    size as ``n``) and serving-side pinned budgets all use it, so they
    land on the same static shapes and reuse each other's compiled
    programs.
    """
    need = max(int(needed), k, 1)
    return int(min(n, 1 << (need - 1).bit_length()))


def fitted_budget(index: BallForest, k: int, needed: int) -> int:
    """:func:`fitted_budget_for_n` against a whole index."""
    return fitted_budget_for_n(index.n, k, needed)


def knn(index: BallForest, y, k: int, budget: int | None = None,
        approx_p: float | None = None) -> SearchResult:
    """Host-level kNN: retries with doubled budget when the union overflows.

    Always exact when ``approx_p is None``; with ``approx_p`` the result has
    the paper's probability guarantee instead.
    """
    index = _as_forest(index, k)
    y = jnp.asarray(y, jnp.float32)
    validate_queries(index.family, y)
    validate_p_guarantee(approx_p)
    budget = resolve_budget(budget, index.n, k)
    while True:
        if approx_p is None:
            res = knn_search(index, y, k, budget, validate=False)
        else:
            res = knn_search_approx(index, y, k, budget,
                                    jnp.float32(approx_p), validate=False)
        if bool(res.exact) or budget >= index.n:
            return res
        budget = min(index.n, budget * 2)


def knn_batch(index: BallForest, ys, k: int, budget: int | None = None,
              approx_p: float | None = None, *,
              target_recall: float | None = None,
              max_doublings: int = MAX_BUDGET_DOUBLINGS,
              block_rows: int | None = None,
              stop_retry=None, return_stats: bool = False,
              validate: bool = True):
    """Batched kNN via the fused :func:`knn_search_batch` pipeline.

    One retry policy for the whole batch: if ANY query's Theorem-3 union
    overflows, the block re-runs with a budget sized to the largest
    observed union (``num_candidates`` is budget-independent, so one retry
    normally resolves the overflow), rounded up to a power of two so
    repeated budgets reuse compiled programs.  The loop is bounded by
    ``max_doublings``; if exhausted, a warning is logged and the block
    falls back to ONE fused brute-force scan (exact by construction, no
    per-query dataset gather), preserving the invariant that exact-mode
    results are exact and approx-mode results carry the §8 guarantee.

    ``block_rows`` tunes the streaming scans' block size (peak memory vs
    scan overhead — :func:`resolve_block_rows`); it is forwarded to every
    retry, so one setting governs the whole call.

    **Deadline-capped ladder**: ``stop_retry`` (no-arg callable -> bool) is
    consulted before every ADDITIONAL launch — each budget-growth retry
    and the final scan escalation.  Returning True ends the ladder
    immediately with the best result so far (rows whose union overflowed
    keep ``exact=False`` — a budget-capped PARTIAL result), instead of
    doubling forever past a deadline.  serve/retrieval.py passes
    ``lambda: clock() + est_launch > deadline`` here; the default ``None``
    preserves the always-exact contract.

    ``return_stats=True`` returns ``(SearchResult, BatchStats)`` — the
    structured escalation counters services and benchmarks alert on
    (the log line is advisory only) and one record per launch.  Each
    launch runs inside a ``knn.launch`` profiler span (``budget``,
    ``attempt``) around ``knn.dispatch`` and ``knn.wait``, as the
    service's ``svc.launch``.

    ``target_recall`` (mutually exclusive with ``approx_p``) selects the
    approximate mode at a CALIBRATED shrink: the index's fitted recall
    curve is inverted on the host (core/calibrate.py) and the resolved
    ``p_guarantee`` drives the usual §8 pipeline.
    """
    index = _as_forest(index, k)
    if target_recall is not None:
        if approx_p is not None:
            raise ValueError(
                "pass at most one of approx_p / target_recall")
        approx_p, _ = resolve_p_guarantee(index, target_recall)
    validate_p_guarantee(approx_p)
    ys = jnp.asarray(ys, jnp.float32)
    if ys.ndim != 2:
        raise ValueError(f"knn_batch wants (q, d) queries, got {ys.shape}")
    if validate:
        validate_queries(index.family, ys)
    budget = resolve_budget(budget, index.n, k)
    p = None if approx_p is None else jnp.float32(approx_p)

    def run(b):
        if p is None:
            return knn_search_batch(index, ys, k, b, block_rows,
                                    validate=False)
        return knn_search_batch_approx(index, ys, k, b, p, block_rows,
                                       validate=False)

    launches = []

    def launch(b, attempt, thunk, dense):
        with jax.profiler.TraceAnnotation("knn.launch", budget=b,
                                          attempt=attempt):
            with jax.profiler.TraceAnnotation("knn.dispatch"):
                t1 = time.perf_counter()
                res = thunk()
            with jax.profiler.TraceAnnotation("knn.wait"):
                t2 = time.perf_counter()
                jax.block_until_ready(res)
                t3 = time.perf_counter()
            launches.append({
                "budget": int(b),
                "num_candidates": int(np.asarray(res.num_candidates).max()),
                "dense": dense, "dispatch_s": t2 - t1, "wait_s": t3 - t2})
        return res

    def done(res, escalations, scan=False, stopped=False):
        stats = BatchStats(escalations=escalations, budget_final=budget,
                           escalated_to_scan=scan, stopped_early=stopped,
                           launches=tuple(launches))
        return (res, stats) if return_stats else res

    for attempt in range(max_doublings + 1):
        # A tiered store refines by its own gather (core/tiered.py).
        dense = (not getattr(index, "is_tiered_store", False)
                 and dense_refine(ys.shape[0], budget, index.n))
        res = launch(budget, attempt, lambda: run(budget), dense)
        if bool(np.asarray(res.exact).all()) or budget >= index.n:
            return done(res, attempt)
        if attempt == max_doublings:
            break
        if stop_retry is not None and stop_retry():
            # Deadline exhausted: hand back the budget-capped partial
            # result (overflowed rows keep exact=False) instead of
            # launching again.
            return done(res, attempt, stopped=True)
        # needed > budget on overflow, so the fitted budget strictly grows.
        budget = fitted_budget(index, k, launches[-1]["num_candidates"])
    escalations = max_doublings
    if stop_retry is not None and stop_retry():
        return done(res, escalations, stopped=True)
    logger.warning(
        "knn_batch: budget cap exhausted after %d doublings (budget=%d, "
        "%d/%d queries overflowed); escalating to a full linear scan "
        "(n=%d)", max_doublings, budget,
        int(jnp.sum(~res.exact)), ys.shape[0], index.n)
    # Full scan instead of run(index.n): a budget=n refine would gather a
    # (q, n, d) copy of the dataset; the fused brute-force distance needs
    # no per-query row gather.  num_candidates (budget-independent) comes
    # from the last capped run.  A tiered store pays one full
    # materialization here — the escalation is already the worst case.
    scan_index = (index.as_resident_forest()
                  if getattr(index, "is_tiered_store", False) else index)
    capped = res

    def scan():
        ids, dists = _brute_force_live(scan_index, ys, k)
        return SearchResult(ids=ids, dists=dists,
                            exact=jnp.ones(ys.shape[0], bool),
                            num_candidates=capped.num_candidates)

    res = launch(index.n, max_doublings + 1, scan, False)
    return done(res, escalations, scan=True)


@functools.partial(jax.jit, static_argnames=("k",))
def _brute_force_live(index: BallForest, ys: Array, k: int):
    """Linear scan over the LIVE rows of an index — the escalation oracle.

    Unlike :func:`brute_force_knn` over ``index.data``, this masks
    tombstoned/padded rows (``point_ids < 0``, whose data is the inert
    ones-fill at a finite distance) so a mutated index never surfaces a
    deleted id even on the budget-cap escape hatch.  ``rows_view`` decodes
    the int8 tier, so the scan is exact over the stored point set there
    too.
    """
    fam = index.family
    rows = index.rows_view()
    dist = jax.vmap(lambda y: fam.distance(rows, y[None, :]))(ys)
    dist = jnp.where((index.point_ids >= 0)[None, :], dist, POS_BIG)
    neg, idx = jax.lax.top_k(-dist, k)                  # (q, k)
    return jnp.take(index.point_ids, idx), -neg


def brute_force_knn(data, y, k: int, family) -> tuple[Array, Array]:
    """Linear-scan oracle (used by tests and as the paper's baseline floor).

    ``y`` may be a single (d,) query or a (q, d) batch; the batch form
    returns ((q, k) ids, (q, k) dists) so tests and benchmarks share one
    oracle with the batched pipeline.
    """
    fam = get_family(family) if isinstance(family, str) else family
    y = jnp.asarray(y)
    if y.ndim == 2:
        return jax.vmap(lambda yy: brute_force_knn(data, yy, k, fam))(y)
    dist = fam.distance(jnp.asarray(data), y[None, :])
    neg, idx = jax.lax.top_k(-dist, k)
    return idx, -neg
