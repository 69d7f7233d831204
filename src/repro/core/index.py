"""BallForest — the TPU-native BB-forest (paper §6, adapted per DESIGN.md §2).

One flat Bregman-ball table per subspace (IVF-style, no pointer chasing),
all tables indexing the SAME physical point order.  The shared order is the
paper's BB-forest layout trick: points are sorted by the reference
subspace's cluster id, so candidate gathers from different subspaces touch
overlapping regions (the TPU analogue of shared disk pages, boosted by PCCP
making subspace clusterings similar).

Pruning uses the tuple-space cluster lower bound (DESIGN.md §3.3):

    LB_cluster(i) = alpha_min[c,i] + qconst[i] - sqrt_gamma_max[c,i]*sqrt_delta[i]
                  <= min_{x in c} D_f(x_i., y_i.)

so "LB_cluster > qb_i" prunes cluster c in subspace i without any member
distance evaluation, and never prunes a true Theorem-3 candidate.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from .bregman import BregmanFamily, get_family
from .transform import Partition, make_partition, p_transform
from .partition import build_pccp_partition, fit_cost_model
from .clustering import kmeans, cluster_stats
from . import quantize as qz

Array = jax.Array

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class BallForest:
    """Immutable search index. All arrays live on device (or sharded).

    Two storage tiers share this one dataclass (``storage`` is static):

    * ``"f32"`` — the original layout: every point-major table fp32.
    * ``"int8"`` — ``data``/``alpha``/``sqrt_gamma``/``alpha_min_pt``/
      ``sqrt_gamma_max_pt`` hold int8 CODES and the ``*_scale``/``*_zp``
      companions hold the per-row affine decode (core/quantize.py).  The
      index's point set is the DEQUANTIZED rows (:meth:`rows_view`); the
      search pipeline stays exact over that set because filter bounds are
      inflated by the stat rounding error and corner stats are
      directed-rounded (conservative) at build time.

    Never read ``data``/``alpha``/... raw in new code — go through
    :meth:`rows_view` / the dequant helpers in core/search.py, which are
    the single place the storage variants branch.
    """

    family_name: str
    partition: Partition
    num_clusters: int
    data: Array           # (n, d)  points in shared layout order (codes in int8)
    point_ids: Array      # (n,)    original ids (layout -> original)
    alpha: Array          # (n, M)  P-tuple alpha (codes in int8)
    sqrt_gamma: Array     # (n, M)  P-tuple sqrt(gamma) (codes in int8)
    assign: Array         # (n, M)  cluster id of each point per subspace
    alpha_min: Array      # (M, C)  per-cluster min alpha
    sqrt_gamma_max: Array # (M, C)  per-cluster max sqrt(gamma)
    counts: Array         # (M, C)
    centers: Array        # (M, C, w) cluster centers (diagnostics/benchmarks)
    beta_samples: Array   # (S,) sorted empirical beta_xy sample (approx search)
    alpha_min_pt: Array       # (n, M)  own-cluster corner alpha_min per point
    sqrt_gamma_max_pt: Array  # (n, M)  own-cluster corner sqrt_gamma_max per point
    gamma_edges: Array    # (M, nb-1) gamma-bucket quantile edges (for appends)
    storage: str = "f32"      # "f32" | "int8" — static (jit cache key)
    # Per-block corner envelopes over ENV_BLOCK_ROWS-row groups of the
    # layout: row e holds the tightest alpha_min / loosest sqrt_gamma_max of
    # rows [e*ENV_BLOCK_ROWS, (e+1)*ENV_BLOCK_ROWS) — always fp32 (in the
    # int8 tier they are reduced over the DECODED directed-rounded corners,
    # so they dominate exactly what the per-point test decodes).  The
    # streaming batched prune tests a whole block against these before
    # touching its per-point tile and skips blocks no query admits
    # (core/search._stream_prune_compact).  Tiny (n / ENV_BLOCK_ROWS rows),
    # replicated on every shard.
    env_alpha_min: Array | None = None        # (nE, M) fp32
    env_sqrt_gamma_max: Array | None = None   # (nE, M) fp32
    data_scale: Array | None = None   # (n,) data row affine scale (int8 tier)
    data_zp: Array | None = None      # (n,) data row affine zero-point
    alpha_scale: Array | None = None  # (n,) filter-stat decode, round-nearest
    alpha_zp: Array | None = None
    sg_scale: Array | None = None
    sg_zp: Array | None = None
    amin_scale: Array | None = None   # (n,) corner decode, floor-rounded
    amin_zp: Array | None = None
    gmax_scale: Array | None = None   # (n,) corner decode, ceil-rounded
    gmax_zp: Array | None = None
    # Host-only recall calibration (core/calibrate.py RecallCalibration) —
    # deliberately NOT part of the pytree flatten: traced code never reads
    # it (a target_recall inverts the curve on the HOST before any launch),
    # and keeping it out of the statics/leaves means attaching or swapping
    # a curve can never fragment a jit cache.  It rides along through every
    # dataclasses.replace-based index op (pad / slice / concat / shard /
    # tombstone / quantize / envelope refresh) and comes back None from
    # tree_unflatten — i.e. it does not survive a raw jax.tree.map
    # round-trip, which only traced internals perform.
    calibration: object | None = None

    # Fields deliberately excluded from BOTH flatten sides: host-only
    # payload that does not survive a jax.tree.map round-trip (the
    # brelint pytree-contract pass requires every dataclass field to be
    # dynamic, static aux, or listed here — docs/static_analysis.md).
    HOST_ONLY_FIELDS = ("calibration",)

    @property
    def family(self) -> BregmanFamily:
        return get_family(self.family_name)

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def d(self) -> int:
        return self.data.shape[1]

    @property
    def m(self) -> int:
        return self.partition.num_subspaces

    def rows_view(self) -> Array:
        """(n, d) fp32 point rows — THE point set this index searches.

        In the int8 tier this dequantizes the whole table; use it for
        oracles, cost-model fits, and rebuilds, never on the per-query
        path (refinement dequantizes only the candidate rows).
        """
        if self.storage == "f32":
            return self.data
        return qz.dequantize_rows(self.data, self.data_scale, self.data_zp,
                                  self.family)

    def tree_flatten(self):
        dyn = (self.data, self.point_ids, self.alpha, self.sqrt_gamma,
               self.assign, self.alpha_min, self.sqrt_gamma_max, self.counts,
               self.centers, self.beta_samples, self.alpha_min_pt,
               self.sqrt_gamma_max_pt, self.gamma_edges,
               self.env_alpha_min, self.env_sqrt_gamma_max,
               self.data_scale, self.data_zp, self.alpha_scale, self.alpha_zp,
               self.sg_scale, self.sg_zp, self.amin_scale, self.amin_zp,
               self.gmax_scale, self.gmax_zp)
        static = (self.family_name, self.partition, self.num_clusters,
                  self.storage)
        return dyn, static

    @classmethod
    def tree_unflatten(cls, static, dyn):
        return cls(static[0], static[1], static[2], *dyn[:13],
                   storage=static[3],
                   env_alpha_min=dyn[13], env_sqrt_gamma_max=dyn[14],
                   data_scale=dyn[15], data_zp=dyn[16],
                   alpha_scale=dyn[17], alpha_zp=dyn[18],
                   sg_scale=dyn[19], sg_zp=dyn[20],
                   amin_scale=dyn[21], amin_zp=dyn[22],
                   gmax_scale=dyn[23], gmax_zp=dyn[24])


jax.tree_util.register_pytree_node(
    BallForest, BallForest.tree_flatten, BallForest.tree_unflatten
)


# Row-group size of the precomputed corner envelopes: one envelope row
# summarizes this many layout rows.  A streaming-scan block of B rows
# covers at most ceil(B / ENV_BLOCK_ROWS) + 1 envelope rows at any
# alignment, which is how the per-block skip test stays cheap for every
# ``block_rows`` setting (core/search.py).
ENV_BLOCK_ROWS = 256

# Point-major (n, ...) fields — the arrays a data-parallel shard slices.
# Everything else (per-cluster corners, centers, beta samples, block
# envelopes) is small and replicated on every shard.  The int8 storage tier
# adds the per-row decode fields; every consumer that walks point-major
# arrays must go through point_fields(forest), not the bare f32 tuple.
# The envelope tables are NOT point-major (their leading axis counts
# ENV_BLOCK_ROWS-row groups, not rows), so pad/slice/concat/tombstone
# maintain them explicitly rather than through the point_fields walk.
POINT_FIELDS = ("data", "point_ids", "alpha", "sqrt_gamma", "assign",
                "alpha_min_pt", "sqrt_gamma_max_pt")
ENV_FIELDS = ("env_alpha_min", "env_sqrt_gamma_max")
QUANT_FIELDS = ("data_scale", "data_zp", "alpha_scale", "alpha_zp",
                "sg_scale", "sg_zp", "amin_scale", "amin_zp",
                "gmax_scale", "gmax_zp")
REPLICATED_FIELDS = ("alpha_min", "sqrt_gamma_max", "counts", "centers",
                     "beta_samples", "gamma_edges") + ENV_FIELDS


def point_fields(index_or_storage) -> tuple:
    """The point-major field names of an index (storage-variant aware)."""
    storage = getattr(index_or_storage, "storage", index_or_storage)
    return POINT_FIELDS + QUANT_FIELDS if storage == "int8" else POINT_FIELDS


# Residency tiers (core/tiered.py).  The COLD point-major fields are the
# ones only the post-filter stages touch — the (n, d) rows the refine
# kernel reads and the (n, M) per-point corners the Theorem-3 prune reads
# — exactly the tables the hoisted envelope gate can veto a block of
# before any fetch.  Everything else is HOT: the filter phase streams
# alpha/sqrt_gamma for every row of every query, point_ids resolves the
# final top-k, and the replicated/envelope tables are O(n/256) small.
COLD_POINT_FIELDS = ("data", "alpha_min_pt", "sqrt_gamma_max_pt")
COLD_QUANT_FIELDS = ("data_scale", "data_zp", "amin_scale", "amin_zp",
                     "gmax_scale", "gmax_zp")


def cold_point_fields(index_or_storage) -> tuple:
    """Field names eligible for the host-RAM cold tier (storage-aware)."""
    storage = getattr(index_or_storage, "storage", index_or_storage)
    if storage == "int8":
        return COLD_POINT_FIELDS + COLD_QUANT_FIELDS
    return COLD_POINT_FIELDS


# Corner sentinel for padded rows: an alpha_min_pt of +PAD_CORNER makes the
# tuple-space lower bound exceed any finite search bound, so a padded row
# can never enter a Theorem-3 candidate set; the same value in alpha keeps
# it out of every filter top-k.
PAD_CORNER = 1e30

# The search-inert row: PAD_CORNER corners/filter stats (never admitted,
# never in a top-k), point_ids -1, data rows of ones (inside every family's
# domain, so inert rows are numerically harmless even if a kernel touches
# them).  Shared by padding (pad_points) and tombstoning (tombstone_rows):
# a deleted point IS a pad row that happens to sit mid-array.
INERT_FILL = {"data": 1.0, "point_ids": -1, "alpha": PAD_CORNER,
              "sqrt_gamma": 0.0, "assign": 0, "alpha_min_pt": PAD_CORNER,
              "sqrt_gamma_max_pt": 0.0}

# Int8-tier inert row: all codes zero; the sentinels move into the per-row
# decode fields (zero scales so an inert row adds no bound slack, PAD_CORNER
# zero-points where the f32 fill is PAD_CORNER, data_zp 1.0 so the
# dequantized row is the same domain-safe ones-row as the f32 fill).
INERT_FILL_INT8 = {
    "data": 0, "point_ids": -1, "alpha": 0, "sqrt_gamma": 0, "assign": 0,
    "alpha_min_pt": 0, "sqrt_gamma_max_pt": 0,
    "data_scale": 0.0, "data_zp": 1.0,
    "alpha_scale": 0.0, "alpha_zp": PAD_CORNER,
    "sg_scale": 0.0, "sg_zp": 0.0,
    "amin_scale": 0.0, "amin_zp": PAD_CORNER,
    "gmax_scale": 0.0, "gmax_zp": 0.0,
}


def inert_fill(index_or_storage) -> dict:
    """Per-field inert fill values for an index's storage tier."""
    storage = getattr(index_or_storage, "storage", index_or_storage)
    return INERT_FILL_INT8 if storage == "int8" else INERT_FILL


def corner_envelopes(amin_pt: Array, gmax_pt: Array) -> tuple[Array, Array]:
    """Block envelopes of (n, M) fp32 corner tables -> ((nE, M), (nE, M)).

    Row e is the componentwise min/max over layout rows
    ``[e*ENV_BLOCK_ROWS, (e+1)*ENV_BLOCK_ROWS)``; a short tail group is
    completed with the inert corner (``alpha_min`` PAD_CORNER,
    ``sqrt_gamma_max`` 0), which contributes nothing to either reduction —
    the same reason padded/tombstoned rows never loosen an envelope.
    """
    n, m = amin_pt.shape
    ne = max(-(-n // ENV_BLOCK_ROWS), 1)
    pad = ne * ENV_BLOCK_ROWS - n
    a = jnp.pad(amin_pt, ((0, pad), (0, 0)), constant_values=PAD_CORNER)
    g = jnp.pad(gmax_pt, ((0, pad), (0, 0)), constant_values=0.0)
    return (jnp.min(a.reshape(ne, ENV_BLOCK_ROWS, m), axis=1),
            jnp.max(g.reshape(ne, ENV_BLOCK_ROWS, m), axis=1))


def refresh_envelopes(forest: BallForest) -> BallForest:
    """Recompute the block-envelope tables from the per-point corners.

    In the int8 tier the reduction runs over the DECODED (directed-rounded,
    conservative) corners, so the envelope of a block always dominates the
    values the per-point Theorem-3 test will decode for its rows — the
    invariant that makes envelope-level block skipping loss-free.
    """
    amin, gmax = qz.decoded_corner_tables(forest)
    ea, eg = corner_envelopes(amin, gmax)
    return dataclasses.replace(forest, env_alpha_min=ea,
                               env_sqrt_gamma_max=eg)


def _pad_envelopes(forest: BallForest, padded_n: int) -> dict:
    """ENV_FIELDS updates covering ``padded_n`` rows with inert tail rows."""
    if forest.env_alpha_min is None:
        return {}
    ne_new = max(-(-padded_n // ENV_BLOCK_ROWS), 1)
    grow = ne_new - forest.env_alpha_min.shape[0]
    if grow <= 0:
        return {}
    m = forest.env_alpha_min.shape[1]
    # The boundary group's existing envelope stays valid: the appended rows
    # are inert (PAD_CORNER corners) and move neither reduction.
    return {
        "env_alpha_min": jnp.concatenate(
            [forest.env_alpha_min,
             jnp.full((grow, m), PAD_CORNER, jnp.float32)]),
        "env_sqrt_gamma_max": jnp.concatenate(
            [forest.env_sqrt_gamma_max, jnp.zeros((grow, m), jnp.float32)]),
    }


def pad_points(forest: BallForest, multiple: int) -> BallForest:
    """Pad the point-major arrays with inert rows so ``n % multiple == 0``."""
    pad = (-forest.n) % multiple
    if pad == 0:
        return forest
    fill = inert_fill(forest)

    def pad_rows(a, v):
        return jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], v, a.dtype)], axis=0)

    return dataclasses.replace(forest, **{
        f: pad_rows(getattr(forest, f), fill[f])
        for f in point_fields(forest)},
        **_pad_envelopes(forest, forest.n + pad))


def tombstone_rows(forest: BallForest, dead: Array) -> BallForest:
    """Overwrite the rows where ``dead`` is True with the inert fill.

    This is how the mutable index (core/segments.py) deletes: the row stays
    physically present (static shapes, no recompile) but its filter stats
    put it beyond any finite top-k and its corner stats fail every
    Theorem-3 admission, so the filter, prune, and refine phases of all
    three search paths skip it without knowing deletions exist.

    The block-envelope tables are left untouched: removing a row can only
    TIGHTEN a block's true envelope, so the stored one stays a valid
    (merely looser) dominator and block skipping stays loss-free.
    Compaction recomputes them exactly.
    """
    dead = jnp.asarray(dead, bool)
    fill = inert_fill(forest)

    def patch(a, v):
        d = dead.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(d, jnp.asarray(v, a.dtype), a)

    return dataclasses.replace(forest, **{
        f: patch(getattr(forest, f), fill[f]) for f in point_fields(forest)})


def concat_points(forests) -> BallForest:
    """Concatenate point-major arrays of segments sharing one sealed layout.

    All inputs must agree on the static fields and share the first
    segment's replicated (per-cluster / sample) arrays — exactly the shape
    of a SegmentedForest's main + append segments.  The result is a plain
    searchable :class:`BallForest` view.
    """
    forests = list(forests)
    head = forests[0]
    for f in forests[1:]:
        if (f.family_name != head.family_name
                or f.partition != head.partition
                or f.num_clusters != head.num_clusters
                or f.storage != head.storage):
            raise ValueError("concat_points needs segments of one index")
    if len(forests) == 1:
        return head
    out = dataclasses.replace(head, **{
        f: jnp.concatenate([getattr(seg, f) for seg in forests], axis=0)
        for f in point_fields(head)})
    # Segment boundaries rarely align with ENV_BLOCK_ROWS, so the result's
    # envelope groups straddle segments; recompute from the concatenated
    # per-point corners instead of stitching per-segment tables (O(n * M),
    # paid once per snapshot — view() caches the result).
    if head.env_alpha_min is not None:
        out = refresh_envelopes(out)
    return out


def slice_points(forest: BallForest, start: int, size: int) -> BallForest:
    """The ``[start, start+size)`` point-shard view of a forest.

    This is the host-side mirror of what one device sees under the
    ``shard_map`` in dist/knn.py: point-major arrays sliced, per-cluster /
    sample arrays shared.  (The real sharded path keeps the GLOBAL envelope
    tables replicated and indexes them by shard offset; this standalone
    view re-derives envelopes for its own row range so it is a complete
    self-consistent index.)
    """
    out = dataclasses.replace(forest, **{
        f: jax.lax.slice_in_dim(getattr(forest, f), start, start + size,
                                axis=0)
        for f in point_fields(forest)})
    if forest.env_alpha_min is not None:
        out = refresh_envelopes(out)
    return out


def default_num_clusters(n: int) -> int:
    return int(np.clip(n // 32, 8, 8192))


def quantize_point_tables(forest: BallForest, data_codes: Array,
                          data_scale: Array, data_zp: Array) -> BallForest:
    """Swap a built fp32 forest's point-major tables for the int8 tier.

    ``data_codes``/``data_scale``/``data_zp`` must dequantize EXACTLY to
    ``forest.data`` (the forest was built over the dequantized rows, so the
    stats/corners being re-encoded here were computed from the point set
    the codes decode to).  Filter stats round to nearest (covered by the
    `_qb_slack` bound inflation at query time); corner stats round
    directionally so the Theorem-3 test stays conservative with no
    query-time correction.
    """
    if forest.storage != "f32":
        raise ValueError("quantize_point_tables wants an f32 forest")
    out = dataclasses.replace(
        forest, storage="int8",
        data=data_codes, data_scale=data_scale, data_zp=data_zp,
        **qz.encode_stat_tables(forest.alpha, forest.sqrt_gamma,
                                forest.alpha_min_pt,
                                forest.sqrt_gamma_max_pt))
    # The corner re-encode just moved every per-point corner by up to one
    # directed-rounding step, so any envelopes carried in from the fp32
    # forest no longer dominate the DECODED corners — refit them here so
    # the invariant holds for every caller, not just build_index.
    return refresh_envelopes(out)


def build_index(
    data,
    family: str | BregmanFamily,
    *,
    m: int | None = None,
    pccp: bool = True,
    num_clusters: int | None = None,
    kmeans_iters: int = 12,
    beta_sample_size: int = 4096,
    gamma_buckets: int = 4,
    quantize: bool = False,
    calibrate: bool = False,
    calibrate_k: int = 10,
    calibration_queries: int = 64,
    seed: int = 0,
) -> BallForest:
    """Offline precomputation (paper Alg. 5): partition -> transform -> forest.

    ``m=None`` fits the Theorem-4 cost model and uses M*.

    ``gamma_buckets`` (beyond-paper tightening): within each ball, members
    are split into gamma-quantile buckets and each bucket contributes its
    own (alpha_min, sqrt_gamma_max) corner, so the cluster lower bound
    LB = alpha_min + qconst - sqrt_gamma_max*sqrt_delta is evaluated on
    buckets whose gamma spread is ~1/gamma_buckets of the ball's — strictly
    tighter, still conservative (each point belongs to exactly one bucket
    and its bucket's corner lower-bounds its distance).

    ``calibrate=True`` additionally fits the per-index recall-calibration
    curve (core/calibrate.py): measured recall@``calibrate_k`` over a
    ``p_guarantee`` grid on ``calibration_queries`` held-out jittered
    rows, stored host-side on :attr:`BallForest.calibration` so
    ``target_recall`` requests can invert it (docs/accuracy.md).

    ``quantize=True`` builds the int8 storage tier: ``data`` is snapped to
    per-row int8 FIRST and the whole index (clustering, transforms,
    corners, beta samples) is built over the dequantized rows, so every
    stored stat describes exactly the point set search will refine against
    (docs/quantization.md).  Search over the result is exact w.r.t. those
    dequantized points — identical ids/distances to an fp32 index built
    over ``rows_view()``.
    """
    t0 = time.perf_counter()
    fam = get_family(family) if isinstance(family, str) else family
    data = jnp.asarray(data, dtype=jnp.float32)
    if quantize:
        data_codes, data_scale, data_zp = qz.quantize_rows(data)
        data = qz.dequantize_rows(data_codes, data_scale, data_zp, fam)
    n, d = data.shape
    data_np = np.asarray(data)

    t_load = time.perf_counter()
    if m is None:
        m = fit_cost_model(data_np, fam, seed=seed).m_star()
    m = int(np.clip(m, 1, d))
    t_cost = time.perf_counter()

    if pccp and m < d:
        part = build_pccp_partition(data_np, m, seed=seed)
    else:
        part = make_partition(d, m)
    t_part = time.perf_counter()

    c = num_clusters or default_num_clusters(n)
    c = int(min(c, n))
    key = jax.random.PRNGKey(seed)

    # Per-subspace Bregman k-means over the (n, w) subspace views.  The jit
    # cache is shared across subspaces (same shapes / family).
    sub_views = part.gather(data)                   # (n, M, w)
    mask = part.subspace_mask()                     # (M, w)
    centers_list, assign_list = [], []
    for i in range(m):
        ki = jax.random.fold_in(key, i)
        cen, asg = kmeans(
            sub_views[:, i, :], mask[i], ki,
            family=fam, num_clusters=c, iters=kmeans_iters,
        )
        # Number the clusters by their center's alpha, so the layout sort
        # below puts clusters of like magnitude in adjacent rows.  With
        # k-means' arbitrary labels every ENV_BLOCK_ROWS group mixed far
        # apart clusters and no block envelope could skip anything.
        perm = jnp.argsort(jnp.sum(fam.phi(cen) * mask[i], axis=-1))
        cen, asg = cen[perm], jnp.argsort(perm).astype(jnp.int32)[asg]
        centers_list.append(cen)
        assign_list.append(asg)
    centers = jnp.stack(centers_list)               # (M, C, w)
    assign = jnp.stack(assign_list, axis=1)         # (n, M)
    jax.block_until_ready(assign)
    t_kmeans = time.perf_counter()

    # Shared layout: order points by the reference subspace's cluster id.
    order = jnp.argsort(assign[:, 0], stable=True)
    data_l = data[order]
    assign_l = assign[order]
    point_ids = order.astype(jnp.int32)

    p = p_transform(data_l, part, fam)
    alpha, sqrt_gamma = p["alpha"], p["sqrt_gamma"]

    # gamma-bucketed corners: effective segment id = ball * nb + bucket,
    # bucket = global per-subspace gamma quantile of the member
    nb = max(int(gamma_buckets), 1)
    assign_eff, edges = [], []
    for i in range(m):
        qs = jnp.quantile(sqrt_gamma[:, i],
                          jnp.linspace(0.0, 1.0, nb + 1)[1:-1])
        bucket = jnp.searchsorted(qs, sqrt_gamma[:, i]).astype(jnp.int32)
        assign_eff.append(assign_l[:, i] * nb + bucket)
        edges.append(qs)
    assign_eff = jnp.stack(assign_eff, axis=1)      # (n, M) in [0, C*nb)
    gamma_edges = jnp.stack(edges)                  # (M, nb-1) bucket edges
    c_eff = c * nb

    amin = jnp.stack([
        cluster_stats(alpha[:, i], assign_eff[:, i], c_eff)["min"]
        for i in range(m)
    ])                                              # (M, C*nb)
    gmax = jnp.stack([
        cluster_stats(sqrt_gamma[:, i], assign_eff[:, i], c_eff)["max"]
        for i in range(m)
    ])
    counts = jnp.stack([
        cluster_stats(alpha[:, i], assign_eff[:, i], c_eff)["count"]
        for i in range(m)
    ])

    # Per-point view of the bucketed corners: alpha_min_pt[p, i] is the
    # corner of the bucket point p lives in for subspace i.  Gathering this
    # ONCE at build time makes the batched query-time cluster pruning
    # (core/search.py knn_search_batch) a pure elementwise compare — no
    # query-time gathers over (n, M, q).
    amin_pt = jax.vmap(lambda a, s: a[s], in_axes=(0, 1), out_axes=1)(
        amin, assign_eff)                           # (n, M)
    gmax_pt = jax.vmap(lambda a, s: a[s], in_axes=(0, 1), out_axes=1)(
        gmax, assign_eff)                           # (n, M)

    # Empirical beta_xy sample for the approximate search (Prop. 1): the CDF
    # of the cross term over random (data, query) pairs.
    rng = np.random.default_rng(seed)
    s = min(beta_sample_size, n * n)
    xi = rng.integers(0, n, size=s)
    yi = rng.integers(0, n, size=s)
    grads = fam.phi_prime(data_np[yi])
    betas = -np.sum(data_np[xi] * grads, axis=-1)
    beta_samples = jnp.sort(jnp.asarray(betas, dtype=jnp.float32))

    forest = BallForest(
        family_name=fam.name,
        partition=part,
        num_clusters=c_eff,
        data=data_l,
        point_ids=point_ids,
        alpha=alpha,
        sqrt_gamma=sqrt_gamma,
        assign=assign_eff,
        alpha_min=amin,
        sqrt_gamma_max=gmax,
        counts=counts,
        centers=centers,
        beta_samples=beta_samples,
        alpha_min_pt=amin_pt,
        sqrt_gamma_max_pt=gmax_pt,
        gamma_edges=gamma_edges,
    )
    if quantize:
        forest = quantize_point_tables(
            forest, data_codes[order], data_scale[order], data_zp[order])
    # Envelopes come LAST so the int8 tier reduces over the decoded
    # directed-rounded corners it will serve, not the pre-encode fp32 ones
    # (whose floor-rounding could otherwise dip below the envelope).
    forest = refresh_envelopes(forest)
    jax.block_until_ready(forest.env_alpha_min)
    # Phase wall times (host cost model, host PCCP, device k-means) for
    # bring-up and capacity planning; read via the log record's extra.
    seconds = {"cost_model": t_cost - t_load, "pccp": t_part - t_cost,
               "kmeans": t_kmeans - t_part,
               "total": time.perf_counter() - t0}
    logger.info("build_index n=%d M=%d C=%d: %s", n, m, c, seconds,
                extra={"build_seconds": seconds})
    if calibrate:
        # Fit over the finished index (lazy import: calibrate drives the
        # search entry points, which import this module).
        from . import calibrate as _calibrate
        forest = dataclasses.replace(
            forest,
            calibration=_calibrate.fit_calibration(
                forest, k=min(calibrate_k, n),
                num_queries=calibration_queries, seed=seed))
    return forest
