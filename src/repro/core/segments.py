"""Mutable segmented BallForest: streaming insert/delete without rebuild.

The paper's partition-filter-refinement index (§5-§7) is built once
offline; serving workloads (streaming ingestion into the kNN-LM datastore,
per-user corpora) need to add and retire points without the full
O(n * d * M) rebuild.  The classic LSM answer, adapted to the BB-forest:

* **Sealed main segment** — a :class:`~repro.core.index.BallForest` built
  by ``build_index`` exactly as today.  Its partition, Bregman-k-means
  centroids, gamma-bucket edges and beta samples are FROZEN: they define
  the coordinate system every later mutation reuses.
* **Append segments** — each :meth:`SegmentedForest.insert` call seals its
  points into a small BallForest that shares the main segment's statics
  and replicated tables.  New points do NOT re-run PCCP or the Theorem-4
  cost model: they are P-transformed with the sealed partition
  (``transform.p_transform_views``), assigned to the nearest EXISTING
  centroid per subspace, gamma-bucketed with the sealed quantile edges,
  and given *singleton* per-point corners (``alpha_min_pt = alpha``,
  ``sqrt_gamma_max_pt = sqrt_gamma``).  A singleton corner is the point's
  own Cauchy lower bound, so the Theorem-3 admission test stays exact for
  appended points (it is in fact tighter than a shared cluster corner).
* **Tombstones** — :meth:`SegmentedForest.delete` overwrites a point's row
  with the search-inert fill (``index.tombstone_rows``): filter stats
  beyond any finite top-k, corner stats that fail every admission, id -1.
  The filter, Theorem-3 prune, and refine phases of both search paths
  (``knn_search_batch`` and ``dist.distributed_knn``) skip deleted rows
  without knowing deletions exist.
* **Compaction** — :meth:`SegmentedForest.compact` re-seals everything
  into one main segment, either by a cheap **merge** (drop dead rows,
  re-sort the shared layout, recompute corner tables with
  ``clustering.cluster_stats`` — no k-means) or a full **rebuild**
  (``build_index`` over the live points, original ids preserved).  The
  choice is driven by the fitted Theorem-4 :class:`CostModel`
  (``partition.decide_compaction``); inserts auto-compact when the stale
  fraction crosses :attr:`SegmentedForest.compact_threshold`.

Searches never look at this class's bookkeeping: :meth:`view` snapshots
the segments into ONE plain BallForest (``index.concat_points``) and every
entry point in ``core/search.py`` / ``dist/knn.py`` accepts either type.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .bregman import BregmanFamily, validate_rows
from .clustering import cluster_stats, pairwise_bregman
from .index import (
    BallForest,
    build_index,
    concat_points,
    point_fields,
    refresh_envelopes,
    tombstone_rows,
)
from .partition import CostModel, decide_compaction, fit_cost_model
from .transform import p_transform_views
from . import quantize as qz

Array = jax.Array

# Stale fraction (appended + deleted over live) above which insert/delete
# auto-compact.  0.5 ~ "append segments cost as much as the main scan".
DEFAULT_COMPACT_THRESHOLD = 0.5


def _append_segment(main: BallForest, points: Array,
                    first_id: int) -> BallForest:
    """Seal ``points`` into a searchable append segment of ``main``'s index.

    Reuses the sealed partition / transforms / centroids / bucket edges;
    recomputes only the per-point P-tuples, the nearest-centroid
    assignment, and the (singleton) per-point corner stats.

    In the int8 storage tier the new points are snapped to int8 FIRST
    (fresh per-row affines — the sealed part of the quantizer is the
    scheme, the scales travel with each row) and the transforms run over
    the decoded rows, so the appended segment obeys the same contract as
    the main one: its stats describe exactly the rows refine will decode.
    """
    part, fam = main.partition, main.family
    pts = jnp.asarray(points, jnp.float32)
    if pts.ndim != 2 or pts.shape[1] != main.d:
        raise ValueError(f"expected (a, {main.d}) points, got {pts.shape}")
    if main.storage == "int8":
        codes, d_scale, d_zp = qz.quantize_rows(pts)
        pts = qz.dequantize_rows(codes, d_scale, d_zp, fam)
    sub = part.gather(pts)                          # (a, M, w)
    mask = part.subspace_mask()
    p = p_transform_views(sub, mask, fam)
    alpha, sqrt_gamma = p["alpha"], p["sqrt_gamma"]

    # Nearest existing centroid per subspace, then the sealed gamma-bucket
    # edges, reproduce build_index's effective segment id for new points.
    num_centers = main.centers.shape[1]
    nb = main.num_clusters // num_centers
    assign_eff = []
    for i in range(part.num_subspaces):
        dist = pairwise_bregman(sub[:, i, :], main.centers[i], mask[i], fam)
        ball = jnp.argmin(dist, axis=-1).astype(jnp.int32)
        bucket = jnp.searchsorted(
            main.gamma_edges[i], sqrt_gamma[:, i]).astype(jnp.int32)
        assign_eff.append(ball * nb + bucket)
    assign_eff = jnp.stack(assign_eff, axis=1)      # (a, M)

    ids = jnp.arange(first_id, first_id + pts.shape[0], dtype=jnp.int32)
    # Singleton corners: the point's own lower-bound tuple.  Conservative
    # (lb = LB_i(x, y) <= D_i(x, y)) and tighter than any shared corner, so
    # appended points need no update to the sealed cluster tables.
    if main.storage == "int8":
        # Exact per-point stats are in hand, so the (singleton) corner
        # codes round directionally from the TRUE values — same
        # conservatism as build, one shared encode rule.
        seg = dataclasses.replace(
            main, data=codes, data_scale=d_scale, data_zp=d_zp,
            point_ids=ids, assign=assign_eff,
            **qz.encode_stat_tables(alpha, sqrt_gamma, alpha, sqrt_gamma))
    else:
        seg = dataclasses.replace(
            main, data=pts, point_ids=ids, alpha=alpha,
            sqrt_gamma=sqrt_gamma, assign=assign_eff, alpha_min_pt=alpha,
            sqrt_gamma_max_pt=sqrt_gamma)
    # The segment's block envelopes come from ITS (decoded singleton)
    # corners, not the main segment's — the snapshot concat recomputes the
    # global table, but a self-consistent per-segment table keeps every
    # BallForest independently searchable.
    return refresh_envelopes(seg)


@dataclasses.dataclass
class SegmentedForest:
    """A mutable BrePartition index: sealed main + append segments.

    Host-side bookkeeping (live masks, id lookup) lives in numpy; all
    searchable state lives in the segments' device arrays, so
    :meth:`view` is a concat — no host->device transfer per query.
    """

    main: BallForest
    segments: list[BallForest]
    live: list[np.ndarray]          # bool mask per block (0 = main)
    ids_host: list[np.ndarray]      # point_ids per block (-1 = dead/pad)
    next_id: int
    cost_model: CostModel | None = None
    compact_threshold: float = DEFAULT_COMPACT_THRESHOLD
    _view: BallForest | None = dataclasses.field(
        default=None, init=False, repr=False)

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_forest(cls, forest: BallForest, *,
                    cost_model: CostModel | None = None,
                    compact_threshold: float = DEFAULT_COMPACT_THRESHOLD,
                    ) -> "SegmentedForest":
        ids = np.asarray(forest.point_ids)
        return cls(main=forest, segments=[], live=[ids >= 0],
                   ids_host=[ids.copy()],
                   next_id=int(ids.max(initial=-1)) + 1,
                   cost_model=cost_model,
                   compact_threshold=compact_threshold)

    # -- snapshot & stats ---------------------------------------------------

    def view(self) -> BallForest:
        """One searchable BallForest over every segment (cached)."""
        if self._view is None:
            self._view = concat_points([self.main] + self.segments)
        return self._view

    @property
    def family(self) -> BregmanFamily:
        return self.main.family

    @property
    def family_name(self) -> str:
        return self.main.family_name

    @property
    def partition(self):
        return self.main.partition

    @property
    def num_clusters(self) -> int:
        return self.main.num_clusters

    @property
    def storage(self) -> str:
        return self.main.storage

    @property
    def calibration(self):
        """The recall-calibration curve (core/calibrate.py), if fitted.

        Lives on the sealed main segment; inserts and tombstones leave it
        in place (stale-but-measured, like the block envelopes staying
        conservatively loose), :meth:`compact` refits it.
        """
        return self.main.calibration

    @property
    def n(self) -> int:
        """Physical rows (tombstones included) — the searched array length."""
        return self.main.n + sum(s.n for s in self.segments)

    @property
    def d(self) -> int:
        return self.main.d

    @property
    def m(self) -> int:
        return self.main.m

    @property
    def live_n(self) -> int:
        return int(sum(int(mask.sum()) for mask in self.live))

    @property
    def appended_live(self) -> int:
        return int(sum(int(mask.sum()) for mask in self.live[1:]))

    @property
    def deleted_n(self) -> int:
        return self.n - self.live_n

    @property
    def append_fraction(self) -> float:
        return self.appended_live / max(self.live_n, 1)

    @property
    def stale_fraction(self) -> float:
        """Appended + deleted over live — the compaction pressure metric."""
        return (self.appended_live + self.deleted_n) / max(self.live_n, 1)

    def live_ids(self) -> np.ndarray:
        """Original ids of the live points, in layout order."""
        return np.concatenate(
            [ids[mask] for ids, mask in zip(self.ids_host, self.live, strict=True)])

    def append_row_range(self) -> tuple[int, int]:
        """[start, stop) rows of ``view()`` held by the append segments.

        ``view()`` concatenates main first, then segments in order, so
        the append rows are exactly the tail.  The tiered store pins
        this range device-resident (core/tiered.py): append segments are
        the hot, recently-written working set, and compaction folds them
        into the sealed main — the only tier that goes cold.
        """
        return self.main.n, self.n

    # -- mutations ----------------------------------------------------------

    def insert(self, points, *, auto_compact: bool = True,
               validate: bool = False) -> np.ndarray:
        """Append ``points`` as a new searchable segment; returns their ids.

        O(a * d * C) — one nearest-centroid pass against the sealed
        centroids — versus O(n * d * C * iters) for a rebuild.  Note the
        snapshot's row count changes, so the next search compiles a new
        program; batch inserts (and the auto-compact threshold) keep that
        churn bounded.

        ``validate=True`` runs the domain gate
        (:func:`~repro.core.bregman.validate_rows`) and raises — naming
        the offending row — BEFORE anything is sealed, so a poisoned
        ingest batch can never contaminate the searchable tables.
        Ingestion paths that prefer quarantine-over-reject insert without
        validation and call :meth:`quarantine` afterwards (or let the
        serving layer do it — serve/retrieval.py).
        """
        if validate:
            validate_rows(self.family, points, what="insert row")
        seg = _append_segment(self.main, points, self.next_id)
        self.segments.append(seg)
        self.live.append(np.ones(seg.n, dtype=bool))
        self.ids_host.append(np.asarray(seg.point_ids).copy())
        self.next_id += seg.n
        self._view = None
        out = np.asarray(seg.point_ids)
        if auto_compact and self.stale_fraction > self.compact_threshold:
            self.compact()
        return out

    def delete(self, ids, *, auto_compact: bool = True) -> int:
        """Tombstone the given original ids; returns how many were live.

        Unknown or already-deleted ids are ignored.  Rows stay physically
        present (static shapes — no recompile) but become search-inert in
        every phase of every path; compaction reclaims them.
        """
        ids = np.unique(np.asarray(ids, np.int64))
        removed = 0
        blocks = [self.main] + self.segments
        for b, block in enumerate(blocks):
            dead = np.isin(self.ids_host[b], ids) & self.live[b]
            if not dead.any():
                continue
            removed += int(dead.sum())
            self.live[b] = self.live[b] & ~dead
            self.ids_host[b][dead] = -1
            patched = tombstone_rows(block, jnp.asarray(dead))
            if b == 0:
                self.main = patched
            else:
                self.segments[b - 1] = patched
        if removed:
            self._view = None
            if auto_compact and self.stale_fraction > self.compact_threshold:
                self.compact()
        return removed

    def find_invalid(self) -> np.ndarray:
        """Original ids of LIVE rows that violate the family domain.

        Scans what refine actually computes distances over —
        ``rows_view()``, i.e. the decoded rows in the int8 tier — for
        NaN/inf or open-domain violations (``bregman.validate_rows``).  A
        poisoned row makes every query that admits it return NaN
        distances, so this is the index-side admission check the serving
        layer runs before trusting a tenant's index (and after any
        unvalidated bulk load).
        """
        blocks = [self.main] + self.segments
        bad: list[np.ndarray] = []
        for b, mask in zip(blocks, self.live, strict=True):
            if not mask.any():
                continue
            rows = np.asarray(b.rows_view())
            ok = validate_rows(self.family, rows, mode="mask")
            bad_rows = mask & ~ok
            if bad_rows.any():
                bad.append(np.asarray(b.point_ids)[bad_rows])
        if not bad:
            return np.empty((0,), np.int32)
        return np.concatenate(bad).astype(np.int32)

    def quarantine(self) -> np.ndarray:
        """Tombstone every domain-violating live row; returns their ids.

        The poisoned-index containment path: rows found by
        :meth:`find_invalid` become search-inert tombstones (exactly like
        :meth:`delete`, but auto-compaction is suppressed so the caller
        controls when the reclaim pause happens).  Searches over the
        remaining live set are exact again; the returned ids let the
        owner audit or re-ingest corrected rows.
        """
        bad = self.find_invalid()
        if bad.size:
            self.delete(bad, auto_compact=False)
        return bad

    # -- compaction ---------------------------------------------------------

    def fitted_cost_model(self) -> CostModel:
        """The Theorem-4 model for merge-vs-rebuild (fit lazily, cached)."""
        if self.cost_model is None:
            self.cost_model = fit_cost_model(self._live_rows(), self.family)
        return self.cost_model

    def decide(self) -> str:
        """``"merge"`` or ``"rebuild"`` per the CostModel rule."""
        return decide_compaction(self.fitted_cost_model(), self.m,
                                 stale_fraction=self.stale_fraction)

    def compact(self, mode: str | None = None, *, seed: int = 0) -> str:
        """Re-seal every segment (and reclaim tombstones) into the main.

        ``mode`` forces ``"merge"`` or ``"rebuild"``; ``None`` asks
        :meth:`decide`.  Either way original ids are preserved, so stored
        side tables (e.g. the kNN-LM token values) stay valid.

        A fitted recall calibration is REFIT over the compacted index with
        its stored fit parameters (both modes: a merge changes the layout
        and drops rows, a rebuild re-clusters — either moves the measured
        curve), so ``target_recall`` contracts stay anchored to what the
        live index actually serves.
        """
        prev_cal = self.main.calibration
        if self.live_n == 0:
            # Nothing to model or re-cluster: an empty merge just drops the
            # dead rows (a rebuild would hand build_index a 0-row array).
            mode = "merge"
        elif mode is None:
            mode = self.decide()
        if mode not in ("merge", "rebuild"):
            raise ValueError(f"unknown compaction mode {mode!r}")
        if mode == "rebuild":
            self.main = self._rebuild(seed)
        else:
            self.main = self._merge()
        self.segments = []
        if prev_cal is not None:
            from . import calibrate as _calibrate
            cal = None
            if self.main.n and int(np.sum(
                    np.asarray(self.main.point_ids) >= 0)) >= prev_cal.k:
                cal = _calibrate.fit_calibration(
                    self.main, k=prev_cal.k,
                    num_queries=prev_cal.num_queries,
                    p_grid=prev_cal.p_grid, seed=prev_cal.seed,
                    jitter=prev_cal.jitter)
            # Too few live rows to measure recall@k: drop the curve rather
            # than serve a stale one over a different point set.
            self.main = dataclasses.replace(self.main, calibration=cal)
        ids = np.asarray(self.main.point_ids)
        self.live = [ids >= 0]
        self.ids_host = [ids.copy()]
        self._view = None
        # The model was fit on a previous cycle's live set; n/alpha/beta
        # drift with every grow/evict, so refit per compaction cycle.
        self.cost_model = None
        return mode

    def _live_arrays(self, fields=None) -> tuple[np.ndarray, ...]:
        """Host copies of the live rows of the given point-major fields."""
        if fields is None:
            fields = point_fields(self.main)
        blocks = [self.main] + self.segments
        out = []
        for f in fields:
            out.append(np.concatenate([
                np.asarray(getattr(b, f))[mask]
                for b, mask in zip(blocks, self.live, strict=True)]))
        return tuple(out)

    def _live_rows(self) -> np.ndarray:
        """Live fp32 point rows (decoded in the int8 tier), layout order."""
        blocks = [self.main] + self.segments
        return np.concatenate([
            np.asarray(b.rows_view())[mask]
            for b, mask in zip(blocks, self.live, strict=True)])

    def _rebuild(self, seed: int) -> BallForest:
        """Full Alg.-5 rebuild over the live points, original ids kept.

        In the int8 tier the rebuild re-quantizes the decoded rows with
        fresh per-row affines, so stored points may drift by at most one
        quantization step per coordinate (docs/quantization.md); a merge
        preserves them bit-exactly.
        """
        (ids,) = self._live_arrays(("point_ids",))
        data = self._live_rows()
        num_centers = self.main.centers.shape[1]
        nb = max(self.main.num_clusters // num_centers, 1)
        forest = build_index(
            data, self.family_name, m=self.m,
            num_clusters=min(num_centers, data.shape[0]),
            gamma_buckets=nb, quantize=self.storage == "int8", seed=seed)
        # build_index ids index into `data`; route them through the
        # original-id map so external references survive the rebuild.
        return dataclasses.replace(
            forest,
            point_ids=jnp.asarray(ids)[forest.point_ids])

    def _merge(self) -> BallForest:
        """Cheap re-seal: keep the sealed centroids/buckets, drop dead rows,
        restore the shared layout, recompute the corner tables exactly.

        Int8 tier: data/stat codes and their per-row decode move as
        opaque rows (the stored points are bit-identical after the merge);
        only the corner tables are refit — from a CONSERVATIVE decode of
        the stat codes (nearest-rounded, so the true stat may be half a
        step to either side) and then directed-rounded again, keeping the
        Theorem-3 test admissible across compactions.
        """
        fields = point_fields(self.main)
        arrays = dict(zip(fields, self._live_arrays(fields), strict=True))
        order = np.argsort(arrays["assign"][:, 0], kind="stable")
        arrays = {f: jnp.asarray(a[order]) for f, a in arrays.items()}

        if self.main.storage == "int8":
            # Worst-case true stats behind the nearest-rounded codes: alpha
            # may be up to scale/2 below its decode, sqrt_gamma up to
            # scale/2 above, so min/max over these envelopes bound the
            # min/max of the true values.
            alpha = qz.dequantize_stats(
                arrays["alpha"], arrays["alpha_scale"], arrays["alpha_zp"])
            sqrt_gamma = qz.dequantize_stats(
                arrays["sqrt_gamma"], arrays["sg_scale"], arrays["sg_zp"])
            alpha_lo = alpha - qz.UB_SLACK * arrays["alpha_scale"][:, None]
            sg_hi = sqrt_gamma + qz.UB_SLACK * arrays["sg_scale"][:, None]
        else:
            alpha = alpha_lo = arrays["alpha"]
            sqrt_gamma = sg_hi = arrays["sqrt_gamma"]
        assign = arrays["assign"]

        c_eff, m = self.num_clusters, self.m
        stats_a = [cluster_stats(alpha_lo[:, i], assign[:, i], c_eff)
                   for i in range(m)]
        stats_g = [cluster_stats(sg_hi[:, i], assign[:, i], c_eff)
                   for i in range(m)]
        amin = jnp.stack([s["min"] for s in stats_a])
        gmax = jnp.stack([s["max"] for s in stats_g])
        counts = jnp.stack([s["count"] for s in stats_a])
        take_pt = jax.vmap(lambda a, s: a[s], in_axes=(0, 1), out_axes=1)
        amin_pt, gmax_pt = take_pt(amin, assign), take_pt(gmax, assign)
        if self.main.storage == "int8":
            corners = qz.encode_corner_tables(amin_pt, gmax_pt)
            merged = dataclasses.replace(
                self.main,
                **{f: arrays[f] for f in fields if f not in corners},
                alpha_min=amin, sqrt_gamma_max=gmax, counts=counts,
                **corners)
        else:
            merged = dataclasses.replace(
                self.main, data=arrays["data"],
                point_ids=arrays["point_ids"],
                alpha=alpha, sqrt_gamma=sqrt_gamma, assign=assign,
                alpha_min=amin, sqrt_gamma_max=gmax, counts=counts,
                alpha_min_pt=amin_pt, sqrt_gamma_max_pt=gmax_pt)
        # Dead rows are gone and the layout re-sorted, so the block
        # envelopes are refit exactly (tombstoning itself only ever leaves
        # them conservatively loose — index.tombstone_rows).
        return refresh_envelopes(merged)


def build_segmented_index(data, family, **build_kwargs) -> SegmentedForest:
    """``build_index`` wrapped as the mutable index (Alg. 5 + segments)."""
    threshold = build_kwargs.pop("compact_threshold",
                                 DEFAULT_COMPACT_THRESHOLD)
    forest = build_index(data, family, **build_kwargs)
    return SegmentedForest.from_forest(forest, compact_threshold=threshold)
