"""Recall calibration: turn the §8 approximate knob into a measured SLO.

The approximate search mode (paper §8, ``core/search.py``) shrinks the
Alg.-4 filter bounds by a factor derived from the empirical beta_xy CDF at
a guarantee level ``p_guarantee``.  Prop. 1 ties ``p`` to the probability
that any single pruned point was a true neighbor — NOT to recall@k, which
is what callers actually care about and what depends on the data
distribution, the family, k, and the index layout.  Following Abdullah et
al. (arXiv 1108.0835 — trade accuracy for time, but *measure* the trade),
this module makes the mapping empirical:

* :func:`fit_calibration` sweeps a ``p`` grid over a held-out query sample
  (jittered live rows — in-distribution by construction, valid for every
  family domain), measures recall@k against the exact oracle
  (``_brute_force_live``), and monotone-regularizes the curve (recall is
  non-decreasing in ``p`` in expectation; isotonic projection removes
  sampling noise).  One compiled program serves the whole sweep: ``p`` is
  a traced scalar of the approx pipeline, never a static.
* :class:`RecallCalibration` stores the fitted curve as plain host-side
  numpy.  It lives on ``BallForest.calibration`` — a host-only field
  EXCLUDED from the pytree flatten, so it survives every
  ``dataclasses.replace``-based index operation (pad / slice / concat /
  shard / tombstone / quantize) without fragmenting any jit cache, and is
  simply absent inside traced code (inversion happens on the host before
  a launch, never inside one).
* :func:`resolve_p_guarantee` inverts the curve conservatively: the
  SMALLEST grid ``p`` whose measured recall meets the target, reported
  together with that measured recall as the ``expected_recall`` estimate.
  Uncalibrated indexes fall back to the historical behavior (``p`` =
  target, no estimate) with a one-time warning, so nothing breaks for
  indexes built before calibration existed.

Lifecycle: fitted at ``build_index(calibrate=True)`` /
``build_datastore(calibrate=True)`` time; inserts and tombstones leave the
curve in place (stale-but-conservative, same philosophy as the block
envelope tables); ``SegmentedForest.compact`` refits it with the stored
fit parameters for both merge and rebuild.  See docs/accuracy.md.
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

# Default guarantee grid: dense near the top where the recall curve is
# steepest (and where SLO targets live), sparse below.
DEFAULT_P_GRID = (0.0, 0.2, 0.4, 0.6, 0.7, 0.8, 0.9, 0.95, 0.99, 1.0)
DEFAULT_NUM_QUERIES = 64
DEFAULT_JITTER = 0.05

_warned_uncalibrated = False


@dataclasses.dataclass(frozen=True)
class RecallCalibration:
    """A fitted ``p_guarantee`` -> measured recall@k curve (host-side).

    ``p_grid`` is ascending and ends at 1.0 (the no-shrink point);
    ``recall_grid`` is the monotone-regularized measured recall@``k`` at
    each grid point.  ``num_queries`` / ``seed`` / ``jitter`` record the
    fit so compaction can refit with identical settings.
    """

    p_grid: np.ndarray          # (G,) ascending guarantee levels
    recall_grid: np.ndarray     # (G,) measured recall@k, non-decreasing
    k: int
    num_queries: int
    seed: int
    jitter: float = DEFAULT_JITTER

    def __post_init__(self):
        # Accept tuples/lists (hand-built curves in tests, literals in
        # docs) but store arrays so the lookups below stay uniform.
        object.__setattr__(self, "p_grid",
                           np.asarray(self.p_grid, np.float64))
        object.__setattr__(self, "recall_grid",
                           np.asarray(self.recall_grid, np.float64))

    def expected_recall(self, p: float) -> float:
        """Measured recall estimate at guarantee level ``p`` (interpolated)."""
        return float(np.interp(float(p), self.p_grid, self.recall_grid))

    def resolve(self, target_recall: float) -> tuple[float, float]:
        """Smallest grid ``p`` whose MEASURED recall meets the target.

        Returns ``(p_guarantee, expected_recall)``.  Conservative on both
        ends: an achievable target gets the cheapest grid point that met
        it during the fit (never an interpolated p between grid points,
        whose recall was not measured); a target above everything the fit
        achieved gets ``p = 1.0`` — the unshrunk §8 pipeline — and the
        honest (lower) measured estimate, so callers can see the SLO is
        not attainable rather than being promised it silently.
        """
        t = float(target_recall)
        if not 0.0 <= t <= 1.0:
            raise ValueError(f"target_recall must be in [0, 1], got {t}")
        idx = int(np.searchsorted(self.recall_grid, t, side="left"))
        if idx >= self.p_grid.shape[0]:
            return float(self.p_grid[-1]), float(self.recall_grid[-1])
        return float(self.p_grid[idx]), float(self.recall_grid[idx])


def _recall_at_k(ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean fraction of oracle ids recovered, set-wise per query row."""
    hits = 0
    for row, truth in zip(ids, true_ids, strict=True):
        hits += len(set(row.tolist()) & set(truth.tolist()))
    return hits / true_ids.size


def held_out_queries(index, num_queries: int, seed: int,
                     jitter: float = DEFAULT_JITTER) -> np.ndarray:
    """An in-distribution held-out query sample: jittered live rows.

    Multiplicative log-normal jitter keeps every positive-domain family
    (Itakura-Saito / Burg / Shannon) inside its open domain and perturbs
    each coordinate by ~``jitter`` relative — close enough to the data to
    have non-trivial neighbors, far enough to not be the stored row
    itself.
    """
    from .search import _as_forest
    forest = _as_forest(index)
    rows = np.asarray(forest.rows_view())
    live = np.flatnonzero(np.asarray(forest.point_ids) >= 0)
    if live.size == 0:
        raise ValueError("cannot sample held-out queries: no live rows")
    rng = np.random.default_rng(seed)
    pick = rng.choice(live, size=num_queries, replace=live.size < num_queries)
    qs = rows[pick] * np.exp(
        jitter * rng.standard_normal((num_queries, rows.shape[1])))
    return np.asarray(qs, np.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def _brute_force_live(index, ys, k: int):
    """Linear scan over the LIVE rows of an index — the calibration oracle.

    Tombstoned/padded rows (``point_ids < 0``, whose data is the inert
    ones-fill at a finite distance) are masked, so a mutated index never
    surfaces a deleted id.  ``rows_view`` decodes the int8 tier, so the
    scan is exact over the stored point set there too.
    """
    fam = index.family
    rows = index.rows_view()
    dist = jax.vmap(lambda y: fam.distance(rows, y[None, :]))(ys)
    dist = jnp.where((index.point_ids >= 0)[None, :], dist, 1e30)
    neg, idx = jax.lax.top_k(-dist, k)                  # (q, k)
    return jnp.take(index.point_ids, idx), -neg


def fit_calibration(index, *, k: int = 10,
                    num_queries: int = DEFAULT_NUM_QUERIES,
                    p_grid=None, seed: int = 0,
                    jitter: float = DEFAULT_JITTER) -> RecallCalibration:
    """Measure recall@``k`` over a ``p_guarantee`` grid for this index.

    Accepts a BallForest or a SegmentedForest (snapshotted).  The oracle
    is the live-row linear scan — tombstones masked, int8 rows decoded —
    so the measured recall is w.r.t. exactly the point set the approx
    pipeline searches.  ``p`` rides the grid as a traced scalar, so the
    whole sweep compiles once.
    """
    from .search import _as_forest, knn_batch
    forest = _as_forest(index, k)
    grid = np.asarray(DEFAULT_P_GRID if p_grid is None else p_grid,
                      np.float64)
    if grid.ndim != 1 or grid.size < 2 or np.any(np.diff(grid) <= 0):
        raise ValueError("p_grid must be a strictly ascending 1-D grid")
    if grid[-1] != 1.0:
        raise ValueError("p_grid must end at 1.0 (the no-shrink point)")
    live = int(np.sum(np.asarray(forest.point_ids) >= 0))
    num_queries = max(1, min(int(num_queries), max(live, 1)))
    qs = held_out_queries(forest, num_queries, seed, jitter)
    true_ids, _ = _brute_force_live(forest, qs, k)
    true_ids = np.asarray(true_ids)
    rec = np.empty(grid.shape[0], np.float64)
    for i, p in enumerate(grid):
        res = knn_batch(forest, qs, k, approx_p=float(p), validate=False)
        rec[i] = _recall_at_k(np.asarray(res.ids), true_ids)
    # Isotonic projection: recall is non-decreasing in p in expectation;
    # the running max removes finite-sample wiggles while never promising
    # more than some grid point actually measured.
    rec = np.maximum.accumulate(rec)
    return RecallCalibration(p_grid=grid, recall_grid=rec, k=k,
                             num_queries=num_queries, seed=seed,
                             jitter=float(jitter))


def validate_target_recall(target_recall) -> None:
    """Range-gate a raw ``target_recall`` knob (None = knob unused).

    The resolver pair (:func:`resolve_p_guarantee` / the calibration's
    ``resolve``) re-checks the range where it inverts the curve; this
    standalone gate is for entry points that accept the knob but hand it
    off later (serve/retrieval.py stores it per-request), so a malformed
    value fails at submission instead of deep inside the batch ladder.
    """
    if target_recall is None:
        return
    t = float(target_recall)
    if not 0.0 <= t <= 1.0:    # False for NaN too
        raise ValueError(f"target_recall must be in [0, 1], got {t}")


def resolve_p_guarantee(index, target_recall: float):
    """Invert an index's calibration curve: target recall -> (p, expected).

    Returns ``(p_guarantee, expected_recall)``.  ``expected_recall`` is
    the fit's measured recall at the chosen grid point, or ``None`` when
    the index carries no calibration — in which case the historical
    conflation (``p = target_recall``) is preserved, announced once per
    process, so pre-calibration indexes keep working unchanged.
    """
    cal = getattr(index, "calibration", None)
    if cal is None:
        global _warned_uncalibrated
        if not _warned_uncalibrated:
            _warned_uncalibrated = True
            logger.warning(
                "target_recall=%s requested on an uncalibrated index; "
                "falling back to p_guarantee=target_recall. Build with "
                "calibrate=True (build_index / build_datastore) for a "
                "measured recall contract.", target_recall)
        t = float(target_recall)
        if not 0.0 <= t <= 1.0:
            raise ValueError(f"target_recall must be in [0, 1], got {t}")
        return t, None
    return cal.resolve(target_recall)


def ensure_calibration(index, *, k: int = 10,
                       num_queries: int = DEFAULT_NUM_QUERIES,
                       p_grid=None, seed: int = 0,
                       jitter: float = DEFAULT_JITTER):
    """Attach a fitted curve to an index that lacks one; returns the index.

    BallForests come back as a ``dataclasses.replace`` copy; a mutable
    SegmentedForest is updated IN PLACE (its sealed main segment carries
    the curve — the duck-typed ``.main`` check avoids importing
    core.segments here) and its cached snapshot invalidated so the next
    ``view()`` carries the curve too.
    """
    if getattr(index, "calibration", None) is not None:
        return index
    cal = fit_calibration(index, k=k, num_queries=num_queries,
                          p_grid=p_grid, seed=seed, jitter=jitter)
    if hasattr(index, "main"):
        index.main = dataclasses.replace(index.main, calibration=cal)
        index._view = None
        return index
    return dataclasses.replace(index, calibration=cal)
