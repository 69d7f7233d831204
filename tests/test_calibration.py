"""Measured-recall calibration contract (core/calibrate.py).

The curve's promise — ``target_recall`` resolves to the smallest fitted
shrink whose MEASURED recall meets the target — is pinned here at three
layers: the fitted curve itself (monotone, honest about p=1), the search
entry points (single-host, int8, 1x1-mesh distributed), and the serving
path (approx responses carry ``expected_recall``; the microbatch key
keeps per-tenant resolution separate).

Inversion semantics are tested on HAND-CRAFTED curves: at this repo's
test scale the Theorem-3 prune admits essentially every row, so fitted
curves truthfully measure recall 1.0 at every p — correct, but useless
for exercising the non-trivial resolve() branches.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import search
from repro.core.bregman import family_names, get_family
from repro.core.calibrate import (
    RecallCalibration,
    ensure_calibration,
    resolve_p_guarantee,
)
from repro.core.index import build_index
from repro.core.segments import build_segmented_index
from repro.dist import knn as dknn
from repro.dist.sharding import make_mesh
from repro.serve.faults import VirtualClock
from repro.serve.retrieval import RetrievalService, ServiceConfig

FAMILIES = family_names()
N, D, M, K = 300, 16, 4, 5
GRID = (0.0, 0.5, 0.8, 1.0)     # small fit grid — p is traced, one compile


def _data(family, n=N, seed=0, d=D):
    fam = get_family(family)
    return np.asarray(fam.sample(jax.random.PRNGKey(seed), (n, d)))


def _queries(family, num=6, seed=1):
    return _data(family, n=num, seed=seed)


def _calibrated(family, quantize=False):
    idx = build_index(_data(family), family, m=M, num_clusters=16,
                      quantize=quantize, seed=0)
    return ensure_calibration(idx, k=K, num_queries=24, p_grid=GRID)


# ---------------------------------------------------------------------------
# The fitted curve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_fitted_curve_monotone(family):
    cal = _calibrated(family).calibration
    assert cal is not None and cal.k == K
    p = np.asarray(cal.p_grid)
    r = np.asarray(cal.recall_grid)
    assert p.shape == r.shape and p[-1] == 1.0
    assert np.all(np.diff(p) > 0)
    assert np.all(np.diff(r) >= 0)          # isotonic by construction
    assert np.all((r >= 0) & (r <= 1))
    assert r[-1] == 1.0                     # p=1 disables the shrink: exact


def test_build_index_calibrate_flag_attaches_curve():
    idx = build_index(_data("shannon"), "shannon", m=M, calibrate=True,
                      calibrate_k=K, calibration_queries=24, seed=0)
    assert idx.calibration is not None and idx.calibration.k == K


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("quantize", [False, True],
                         ids=["fp32", "int8"])
def test_measured_recall_meets_target(family, quantize):
    """target_recall=0.9 must deliver measured recall@k within tolerance
    of the curve's promise, on both storage tiers, for every family."""
    idx = _calibrated(family, quantize=quantize)
    qs = _queries(family)
    p, expected = resolve_p_guarantee(idx, 0.9)
    assert expected is not None and (expected >= 0.9 or p == 1.0)
    exact = search.knn_batch(idx, qs, K)
    res = search.knn_batch(idx, qs, K, target_recall=0.9)
    recs = [len(set(np.asarray(res.ids[i]).tolist())
                & set(np.asarray(exact.ids[i]).tolist())) / K
            for i in range(qs.shape[0])]
    assert float(np.mean(recs)) >= expected - 0.15


# ---------------------------------------------------------------------------
# resolve() semantics (hand-crafted curves)
# ---------------------------------------------------------------------------

def _curve(recall_grid, p_grid=(0.0, 0.5, 1.0)):
    return RecallCalibration(p_grid=tuple(p_grid),
                             recall_grid=tuple(recall_grid),
                             k=K, num_queries=8, seed=0)


def test_resolve_is_conservative():
    """Smallest fitted p whose MEASURED recall >= target — never an
    optimistic interpolation between grid points."""
    cal = _curve((0.4, 0.8, 1.0))
    assert cal.resolve(0.3) == (0.0, 0.4)
    assert cal.resolve(0.4) == (0.0, 0.4)
    assert cal.resolve(0.7) == (0.5, 0.8)   # 0.41..0.8 all round UP to p=0.5
    assert cal.resolve(0.9) == (1.0, 1.0)
    assert cal.resolve(1.0) == (1.0, 1.0)


def test_resolve_unreachable_target_is_honest():
    """A target above everything measured: run exact-mode p=1 and report
    the measured ceiling, not the requested number."""
    cal = _curve((0.2, 0.5, 0.9))
    p, expected = cal.resolve(0.95)
    assert p == 1.0 and expected == 0.9


def test_resolve_rejects_out_of_range_targets():
    cal = _curve((0.4, 0.8, 1.0))
    for bad in (-0.1, 1.1):
        with pytest.raises(ValueError):
            cal.resolve(bad)


def test_expected_recall_interpolates():
    cal = _curve((0.4, 0.8, 1.0))
    assert cal.expected_recall(0.25) == pytest.approx(0.6)
    assert cal.expected_recall(1.0) == pytest.approx(1.0)


def test_uncalibrated_fallback_is_historical_behavior():
    """No curve: target_recall degrades to p=target (pre-calibration
    semantics) with no expected-recall claim, bit-identical to passing
    approx_p directly."""
    idx = build_index(_data("burg"), "burg", m=M, seed=0)
    assert idx.calibration is None
    assert resolve_p_guarantee(idx, 0.9) == (0.9, None)
    qs = _queries("burg")
    a = search.knn_batch(idx, qs, K, target_recall=0.9)
    b = search.knn_batch(idx, qs, K, approx_p=0.9)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_exclusive_knob_validation():
    idx = _calibrated("shannon")
    qs = _queries("shannon")
    with pytest.raises(ValueError):
        search.knn_batch(idx, qs, K, approx_p=0.9, target_recall=0.9)
    with pytest.raises(ValueError):
        search.knn_search_batch_approx(idx, jnp.asarray(qs), K, N)
    with pytest.raises(ValueError):
        search.knn_search_batch_approx(idx, jnp.asarray(qs), K, N,
                                       p_guarantee=0.9, target_recall=0.9)


# ---------------------------------------------------------------------------
# Lifecycle: insert / tombstone leave the curve, compact refits it
# ---------------------------------------------------------------------------

def test_curve_survives_mutations_and_compact_refits():
    sf = build_segmented_index(_data("shannon", n=200), "shannon", m=M)
    sf = ensure_calibration(sf, k=K, num_queries=16, p_grid=GRID)
    fitted = sf.calibration
    assert fitted is not None

    ids = sf.insert(_data("shannon", n=40, seed=3), auto_compact=False)
    assert sf.calibration is fitted         # stale-but-measured: no refit
    sf.delete(ids[:10], auto_compact=False)
    assert sf.calibration is fitted
    assert sf.view().calibration is fitted  # snapshot carries it too

    sf.compact("merge")
    assert sf.calibration is not None and sf.calibration is not fitted
    assert sf.calibration.k == K            # refit with the stored params
    assert tuple(sf.calibration.p_grid) == GRID

    sf2 = build_segmented_index(_data("burg", n=60), "burg", m=M)
    sf2 = ensure_calibration(sf2, k=K, num_queries=8, p_grid=GRID)
    sf2.delete(np.arange(60 - K + 1), auto_compact=False)
    sf2.compact("merge")                    # live_n < k: nothing measurable
    assert sf2.calibration is None


def test_uncalibrated_compact_stays_uncalibrated():
    sf = build_segmented_index(_data("exponential", n=80), "exponential",
                               m=M)
    sf.insert(_data("exponential", n=10, seed=2), auto_compact=False)
    sf.compact("merge")
    assert sf.calibration is None           # no surprise background fits


# ---------------------------------------------------------------------------
# 1x1-mesh distributed parity
# ---------------------------------------------------------------------------

def test_dist_1x1_parity_with_target_recall():
    """distributed_knn(target_recall=...) on a 1-device mesh must match
    the single-host calibrated path bit-for-bit: same curve, same
    resolved p, same SPMD-vs-fused numerics (dist/knn.py contract)."""
    mesh = make_mesh((1,), ("data",))
    forest = _calibrated("itakura_saito")
    qs = _queries("itakura_saito")
    sharded = dknn.shard_index(forest, mesh)
    assert sharded.forest.calibration is not None   # survives sharding
    yv = dknn.query_subview(forest.partition, jnp.asarray(qs))
    res = dknn.distributed_knn(sharded, yv, family="itakura_saito", k=K,
                               budget=N, mesh=mesh, stop_retry=lambda: True,
                               target_recall=0.9)
    ref = search.knn_search_batch_approx(forest, jnp.asarray(qs), K, N,
                                         target_recall=0.9)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    with pytest.raises(ValueError):
        dknn.distributed_knn(sharded, yv, family="itakura_saito", k=K,
                             budget=N, mesh=mesh, approx_p=0.9,
                             target_recall=0.9)


# ---------------------------------------------------------------------------
# Serving path
# ---------------------------------------------------------------------------

def _service(**cfg):
    return RetrievalService(ServiceConfig(**cfg), clock=VirtualClock())


def test_service_approx_reports_expected_recall():
    svc = _service()
    sf = build_segmented_index(_data("shannon", n=200), "shannon", m=M)
    svc.register_tenant("t", sf, calibrate=True, calibrate_k=K)
    assert sf.calibration is not None       # register fit it in place

    r = svc.search_sync("t", _queries("shannon"), K, target_recall=0.9)
    assert r.quality == "approx"
    assert r.expected_recall is not None and 0.0 <= r.expected_recall <= 1.0
    assert r.meta["expected_recall"] == r.expected_recall
    p, expected = resolve_p_guarantee(sf.view(), 0.9)
    assert r.meta["p_guarantee"] == p and r.expected_recall == expected

    # Exact-tier responses claim nothing: recall is 1.0 by construction.
    r = svc.search_sync("t", _queries("shannon"), K)
    assert r.quality == "exact" and r.expected_recall is None


def test_service_uncalibrated_approx_reports_nothing():
    svc = _service()
    svc.register_tenant("t", build_segmented_index(
        _data("shannon", n=200), "shannon", m=M))
    r = svc.search_sync("t", _queries("shannon"), K, target_recall=0.9)
    assert r.quality == "approx" and r.expected_recall is None
    assert r.meta["p_guarantee"] == 0.9     # fallback: p = target


def test_microbatch_key_separates_divergent_tenants():
    """Two tenants sharing target_recall=0.9 resolve to DIFFERENT shrink
    levels through their own curves — the tenant component of the
    microbatch key in step() is load-bearing for this, not just for
    isolation (see the comment there)."""
    weak = _curve((0.2, 0.9, 1.0))          # needs p=0.5 to hit 0.9
    strong = _curve((0.95, 0.99, 1.0))      # already at 0.95 with p=0.0
    svc = _service()
    for name, cal in (("weak", weak), ("strong", strong)):
        idx = build_index(_data("shannon", seed=hash(name) % 7), "shannon",
                          m=M, seed=0)
        svc.register_tenant(name, dataclasses.replace(idx, calibration=cal))

    qs = _queries("shannon")
    t1 = svc.submit("weak", qs, K, target_recall=0.9)
    t2 = svc.submit("strong", qs, K, target_recall=0.9)
    while not (t1.done and t2.done):
        svc.step()
    assert t1.response.meta["p_guarantee"] == 0.5
    assert t1.response.expected_recall == 0.9
    assert t2.response.meta["p_guarantee"] == 0.0
    assert t2.response.expected_recall == 0.95
