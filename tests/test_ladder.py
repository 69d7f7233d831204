"""The one budget ladder (``core/search.run_ladder``) and its callers.

The rule cases drive ``run_ladder`` with a scripted launch.  The caller
cases run ``knn_batch`` over every storage form and the retrieval
service's exact and approx tiers on data whose first launch overflows:
each must end exact within two launches, at the budget fitted to the
first launch's largest union, with the ids a direct launch at that budget
returns.  (``tests/dist_checks.py`` checks ``distributed_knn`` alike.)
"""

import numpy as np
import pytest

from repro.core import search
from repro.core.calibrate import resolve_p_guarantee
from repro.core.index import build_index, cold_point_fields
from repro.core.search import SearchResult, run_ladder
from repro.core.segments import build_segmented_index
from repro.core.tiered import TieredPointStore
from repro.serve.faults import VirtualClock
from repro.serve.retrieval import RetrievalService, ServiceConfig

N, D, K, Q = 400, 16, 5, 4
P_TARGET = 0.9


def _scripted(unions):
    """A launch whose rows' Theorem-3 unions are ``unions`` at any budget."""
    calls = []
    nc = np.asarray(unions)

    def launch(b, attempt):
        calls.append((b, attempt))
        return SearchResult(ids=None, dists=None, exact=nc <= b,
                            num_candidates=nc), int(nc.max())

    return launch, calls


def test_ladder_stops_when_the_first_launch_fits():
    launch, calls = _scripted([10, 64])
    res, budget, retries, stopped = run_ladder(launch, 1000, K, 64)
    assert calls == [(64, 0)] and budget == 64
    assert retries == 0 and not stopped and bool(res.exact.all())


def test_ladder_grows_once_to_the_fitted_budget():
    launch, calls = _scripted([100, 300])
    res, budget, retries, stopped = run_ladder(launch, 1000, K, 64)
    assert calls == [(64, 0), (512, 1)]
    assert budget == search.fitted_budget_for_n(1000, K, 300) == 512
    assert retries == 1 and not stopped and bool(res.exact.all())


def test_ladder_stops_at_n():
    """A launch at budget n ends the ladder even where a union reads past
    it (it cannot overflow n rows in a real search)."""
    launch, calls = _scripted([700])
    _, budget, retries, stopped = run_ladder(launch, 600, K, 64)
    assert calls == [(64, 0), (600, 1)] and budget == 600
    assert retries == 1 and not stopped


def test_ladder_stop_retry_hands_back_the_partial_result():
    launch, calls = _scripted([10, 300])
    asked = []
    res, budget, retries, stopped = run_ladder(
        launch, 1000, K, 64, stop_retry=lambda: asked.append(1) or True)
    assert calls == [(64, 0)] and asked == [1]
    assert budget == 64 and retries == 0 and stopped
    assert res.exact.tolist() == [True, False]


def _data():
    """Eight separated blobs and queries on one: a union of a few hundred
    rows, over the default budget and under n."""
    rng = np.random.default_rng(0)
    blob = rng.integers(0, 8, size=N)
    data = (rng.random((N, D)) + 6.0 * blob[:, None] + 0.1).astype(np.float32)
    return data, data[np.flatnonzero(blob == 1)[:Q]] * 1.01


def _index(case, data):
    if case == "batch-int8":
        return build_index(data, "shannon", m=4, quantize=True)
    if case == "batch-segmented":
        sf = build_segmented_index(data[:N - 100], "shannon", m=4)
        sf.insert(data[N - 100:], auto_compact=False)
        sf.delete(np.arange(0, N, 7), auto_compact=False)
        return sf
    index = build_index(data, "shannon", m=4)
    if case == "batch-tiered":
        cold = sum(np.asarray(getattr(index, f)).nbytes
                   for f in cold_point_fields(index))
        return TieredPointStore(index, resident_bytes=(6 * cold) // 10,
                                block_rows=96)
    return index


def _through_service(index, ys, approx):
    svc = RetrievalService(ServiceConfig(buckets=(Q,)), clock=VirtualClock())
    svc.register_tenant("t", index)
    r = svc.search_sync("t", ys, K,
                        target_recall=P_TARGET if approx else None)
    tier = "approx" if approx else "exact"
    launches = r.meta["launches"]
    assert {x["tier"] for x in launches} == {tier}
    assert svc.counters["escalations"] == len(launches) - 1
    return (r.ids, np.asarray(r.row_quality) == tier,
            [x["budget"] for x in launches],
            [max(x["num_candidates"]) for x in launches])


@pytest.mark.parametrize("case", [
    "batch-f32", "batch-int8", "batch-segmented", "batch-tiered",
    "service-exact", "service-approx"])
def test_every_caller_ends_exact_within_two_launches(case):
    data, ys = _data()
    index = _index(case, data)
    approx = case == "service-approx"
    if case.startswith("service"):
        ids, exact, budgets, unions = _through_service(index, ys, approx)
    else:
        res, stats = search.knn_batch(index, ys, K, return_stats=True)
        ids, exact = np.asarray(res.ids), np.asarray(res.exact)
        budgets = [x["budget"] for x in stats.launches]
        unions = [x["num_candidates"] for x in stats.launches]
        assert stats.budget_final == budgets[-1]
        assert stats.escalations == len(budgets) - 1
    n = search._as_forest(index).n
    default = search.resolve_budget(None, n, K)
    # The data makes the first launch overflow, so the ladder climbs to
    # the fitted budget, short of n.
    assert budgets[0] == default < unions[0]
    assert budgets == [default, search.fitted_budget_for_n(n, K, unions[0])]
    assert budgets[1] < n
    assert unions[1] == unions[0] <= budgets[1]
    assert exact.all()
    if approx:
        p, _ = resolve_p_guarantee(search._as_forest(index), P_TARGET)
        want = search.knn_search_batch_approx(index, ys, K, budgets[-1],
                                              np.float32(p))
    else:
        want = search.knn_search_batch(index, ys, K, budgets[-1])
    assert bool(np.asarray(want.exact).all())
    np.testing.assert_array_equal(ids, np.asarray(want.ids))
