"""Robustness contract of serve/retrieval.py under seeded fault injection.

Every test runs on a VirtualClock: real launches take zero virtual time,
so latency exists exactly where a fault injects it and each scenario is
deterministic and replayable from its FaultPlan seed.
"""

import numpy as np
import pytest

from repro.core import search as bp
from repro.core.bregman import get_family, validate_rows
from repro.core.search import validate_queries
from repro.core.segments import build_segmented_index
from repro.serve.faults import (
    CompactDuringSearch,
    FaultPlan,
    FetchStall,
    LatencySpike,
    LaunchError,
    PoisonQuery,
    VirtualClock,
)
from repro.serve.retrieval import (
    CircuitBreaker,
    RetrievalService,
    ServiceConfig,
)

N, D, K = 400, 16, 5
SPIKE = 0.3     # injected seconds per launch in the latency tests


def make_index(seed=0, n=N):
    rng = np.random.default_rng(seed)
    data = rng.random((n, D)).astype(np.float32) + 0.1
    return build_segmented_index(data, "shannon", m=4)


@pytest.fixture(scope="module")
def index():
    return make_index()


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(7)
    return rng.random((4, D)).astype(np.float32) + 0.1


def oracle(index, queries, k=K):
    """Fault-free exact reference over the CURRENT live rows."""
    snap = bp._as_forest(index)
    return bp.knn_search_batch(snap, queries, k, snap.n)


def make_service(index, *, faults=None, **cfg):
    clock = VirtualClock()
    svc = RetrievalService(ServiceConfig(**cfg), clock=clock, faults=faults)
    svc.register_tenant("t", index)
    return svc, clock


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------

def test_deadline_honored_under_latency(index, queries):
    """No response exceeds its deadline by more than ONE launch — the
    documented guarantee (a running XLA launch cannot be preempted)."""
    plan = FaultPlan([LatencySpike(SPIKE)], seed=1)
    svc, clock = make_service(index, faults=plan, default_deadline_s=0.5)
    for _ in range(6):
        r = svc.search_sync("t", queries, K)
        assert r.latency_s <= 0.5 + SPIKE + 1e-6
        if r.quality != "shed":
            assert r.deadline_met or r.latency_s - 0.5 <= SPIKE + 1e-6
    assert svc.counters["launches"] == len(plan.fired("latency"))


def test_ladder_degrades_as_cost_rises(index, queries):
    """Once the cost model knows a launch costs SPIKE, tighter deadlines
    walk down the ladder: exact -> approx -> partial -> shed."""
    plan = FaultPlan([LatencySpike(SPIKE)], seed=1)
    svc, clock = make_service(index, faults=plan)
    svc.search_sync("t", queries, K)          # teach the cost model
    assert svc.tenants["t"].cost.estimate() >= SPIKE

    # exact needs exact_margin(2.0) * est headroom
    r = svc.search_sync("t", queries, K, deadline_s=2.5 * SPIKE)
    assert r.meta["tier_path"][0] == "exact"
    # approx fits in [1.0, 2.0) * est
    r = svc.search_sync("t", queries, K, deadline_s=1.5 * SPIKE)
    assert r.meta["tier_path"][0] == "approx"
    # partial fits in [0.5, 1.0) * est
    r = svc.search_sync("t", queries, K, deadline_s=0.8 * SPIKE)
    assert r.meta["tier_path"][0] == "partial"
    # below partial_margin * est: shed WITHOUT launching
    before = svc.counters["launches"]
    r = svc.search_sync("t", queries, K, deadline_s=0.3 * SPIKE)
    assert r.quality == "shed" and r.shed_reason == "deadline"
    assert svc.counters["launches"] == before


def test_expired_requests_shed_without_launch(index, queries):
    svc, clock = make_service(index)
    ticket = svc.submit("t", queries, K, deadline_s=0.1)
    clock.advance(0.2)                        # deadline passes while queued
    svc.step()
    assert ticket.done and ticket.response.quality == "shed"
    assert ticket.response.shed_reason == "deadline"
    # Truthful labels: this deadline was MISSED, and the response says so.
    assert ticket.response.deadline_met is False
    assert svc.counters["launches"] == 0


def test_stale_batchmate_never_coupled_to_fresh_traffic(index, queries):
    """REGRESSION: a microbatch runs on min(deadline), so a nearly-expired
    request used to drag fresh batchmates into its shed.  The formation
    spread guard keeps them in separate batches: the stale one sheds
    alone, the fresh one completes at full quality."""
    svc, _ = make_service(index)
    svc.tenants["t"].cost.observe(SPIKE)      # price the tiers
    stale = svc.submit("t", queries, K, deadline_s=0.3 * SPIKE)
    fresh = svc.submit("t", queries, K, deadline_s=10 * SPIKE)
    svc.run_until_drained()
    assert stale.response.shed_reason == "deadline"
    assert fresh.response.quality == "exact"
    np.testing.assert_array_equal(fresh.response.ids,
                                  np.asarray(oracle(index, queries).ids))


def test_deadline_shed_requeues_batchmates_with_slack(index, queries):
    """Within the spread guard two requests DO batch; when the batch sheds
    on its tightest member's deadline, the member with remaining slack is
    requeued and served on its own deadline, not resolved as shed."""
    svc, _ = make_service(index)
    svc.tenants["t"].cost.observe(SPIKE)
    # Remaining-deadline ratio 1.83 <= deadline_spread(2.0): one batch.
    # Its min (0.3*SPIKE) is below the partial floor (0.5*SPIKE) -> shed,
    # but the 0.55*SPIKE member affords the partial tier by itself.
    tight = svc.submit("t", queries, K, deadline_s=0.3 * SPIKE)
    slack = svc.submit("t", queries, K, deadline_s=0.55 * SPIKE)
    svc.step()
    assert tight.done and tight.response.shed_reason == "deadline"
    assert not slack.done                     # requeued, not shed
    svc.run_until_drained()
    assert slack.response.quality in ("exact", "partial")
    assert svc.counters["launches"] >= 1


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

def test_queue_full_returns_retry_after(index, queries):
    svc, _ = make_service(index, queue_depth=2)
    t1 = svc.submit("t", queries, K)
    t2 = svc.submit("t", queries, K)
    t3 = svc.submit("t", queries, K)          # bounced: queue is full
    assert not t1.done and not t2.done
    assert t3.done and t3.response.quality == "shed"
    assert t3.response.shed_reason == "queue_full"
    assert t3.response.retry_after is not None and t3.response.retry_after > 0
    assert svc.counters["rejected_queue_full"] == 1
    svc.run_until_drained()
    assert t1.done and t2.done


def test_bad_k_rejected_up_front(index, queries):
    svc, _ = make_service(index)
    t = svc.submit("t", queries, index.live_n + 1)
    assert t.done and t.response.quality == "shed"
    assert t.response.shed_reason == "bad_k"
    assert "live_n" in t.response.error
    assert svc.counters["launches"] == 0
    # The rejection's sentinel arrays are clamped to live_n columns: a
    # huge k must not allocate gigabytes while building its own bounce.
    t = svc.submit("t", queries, 10**9)
    assert t.response.shed_reason == "bad_k"
    assert t.response.ids.shape == (queries.shape[0], index.live_n)
    t = svc.submit("t", queries, 0)
    assert t.response.shed_reason == "bad_k"
    assert t.response.ids.shape == (queries.shape[0], 1)


def test_microbatching_coalesces_requests(index, queries):
    svc, _ = make_service(index)
    tickets = [svc.submit("t", queries[i:i + 1], K) for i in range(3)]
    svc.step()
    assert all(t.done for t in tickets)
    # 3 single-row requests -> ONE bucketed microbatch (plus possible
    # budget retries, which relaunch the same block).
    assert svc.counters["launches"] <= 2
    ref = oracle(index, queries[:3])
    for i, t in enumerate(tickets):
        assert t.response.quality == "exact"
        np.testing.assert_array_equal(t.response.ids[0],
                                      np.asarray(ref.ids)[i])


# ---------------------------------------------------------------------------
# Circuit breaker + retry
# ---------------------------------------------------------------------------

def test_breaker_opens_half_opens_closes(index, queries):
    plan = FaultPlan([LaunchError(at_launches=(0, 1))], seed=3)
    svc, clock = make_service(index, faults=plan, breaker_threshold=2,
                              breaker_cooldown_s=1.0)
    brk = svc.tenants["t"].breaker

    # Two injected failures: retry with backoff, then the breaker opens.
    r1 = svc.search_sync("t", queries, K)
    assert r1.quality == "shed" and r1.shed_reason == "launch_failed"
    assert "InjectedLaunchError" in r1.error
    assert brk.state == "open" and brk.opens == 1

    # While open: shed with a retry_after hint, no launches.
    before = svc.counters["launches"]
    r2 = svc.search_sync("t", queries, K)
    assert r2.shed_reason == "breaker_open"
    assert 0 < r2.retry_after <= 1.0
    assert svc.counters["launches"] == before

    # After the cooldown: one half-open probe, which succeeds and closes.
    clock.advance(1.1)
    r3 = svc.search_sync("t", queries, K)
    assert r3.quality == "exact"
    assert brk.state == "closed"
    np.testing.assert_array_equal(r3.ids, np.asarray(oracle(index,
                                                            queries).ids))


def test_breaker_allow_is_side_effect_free():
    """allow() must not transition open -> half_open: the probe is marked
    only when a launch actually goes out (begin_probe), so a caller that
    checks and then sheds anyway cannot wedge the breaker."""
    brk = CircuitBreaker(threshold=1, cooldown_s=2.0)
    brk.record_failure(0.0)
    assert brk.state == "open"
    assert not brk.allow(1.0)
    assert brk.allow(2.5) and brk.allow(2.5)  # idempotent, no transition
    assert brk.state == "open"
    brk.begin_probe()
    assert brk.state == "half_open"
    assert not brk.allow(2.5)                 # probe in flight
    assert brk.retry_after(2.5) > 0           # nonzero hint, never 0-forever
    brk.record_failure(3.0)
    assert brk.state == "open" and brk.retry_after(3.5) > 0


def test_breaker_probe_survives_deadline_shed(index, queries):
    """REGRESSION: a post-cooldown batch that sheds on deadline WITHOUT
    launching used to leave the breaker wedged in half_open (allow()
    False, retry_after 0.0 forever).  It must stay open and still admit
    the probe for the next request that can afford a launch."""
    plan = FaultPlan([LaunchError(at_launches=(0, 1))], seed=9)
    svc, clock = make_service(index, faults=plan, breaker_threshold=2,
                              breaker_cooldown_s=1.0)
    brk = svc.tenants["t"].breaker
    r = svc.search_sync("t", queries, K)
    assert r.shed_reason == "launch_failed" and brk.state == "open"

    clock.advance(1.1)                        # cooldown passed: probe due
    svc.tenants["t"].cost.observe(1.0)        # price every tier off-deadline
    r = svc.search_sync("t", queries, K, deadline_s=0.01)
    assert r.shed_reason == "deadline"        # shed BEFORE any launch
    assert brk.state == "open"                # NOT wedged in half_open
    assert brk.retry_after(clock.now()) == 0  # probe still on offer

    r = svc.search_sync("t", queries, K, deadline_s=10.0)
    assert r.quality == "exact"               # the probe ran and closed it
    assert brk.state == "closed"


def test_transient_failure_retried_within_deadline(index, queries):
    plan = FaultPlan([LaunchError(at_launches=0)], seed=4)
    svc, _ = make_service(index, faults=plan, breaker_threshold=3)
    r = svc.search_sync("t", queries, K)
    assert r.quality == "exact"               # retry after backoff succeeded
    assert svc.counters["launch_failures"] == 1
    assert r.latency_s > 0                    # the jittered backoff slept


# ---------------------------------------------------------------------------
# Poison containment
# ---------------------------------------------------------------------------

def test_poisoned_query_degrades_only_its_row(index, queries):
    plan = FaultPlan([PoisonQuery(at_submits=0, row=1)], seed=5)
    svc, _ = make_service(index, faults=plan)
    r = svc.search_sync("t", queries, K)
    assert plan.fired("poison")
    assert r.flagged_rows == [1]
    assert r.row_quality[1] == "shed"
    assert (r.ids[1] == -1).all() and np.isinf(r.dists[1]).all()
    # The batchmates are untouched AND still exact vs the oracle.
    ref = np.asarray(oracle(index, queries).ids)
    for i in (0, 2, 3):
        assert r.row_quality[i] == "exact"
        np.testing.assert_array_equal(r.ids[i], ref[i])
    assert r.quality == "exact"               # headline = worst VALID row


def test_poisoned_index_rows_quarantined_at_register():
    idx = make_index(seed=11, n=200)
    bad = np.full((2, D), 0.5, np.float32)
    bad[0, 3] = np.nan
    bad[1, 5] = -1.0                          # shannon domain is x > 0
    bad_ids = idx.insert(bad, auto_compact=False)
    svc, _ = make_service(idx)
    tenant = svc.tenants["t"]
    assert tenant.degraded
    assert sorted(tenant.quarantined) == sorted(bad_ids)
    q = np.random.default_rng(2).random((2, D)).astype(np.float32) + 0.1
    r = svc.search_sync("t", q, K)
    assert r.quality == "exact" and r.tenant_degraded
    assert not np.isin(r.ids, bad_ids).any()  # quarantined ids never surface


# ---------------------------------------------------------------------------
# Snapshot consistency under mutation
# ---------------------------------------------------------------------------

def test_compaction_during_search_is_snapshot_consistent(queries):
    idx = make_index(seed=13, n=200)
    n0 = idx.live_n
    plan = FaultPlan([CompactDuringSearch(at_launches=0, insert_rows=8)],
                     seed=6)
    svc, _ = make_service(idx, faults=plan, record_snapshots=True)
    r = svc.search_sync("t", queries, K)
    assert plan.fired("compact")
    assert idx.live_n == n0 + 8               # the race really happened
    # Results are bit-identical to searching the pre-mutation snapshot
    # with the same final budget (queries.shape[0] == bucket, no padding).
    snap = r.meta["snapshot"]
    assert snap.n == n0
    ref = bp.knn_search_batch(snap, queries, K, r.meta["budget"])
    np.testing.assert_array_equal(r.ids, np.asarray(ref.ids))
    np.testing.assert_array_equal(r.dists, np.asarray(ref.dists))


# ---------------------------------------------------------------------------
# Quality labels are truthful in all four tiers
# ---------------------------------------------------------------------------

def test_quality_exact_matches_oracle(index, queries):
    svc, _ = make_service(index)
    r = svc.search_sync("t", queries, K)
    assert r.quality == "exact"
    np.testing.assert_array_equal(r.ids, np.asarray(oracle(index,
                                                           queries).ids))


def test_quality_approx_labels_approx_pipeline(index, queries):
    svc, _ = make_service(index)
    r = svc.search_sync("t", queries, K, target_recall=0.9)
    assert r.meta["tier_path"][0] == "approx"
    # §8 results must NEVER claim "exact", however complete they look.
    assert r.quality == "approx"
    assert all(q in ("approx", "partial") for q in r.row_quality)


def test_quality_partial_when_deadline_caps_retries(index, queries):
    # The seed data overflows the default budget (the exact tier needs a
    # budget retry); a deadline that affords exactly one launch caps the
    # ladder there, and the overflowed rows must come back "partial".
    stats_probe = bp.knn_batch(bp._as_forest(index), queries, K,
                               return_stats=True)[1]
    assert stats_probe.escalations >= 1       # scenario precondition
    plan = FaultPlan([LatencySpike(SPIKE)], seed=8)
    # exact_margin=1.0: the exact tier is entered as soon as ONE launch
    # fits, so a 1.2-launch deadline admits the first launch and the
    # stop_retry gate then caps the budget ladder after it.
    svc, _ = make_service(index, faults=plan, exact_margin=1.0)
    svc.tenants["t"].cost.observe(SPIKE)      # pre-trained cost model
    r = svc.search_sync("t", queries, K, deadline_s=1.2 * SPIKE)
    assert r.meta["tier_path"] == ["exact"]
    assert r.quality == "partial"             # capped, and says so
    assert any(q == "partial" for q in r.row_quality)
    # Rows still labeled exact really are exact.
    ref = np.asarray(oracle(index, queries).ids)
    for i, q in enumerate(r.row_quality):
        if q == "exact":
            np.testing.assert_array_equal(r.ids[i], ref[i])


def test_quality_shed_is_explicit(index, queries):
    svc, _ = make_service(index)
    svc.tenants["t"].cost.observe(1.0)
    r = svc.search_sync("t", queries, K, deadline_s=0.01)
    assert r.quality == "shed" and r.shed_reason == "deadline"
    assert (r.ids == -1).all() and np.isinf(r.dists).all()
    assert svc.counters["shed"] >= 1


# ---------------------------------------------------------------------------
# Telemetry: per-launch records in meta, counters, service spans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", ["escalation", "coalesced", "retry",
                                      "latency", "wall_clock"])
def test_meta_records_each_launch(index, queries, scenario):
    """One record per completed launch, shared by the microbatch's
    requests; budgets ascend to the answer's; the timed parts of the
    launches fit inside each request's latency."""
    faults = {"retry": FaultPlan([LaunchError(at_launches=0)], seed=4),
              "latency": FaultPlan([LatencySpike(SPIKE)], seed=1)}
    if scenario == "wall_clock":
        svc = RetrievalService(ServiceConfig(default_deadline_s=60.0))
        svc.register_tenant("t", index)
    else:
        svc, _ = make_service(index, faults=faults.get(scenario))
    blocks = ([queries[i:i + 1] for i in range(3)] if scenario == "coalesced"
              else [queries])
    before = dict(svc.counters)
    tickets = [svc.submit("t", b, K) for b in blocks]
    svc.run_until_drained()
    rs = [t.response for t in tickets]
    assert all(r.quality == "exact" for r in rs)
    launches = rs[0].meta["launches"]
    assert all(r.meta["launches"] is launches for r in rs)
    assert len({r.meta["batch"] for r in rs}) == 1
    rise = {k: svc.counters[k] - before[k] for k in before}
    assert len(launches) == rise["launches"] >= 2   # this data escalates
    budgets = [x["budget"] for x in launches]
    assert budgets == sorted(set(budgets))
    assert budgets[-1] == rs[0].meta["budget"]
    rows = sum(len(b) for b in blocks)
    for x in launches:
        assert x["tier"] == "exact" and x["q"] == rows
        assert len(x["num_candidates"]) == rows
        assert x["dispatch_s"] >= 0 and x["wait_s"] >= 0
    device_s = sum(x["dispatch_s"] + x["wait_s"] for x in launches)
    for r in rs:
        assert r.meta["queue_s"] >= 0
        assert device_s <= r.latency_s
    assert rise["microbatches"] == 1
    assert rise["microbatch_requests"] == len(tickets)
    assert rise["queue_s"] == pytest.approx(
        sum(r.meta["queue_s"] for r in rs))
    assert rise["host_s"] >= 0
    if scenario == "wall_clock":
        assert device_s > 0 and rise["host_s"] > 0
    if scenario == "retry":
        assert rise["launch_failures"] == 1 and rs[0].meta["attempts"] == 2


def _host_spans(trace_dir) -> list:
    """(start_ns, end_ns, name, args) of the ``svc.*`` spans in a profile."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("svc.")]
    return sorted(out)


def test_service_spans_nest_in_a_profile(index, queries, tmp_path):
    import jax

    svc, _ = make_service(index)
    svc.search_sync("t", queries[:2], K)        # compile outside the profile
    before = svc.counters["launches"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            tickets = [svc.submit("t", queries[i:i + 1], K) for i in range(2)]
            svc.step()
    finally:
        jax.profiler.stop_trace()
    assert all(t.done for t in tickets)
    spans = _host_spans(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[2], []).append(sp)

    def parent(sp, name):
        [p] = [p for p in by[name] if p[0] <= sp[0] and sp[1] <= p[1]]
        return p

    assert len(by["svc.step"]) == 2
    assert len(by["svc.microbatch"]) == 2
    for mb in by["svc.microbatch"]:
        parent(mb, "svc.step")
        assert mb[3]["rows"] == 2 and mb[3]["bucket"] == 2
    assert len(by["svc.launch"]) == svc.counters["launches"] - before
    for sp in by["svc.launch"] + by["svc.resolve"]:
        assert sp[3]["batch"] == parent(sp, "svc.microbatch")[3]["batch"]
    for sp in by["svc.dispatch"] + by["svc.wait"]:
        parent(sp, "svc.launch")
    assert len(by["svc.dispatch"]) == len(by["svc.wait"]) == len(
        by["svc.launch"])
    assert {sp[3]["tier"] for sp in by["svc.launch"]} == {"exact"}


# ---------------------------------------------------------------------------
# Satellite: structured escalation stats + query validation
# ---------------------------------------------------------------------------

def test_knn_batch_returns_structured_stats(index, queries):
    snap = bp._as_forest(index)
    res, stats = bp.knn_batch(snap, queries, K, return_stats=True)
    assert bool(np.asarray(res.exact).all())
    assert stats.escalations >= 1             # this data overflows (above)
    assert stats.budget_final >= bp.default_budget(snap, K)
    assert not stats.escalated_to_scan and not stats.stopped_early

    # stop_retry=True before the first RETRY -> budget-capped partial.
    res2, stats2 = bp.knn_batch(snap, queries, K, stop_retry=lambda: True,
                                return_stats=True)
    assert stats2.stopped_early and stats2.escalations == 0
    assert not bool(np.asarray(res2.exact).all())


def test_validate_queries_names_offending_row(index):
    fam = get_family("shannon")
    q = np.full((3, D), 0.5, np.float32)
    q[2, 4] = np.nan
    with pytest.raises(ValueError, match="row 2"):
        validate_queries(fam, q)
    q[2, 4] = -0.5                            # finite but out of domain
    with pytest.raises(ValueError, match="row 2"):
        validate_queries(fam, q)
    mask = validate_queries(fam, q, mode="mask")
    assert mask.tolist() == [True, True, False]
    with pytest.raises(ValueError, match="row 2"):
        bp.knn_search_batch(index, q, K, 64)


def test_segments_insert_validation_and_quarantine():
    idx = make_index(seed=17, n=200)
    bad = np.full((1, D), 0.5, np.float32)
    bad[0, 0] = np.nan
    with pytest.raises(ValueError, match="insert row 0"):
        idx.insert(bad, validate=True)
    assert idx.find_invalid().size == 0       # the raise kept it out
    (bid,) = idx.insert(bad, validate=False)  # simulated corruption
    assert idx.find_invalid().tolist() == [bid]
    assert idx.quarantine().tolist() == [bid]
    assert idx.find_invalid().size == 0
    assert bid not in idx.live_ids()


def test_validate_rows_mask_matches_family_domain():
    fam = get_family("squared_euclidean")
    rows = np.array([[1.0, -2.0], [np.inf, 0.0]], np.float32)
    mask = validate_rows(fam, rows, mode="mask")
    assert mask.tolist() == [True, False]     # all-reals family: finite only


# ---------------------------------------------------------------------------
# Tiered tenants: warm() + FetchStall containment
# ---------------------------------------------------------------------------

def make_tiered_service(index, *, faults=None, **cfg):
    """A service whose tenant's cold point blocks live in host RAM
    (resident_bytes below the ~38 KB cold footprint at n=400, d=16)."""
    clock = VirtualClock()
    svc = RetrievalService(ServiceConfig(**cfg), clock=clock, faults=faults)
    svc.register_tenant("t", index, resident_bytes=20_000)
    return svc, clock


def test_tiered_tenant_matches_oracle_and_warm_prefills(index, queries):
    svc, _ = make_tiered_service(index)
    store = svc.tenants["t"].tiered
    assert store is not None and not store.is_resident

    out = svc.warm("t", shapes=[(len(queries), K)])
    assert len(out["programs"]) >= 1
    assert out["tiered"]["blocks_cached"] > 0
    assert svc.counters["submitted"] == 0      # warming is accounting-free

    r = svc.search_sync("t", queries, K)
    ref = oracle(index, queries)
    assert r.quality == "exact"
    np.testing.assert_array_equal(r.ids, np.asarray(ref.ids))


def test_fetch_stall_within_timeout_rides_like_latency(index, queries):
    """A slow (but not wedged) cold-block fetch delays the launch without
    breaking results or labels."""
    plan = FaultPlan([FetchStall(0.2, at_launches=0, tenant="t")], seed=11)
    svc, _ = make_tiered_service(index, faults=plan)
    r = svc.search_sync("t", queries, K)
    assert len(plan.fired("fetch_stall")) == 1
    assert r.quality == "exact" and r.latency_s >= 0.2
    np.testing.assert_array_equal(r.ids, np.asarray(oracle(index, queries).ids))


def test_fetch_stall_beyond_timeout_contained_by_retry(index, queries):
    """A wedged fetch surfaces as FetchTimeout; the service charges the
    full wait window, retries, and the retry (no longer stalled) serves
    exact results — no hang, no wedged microbatch."""
    plan = FaultPlan([FetchStall(10.0, at_launches=0, tenant="t")], seed=12)
    svc, clock = make_tiered_service(index, faults=plan)
    r = svc.search_sync("t", queries, K, deadline_s=20.0)
    events = plan.fired("fetch_stall")
    assert len(events) == 1 and "FetchTimeout" in events[0].detail
    assert r.quality == "exact"                # retry succeeded, truthfully
    assert svc.counters["launches"] >= 2       # failed launch + clean retry
    assert r.latency_s >= 5.0                  # the timeout window was paid
    np.testing.assert_array_equal(r.ids, np.asarray(oracle(index, queries).ids))


def test_fetch_stall_noop_on_resident_tenant(index, queries):
    """Fully-resident tenants have no fetch to stall: the fault never
    fires and nothing slows down."""
    plan = FaultPlan([FetchStall(10.0, tenant="t")], seed=13)
    svc, _ = make_service(index, faults=plan)
    assert svc.tenants["t"].tiered is None
    r = svc.search_sync("t", queries, K)
    assert not plan.fired("fetch_stall")
    assert r.quality == "exact"


def test_mesh_and_resident_bytes_are_mutually_exclusive(index):
    svc, _ = make_service(index)
    with pytest.raises(ValueError, match="resident_bytes"):
        svc.register_tenant("x", make_index(seed=3), mesh=(1, 1),
                            resident_bytes=20_000)
