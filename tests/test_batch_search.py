"""Batched pipeline parity: knn_search_batch vs per-query vs brute force.

Covers all five Bregman families, exact and approximate modes, the
streaming k-selection (multi-block) path, the capped budget-doubling
retry, the batched refine kernel, and the ub_filter dispatch regression
(no silent ref fallback).
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bregman import get_family, family_names
from repro.core.index import build_index
from repro.core import search
from repro.kernels import ops, ref
from repro.kernels import bregman_ub as _ub
from repro.kernels.bregman_dist import bregman_refine_batch


def _dataset(family, n=500, d=24, q=6, seed=0):
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(seed), (n, d), scale=1.0))
    queries = np.asarray(
        fam.sample(jax.random.PRNGKey(seed + 1), (q, d), scale=1.0))
    return data, queries, fam


@pytest.mark.parametrize("family", family_names())
def test_batch_matches_per_query_and_brute_force(family):
    """Exact batch results == per-query results == linear scan, all families."""
    data, queries, fam = _dataset(family)
    index = build_index(data, family, m=4, num_clusters=16, seed=0)
    k = 7
    res = search.knn_batch(index, queries, k)
    assert bool(jnp.all(res.exact))
    bf_ids, bf_dists = search.brute_force_knn(data, queries, k, fam)
    for qi in range(queries.shape[0]):
        single = search.knn(index, queries[qi], k)
        # identical neighbor sets, per-query vs batched vs oracle
        assert (set(np.asarray(res.ids[qi]).tolist())
                == set(np.asarray(single.ids).tolist()))
        np.testing.assert_allclose(
            np.sort(np.asarray(res.dists[qi])),
            np.sort(np.asarray(single.dists)), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.sort(np.asarray(res.dists[qi])),
            np.sort(np.asarray(bf_dists[qi])), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("family", ["squared_euclidean", "itakura_saito"])
def test_batch_approx_matches_per_query(family):
    """Approximate mode: batched CDF shrink == the per-query shrink."""
    data, queries, fam = _dataset(family, n=700, seed=3)
    index = build_index(data, family, m=4, num_clusters=16, seed=0)
    k, p = 8, 0.8
    res = search.knn_batch(index, queries, k, approx_p=p)
    for qi in range(queries.shape[0]):
        single = search.knn(index, queries[qi], k, approx_p=p)
        if bool(res.exact[qi]) and bool(single.exact):
            assert (set(np.asarray(res.ids[qi]).tolist())
                    == set(np.asarray(single.ids).tolist()))
            np.testing.assert_allclose(
                np.sort(np.asarray(res.dists[qi])),
                np.sort(np.asarray(single.dists)), rtol=1e-5, atol=1e-5)
        assert (int(res.num_candidates[qi]) == int(single.num_candidates))


def test_batch_streaming_blocks_match_single_shot():
    """block_rows < n exercises the scan merge; results must be identical."""
    data, queries, fam = _dataset("exponential", n=600)
    index = build_index(data, "exponential", m=4, num_clusters=16, seed=0)
    full = search.knn_batch(index, queries, 5)
    stream = search.knn_batch(index, queries, 5, block_rows=64)
    np.testing.assert_array_equal(np.asarray(full.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_allclose(np.asarray(full.dists),
                               np.asarray(stream.dists), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(full.num_candidates),
                                  np.asarray(stream.num_candidates))


def test_batch_budget_retry_path():
    """A deliberately tiny budget must be doubled until the batch is exact."""
    data, queries, fam = _dataset("squared_euclidean", n=400)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8, seed=0)
    res = search.knn_batch(index, queries, 5, budget=8)
    assert bool(jnp.all(res.exact))
    _, bf_dists = search.brute_force_knn(data, queries, 5, fam)
    np.testing.assert_allclose(np.sort(np.asarray(res.dists), axis=1),
                               np.sort(np.asarray(bf_dists), axis=1),
                               rtol=2e-3, atol=2e-3)


def test_batch_retry_cap_escalates_to_full_refine(caplog):
    """Exhausting the doubling cap logs a warning and escalates to budget=n,
    so exact-mode results stay exact (the pre-batch invariant)."""
    data, queries, fam = _dataset("squared_euclidean", n=400)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8, seed=0)
    with caplog.at_level(logging.WARNING, logger="repro.core.search"):
        res = search.knn_batch(index, queries, 5, budget=8, max_doublings=0)
    assert any("budget cap exhausted" in r.message for r in caplog.records)
    assert bool(jnp.all(res.exact))
    _, bf_dists = search.brute_force_knn(data, queries, 5, fam)
    np.testing.assert_allclose(np.sort(np.asarray(res.dists), axis=1),
                               np.sort(np.asarray(bf_dists), axis=1),
                               rtol=2e-3, atol=2e-3)


def test_knn_batch_rejects_k_larger_than_index():
    data, queries, _ = _dataset("squared_euclidean", n=128)
    index = build_index(data[:16], "squared_euclidean", m=4, num_clusters=4,
                        seed=0)
    with pytest.raises(ValueError, match="exceeds index size"):
        search.knn_batch(index, queries, 17)


def test_knn_batch_rejects_single_vector():
    data, queries, _ = _dataset("squared_euclidean", n=128)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8, seed=0)
    with pytest.raises(ValueError, match=r"\(q, d\)"):
        search.knn_batch(index, queries[0], 5)
    with pytest.raises(ValueError, match=r"\(q, d\)"):
        search.knn_search_batch(index, jnp.asarray(queries[0]), 5, 16)


def test_knn_batch_rejects_budget_smaller_than_k():
    data, queries, _ = _dataset("squared_euclidean", n=128)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8, seed=0)
    with pytest.raises(ValueError, match="must be >= k"):
        search.knn_batch(index, queries, 10, budget=4)


def test_knnlm_hook_mixes_and_gates_on_exact(monkeypatch):
    """KNNLMHook (serve layer): exact rows get the kNN mixture, rows flagged
    inexact fall back to the pure LM distribution.  Lives here because
    test_serve.py needs the missing repro.dist tree to collect."""
    from repro.serve.knnlm import Datastore, KNNLMHook
    from repro.serve import knnlm as knnlm_mod

    data, queries, fam = _dataset("squared_euclidean", n=200, d=16)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8,
                        seed=0)
    store = Datastore(index=index,
                      next_tokens=np.arange(200, dtype=np.int32) % 32,
                      hidden_dim=16)
    hook = KNNLMHook(store=store, k=4, lam=0.5)
    logits = jnp.zeros((3, 32))
    hidden = jnp.asarray(data[:3])
    out = hook(logits, hidden)
    uniform = jax.nn.log_softmax(jnp.zeros((32,)))
    assert out.shape == (3, 32) and hook.queries_served == 3
    # exact retrieval must actually perturb the LM distribution
    assert not np.allclose(np.asarray(out[0]), np.asarray(uniform),
                           atol=1e-5)
    # value table uploaded once, reused across ticks
    dev = hook._next_dev
    hook(logits, hidden)
    assert hook._next_dev is dev

    # rows flagged inexact must serve the pure LM distribution
    real = knnlm_mod.bp_search.knn_batch

    def inexact_knn(*args, **kwargs):
        res = real(*args, **kwargs)
        if kwargs.get("return_stats"):
            res, stats = res
            return res._replace(exact=jnp.zeros_like(res.exact)), stats
        return res._replace(exact=jnp.zeros_like(res.exact))

    monkeypatch.setattr(knnlm_mod.bp_search, "knn_batch", inexact_knn)
    gated = KNNLMHook(store=store, k=4, lam=0.5)(logits, hidden)
    np.testing.assert_allclose(np.asarray(gated),
                               np.broadcast_to(np.asarray(uniform), (3, 32)),
                               atol=1e-5)


def test_brute_force_batched_matches_per_query():
    data, queries, fam = _dataset("shannon", n=300)
    ids_b, dists_b = search.brute_force_knn(data, queries, 6, fam)
    assert ids_b.shape == dists_b.shape == (queries.shape[0], 6)
    for qi in range(queries.shape[0]):
        ids_1, dists_1 = search.brute_force_knn(data, queries[qi], 6, fam)
        np.testing.assert_array_equal(np.asarray(ids_b[qi]),
                                      np.asarray(ids_1))
        np.testing.assert_allclose(np.asarray(dists_b[qi]),
                                   np.asarray(dists_1), rtol=1e-6)


# ---------------------------------------------------------------------------
# kernel dispatch regressions
# ---------------------------------------------------------------------------

def test_ub_filter_single_query_uses_pallas_path(monkeypatch):
    """Regression: single-query shape must hit the kernel, not silently fall
    back to the jnp reference (the old ``qconst.ndim != 1`` guard)."""
    rng = np.random.default_rng(0)
    alpha = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    sg = jnp.asarray(np.abs(rng.normal(size=(64, 8))), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(8,))), jnp.float32)

    calls = []
    real = _ub.bregman_ub_matrix
    monkeypatch.setattr(
        ops._ub, "bregman_ub_matrix",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    totals, comp_of = ops.bregman_ub_filter(alpha, sg, qc, sd,
                                            impl="interpret")
    assert calls, "interpret impl bypassed the Pallas kernel"
    want = ref.bregman_ub_totals(alpha, sg, qc, sd)
    np.testing.assert_allclose(np.asarray(totals), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(comp_of(3)),
                               np.asarray(alpha[3] + qc + sg[3] * sd),
                               rtol=1e-5)


def test_ub_filter_rejects_query_batch():
    rng = np.random.default_rng(0)
    alpha = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
    sg = jnp.abs(alpha)
    qc = jnp.zeros((2, 4), jnp.float32)
    sd = jnp.ones((2, 4), jnp.float32)
    with pytest.raises(ValueError, match="bregman_ub_matrix"):
        ops.bregman_ub_filter(alpha, sg, qc, sd)


@pytest.mark.parametrize("family", family_names())
def test_batched_refine_kernel_matches_ref(family):
    fam = get_family(family)
    rows = fam.sample(jax.random.PRNGKey(2), (5, 33, 70))
    ys = fam.sample(jax.random.PRNGKey(3), (5, 70))
    grad = fam.phi_prime(ys)
    c_y = jnp.sum(ys * grad, -1) - fam.f(ys)
    got = bregman_refine_batch(rows, grad, c_y, family,
                               block_b=16, block_d=32, interpret=True)
    want = ref.bregman_refine_batch(rows, grad, c_y, family)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    direct = fam.distance(rows, ys[:, None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(direct),
                               rtol=1e-3, atol=1e-3)


def test_refine_batch_dispatch_rejects_bad_rank():
    """Rows are (q, b, d) per query or (n, d) shared; a single query's (d,)
    row and a (d,) grad belong to bregman_refine."""
    with pytest.raises(ValueError, match="bregman_refine_batch"):
        ops.bregman_refine_batch(jnp.zeros((8,)), jnp.zeros((4, 8)),
                                 jnp.zeros((4,)), "squared_euclidean")
    with pytest.raises(ValueError, match="bregman_refine_batch"):
        ops.bregman_refine_batch(jnp.zeros((4, 8)), jnp.zeros((8,)),
                                 jnp.zeros(()), "squared_euclidean")


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_refine_in_query_chunks_matches_one_gather(monkeypatch, storage):
    """A candidate gather above REFINE_GATHER_BYTES is refined in query
    chunks (lax.map); the chunked result has the one-gather result's ids,
    and its distances up to the reduction order of a smaller batch."""
    data, queries, _ = _dataset("exponential", n=300, q=5, seed=4)
    index = build_index(data, "exponential", m=4, num_clusters=16, seed=0,
                        quantize=storage == "int8")
    qs = search._query_struct(index, jnp.asarray(queries))
    rng = np.random.default_rng(0)
    sel = jnp.asarray(rng.integers(0, index.n, (5, 64)), jnp.int32)
    valid = jnp.asarray(rng.random((5, 64)) < 0.9)
    whole = search._refine_batch(index, qs, sel, valid, 7)
    row_bytes = 64 * index.d * index.data.dtype.itemsize
    monkeypatch.setattr(search, "REFINE_GATHER_BYTES", 2 * row_bytes)
    chunked = search._refine_batch(index, qs, sel, valid, 7)
    np.testing.assert_array_equal(np.asarray(chunked[0]),
                                  np.asarray(whole[0]))
    np.testing.assert_allclose(np.asarray(chunked[1]),
                               np.asarray(whole[1]), rtol=1e-6)


def test_build_index_logs_phase_seconds(caplog):
    """build_index reports its cost-model / PCCP / k-means wall times on
    one log record (chip_smoke.py reads them from ``build_seconds``)."""
    data, _, _ = _dataset("squared_euclidean", n=200, seed=5)
    with caplog.at_level(logging.INFO, logger="repro.core.index"):
        build_index(data, "squared_euclidean", num_clusters=8, seed=0)
    recs = [r for r in caplog.records if hasattr(r, "build_seconds")]
    assert len(recs) == 1
    s = recs[0].build_seconds
    assert set(s) == {"cost_model", "pccp", "kmeans", "total"}
    assert all(v >= 0.0 for v in s.values())
    assert s["total"] >= s["cost_model"] + s["pccp"] + s["kmeans"]


@pytest.mark.parametrize("family,d,m", [("itakura_saito", 24, 4),
                                        ("itakura_saito", 400, 50)],
                         ids=["isd-d24-M4", "isd-d400-M50"])
def test_knn_batch_ids_equal_brute_force(family, d, m):
    """Exact knn_batch returns the linear scan's ids, in order, at Fonts'
    width and partition count (d = 400, M = 50) as at a small d."""
    data, queries, fam = _dataset(family, n=2000, d=d, q=8, seed=5)
    index = build_index(data, family, m=m, num_clusters=16, seed=0)
    k = 10
    res = search.knn_batch(index, queries, k)
    assert bool(jnp.all(res.exact))
    bf_ids, bf_dists = search.brute_force_knn(data, queries, k, fam)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(bf_ids))
    # The refine evaluates D_f as sum phi(x) - x . phi'(y) + c_y, which
    # cancels terms of size sum |phi(y)| + |y phi'(y)| (the query's term
    # scale); float32 rounding of those d-term sums leaves an error of a
    # few ulps of that scale, which 1e-5 of it bounds with room at d = 400.
    ys = np.asarray(queries, np.float64)
    scale = np.sum(np.abs(np.asarray(fam.phi(ys)))
                   + np.abs(ys * np.asarray(fam.phi_prime(ys))), axis=1)
    err = np.abs(np.asarray(res.dists, np.float64)
                 - np.asarray(bf_dists, np.float64))
    assert np.all(err <= 1e-5 * scale[:, None])


def _knn_spans(trace_dir) -> list:
    """(start_ns, end_ns, name, args) of the ``knn.*`` spans in a profile."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("knn.")]
    return sorted(out)


@pytest.mark.parametrize("max_doublings", [8, 0], ids=["retry", "scan"])
def test_knn_batch_one_span_and_record_per_launch(max_doublings, tmp_path):
    """Each launch of knn_batch's ladder, the overflowing first budget, its
    retry or the escalated scan, opens one ``knn.launch`` span around
    ``knn.dispatch`` and ``knn.wait`` and leaves one BatchStats record."""
    data, queries, _ = _dataset("squared_euclidean", n=400)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=8, seed=0)

    def call():
        return search.knn_batch(index, queries, 5, budget=8,
                                max_doublings=max_doublings,
                                return_stats=True)

    call()                                      # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        res, stats = call()
    finally:
        jax.profiler.stop_trace()
    assert bool(jnp.all(res.exact))
    assert stats.escalated_to_scan == (max_doublings == 0)
    records = stats.launches
    assert len(records) == stats.escalations + 1 + int(
        stats.escalated_to_scan) >= 2
    assert records[0]["budget"] == 8
    assert records[-1]["budget"] == (index.n if stats.escalated_to_scan
                                     else stats.budget_final)
    for r in records:
        assert set(r) == {"budget", "num_candidates", "dense", "dispatch_s",
                          "wait_s"}
        assert r["dispatch_s"] >= 0 and r["wait_s"] >= 0
    assert records[0]["num_candidates"] == int(
        np.asarray(res.num_candidates).max()) > 8

    spans = _knn_spans(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[2], []).append(sp)
    launches = by["knn.launch"]
    assert len(launches) == len(records)
    assert [sp[3]["budget"] for sp in launches] == [r["budget"]
                                                    for r in records]
    assert [sp[3]["attempt"] for sp in launches] == sorted(
        sp[3]["attempt"] for sp in launches)
    for name in ("knn.dispatch", "knn.wait"):
        assert len(by[name]) == len(launches)
        for sp in by[name]:
            [_] = [p for p in launches if p[0] <= sp[0] and sp[1] <= p[1]]
