"""Out-of-core tiered store parity + residency invariants (core/tiered.py).

The contract under test: a TieredPointStore — cold point blocks in host
RAM, fetched to device only on envelope admission — returns results
BIT-IDENTICAL to the fully-resident ``knn_search_batch`` /
``knn_search_batch_approx`` on the same point set, across all five
Bregman families x {fp32, int8} x {exact, approx}, and after every
point-table mutation the index layer supports (pad / tombstone / slice /
concat, SegmentedForest insert / delete / compact).  Residency mechanics
— the LRU block-cache budget, pinned append blocks, the resident fast
path, prefetch stats, fetch timeouts, and the knob resolvers — are
pinned alongside.
"""

import dataclasses
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bregman import family_names, get_family
from repro.core.index import (build_index, cold_point_fields, concat_points,
                              pad_points, slice_points, tombstone_rows)
from repro.core.segments import build_segmented_index
from repro.core import search
from repro.core.tiered import (DEFAULT_PREFETCH_DEPTH, FetchTimeout,
                               TieredPointStore, resolve_prefetch_depth,
                               resolve_resident_bytes)

N, D, M, Q, K = 420, 16, 4, 4, 5
BLOCK_ROWS = 96          # 5 cold blocks at N=420 — real multi-block tiering
BUDGET = 64
P_APPROX = 0.8
# fp32 cold-tier footprint at these shapes; int8 tiers are ~8x smaller,
# so budgets are sized per index (see _small_budget) to force real
# multi-block fetch/evict traffic in both storage modes.
SMALL_BUDGET_BYTES = 40_000


def _small_budget(index):
    """~60% of the index's cold footprint: tiered, holds a few bundles."""
    view = getattr(index, "view", None)
    forest = view() if callable(view) else index
    cold = sum(np.asarray(getattr(forest, f)).nbytes
               for f in cold_point_fields(forest))
    return max(1, (6 * cold) // 10)


def _assert_bitwise_equal(a, b):
    for f in ("ids", "dists", "exact", "num_candidates"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


@functools.lru_cache(maxsize=None)
def _built(family, quantize):
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(0), (N, D), scale=1.0))
    queries = jnp.asarray(np.asarray(
        fam.sample(jax.random.PRNGKey(1), (Q, D), scale=1.0)))
    index = build_index(data, family, m=M, num_clusters=8, seed=0,
                        quantize=quantize)
    return index, queries


@functools.lru_cache(maxsize=None)
def _mutated(family, quantize):
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(2), (N, D), scale=1.0))
    sf = build_segmented_index(data[:N - 64], family, m=M, num_clusters=8,
                               seed=0, quantize=quantize)
    sf.insert(data[N - 64:], auto_compact=False)
    sf.delete([1, 5, N - 30], auto_compact=False)
    return sf


# ---------------------------------------------------------------------------
# Bit parity: all families x storage tiers x {exact, approx}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_tiered_matches_resident(family, quantize):
    """Exact + approx, fp32 + int8: tiered == resident, bit for bit."""
    index, queries = _built(family, quantize)
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    assert not store.is_resident and store.num_blocks == 5

    res = store.search(queries, K, BUDGET)
    ref = search.knn_search_batch(index, queries, K, BUDGET,
                                  block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)
    assert store.stats["host_bytes_fetched"] > 0

    res_a = store.search(queries, K, BUDGET, p_guarantee=P_APPROX)
    ref_a = search.knn_search_batch_approx(index, queries, K, BUDGET,
                                           jnp.float32(P_APPROX),
                                           block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_a, ref_a)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_tiered_matches_resident_mutated_segmented(family, quantize):
    """Parity over a segmented index with appends + tombstones; the
    append-segment rows are pinned device-resident."""
    sf = _mutated(family, quantize)
    fam = get_family(family)
    queries = jnp.asarray(np.asarray(
        fam.sample(jax.random.PRNGKey(3), (Q, D), scale=1.0)))
    store = TieredPointStore.from_index(sf,
                                        resident_bytes=_small_budget(sf),
                                        block_rows=BLOCK_ROWS)
    lo, hi = sf.append_row_range()
    assert lo == sf.main.n and hi == sf.n
    want_pinned = set(range(lo // store._bn, -(-hi // store._bn)))
    assert set(store._pinned) == want_pinned and want_pinned

    budget = sf.live_n
    res = store.search(queries, K, budget)
    ref = search.knn_search_batch(sf, queries, K, budget,
                                  block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)
    # tombstoned ids can never surface through the tiered compaction
    gone = {1, 5, N - 30}
    assert not gone & set(np.asarray(res.ids).ravel().tolist())
    # pinned blocks survive every eviction the search cycle caused
    assert want_pinned <= set(store._cache)

    res_a = store.search(queries, K, budget, p_guarantee=P_APPROX)
    ref_a = search.knn_search_batch_approx(sf, queries, K, budget,
                                           jnp.float32(P_APPROX),
                                           block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_a, ref_a)


@pytest.mark.parametrize("quantize", [False, True])
def test_tiered_matches_after_pad_tombstone_slice_concat(quantize):
    """Every point-table mutation path feeds the same tier contract."""
    index, queries = _built("squared_euclidean", quantize)

    mutants = {
        "pad": pad_points(index, 7),
        "concat": concat_points([slice_points(index, 0, 224),
                                 slice_points(index, 224, N - 224)]),
    }
    dead = np.zeros(index.n, bool)
    dead[::3] = True
    mutants["tombstone"] = tombstone_rows(index, jnp.asarray(dead))
    mutants["slice"] = slice_points(index, 96, 224)

    for name, forest in mutants.items():
        k = min(K, int((np.asarray(forest.point_ids) >= 0).sum()))
        budget = min(BUDGET, forest.n)
        store = TieredPointStore(forest,
                                 resident_bytes=_small_budget(forest),
                                 block_rows=BLOCK_ROWS)
        res = store.search(queries, k, budget)
        ref = search.knn_search_batch(forest, queries, k, budget,
                                      block_rows=BLOCK_ROWS, validate=False)
        _assert_bitwise_equal(res, ref)
        del name


def test_tiered_matches_after_compact():
    fam = get_family("shannon")
    data = np.asarray(fam.sample(jax.random.PRNGKey(2), (N, D), scale=1.0))
    sf = build_segmented_index(data[:N - 64], "shannon", m=M, num_clusters=8,
                               seed=0)
    sf.insert(data[N - 64:], auto_compact=False)
    sf.delete([1, 5, N - 30], auto_compact=False)
    sf.compact("merge")
    queries = jnp.asarray(np.asarray(get_family("shannon").sample(
        jax.random.PRNGKey(4), (Q, D), scale=1.0)))
    store = TieredPointStore.from_index(sf,
                                        resident_bytes=_small_budget(sf),
                                        block_rows=BLOCK_ROWS)
    # post-compaction there are no append segments left to pin
    assert sf.append_row_range()[0] == sf.append_row_range()[1]
    assert not store._pinned
    res = store.search(queries, K, BUDGET)
    ref = search.knn_search_batch(sf, queries, K, BUDGET,
                                  block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)


# ---------------------------------------------------------------------------
# Routing: one public API for both residency modes
# ---------------------------------------------------------------------------

def test_public_entry_points_route_tiered_stores():
    index, queries = _built("squared_euclidean", False)
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    ref = search.knn_search_batch(index, queries, K, BUDGET,
                                  block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(
        search.knn_search_batch(store, queries, K, BUDGET,
                                block_rows=BLOCK_ROWS), ref)
    ref_a = search.knn_search_batch_approx(index, queries, K, BUDGET,
                                           jnp.float32(P_APPROX),
                                           block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(
        search.knn_search_batch_approx(store, queries, K, BUDGET,
                                       jnp.float32(P_APPROX),
                                       block_rows=BLOCK_ROWS), ref_a)

    # a single query is the q = 1 row, sliced to scalar shapes, and
    # climbs the same ladder as over the resident forest
    one = search.knn(store, queries[0], K, BUDGET)
    assert one.ids.shape == (K,) and bool(one.exact)
    _assert_bitwise_equal(one, search.knn(index, queries[0], K, BUDGET))
    one_a = search.knn(store, queries[0], K, BUDGET, approx_p=P_APPROX)
    _assert_bitwise_equal(one_a, search.knn(index, queries[0], K, BUDGET,
                                            approx_p=P_APPROX))

    # knn_batch retries/escalation accept a store
    res = search.knn_batch(store, queries, K, budget=BUDGET,
                           block_rows=BLOCK_ROWS)
    assert res.ids.shape == (Q, K)

    # O(n*q) diagnostics refuse a store with actionable guidance
    with pytest.raises(TypeError, match="as_resident_forest"):
        search.knn_search_batch_stats(store, queries, K, BUDGET)
    with pytest.raises(TypeError, match="as_resident_forest"):
        search.knn_search_batch_reference(store, queries, K, BUDGET)

    # ... and the escape hatch is the full resident forest, bit for bit
    forest = store.as_resident_forest()
    _assert_bitwise_equal(
        search.knn_search_batch(forest, queries, K, BUDGET,
                                block_rows=BLOCK_ROWS), ref)
    for f in cold_point_fields(forest):
        assert isinstance(getattr(forest, f), jax.Array)


def test_search_rejects_conflicting_block_rows_and_knob_misuse():
    index, queries = _built("squared_euclidean", False)
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    with pytest.raises(ValueError, match="pinned"):
        store.search(queries, K, BUDGET, block_rows=2 * BLOCK_ROWS)
    with pytest.raises(ValueError, match="at most one"):
        store.search(queries, K, BUDGET, p_guarantee=0.9, target_recall=0.9)
    with pytest.raises(ValueError, match="p_guarantee"):
        store.search(queries, K, BUDGET, p_guarantee=1.5)
    with pytest.raises(ValueError, match=r"\(q, d\)"):
        store.search(queries[0], K, BUDGET)


# ---------------------------------------------------------------------------
# Residency mechanics
# ---------------------------------------------------------------------------

def test_resident_fast_path_when_budget_fits():
    """cold_bytes <= resident_bytes (or None) => no tiering at all."""
    index, queries = _built("squared_euclidean", False)
    ref = search.knn_search_batch(index, queries, K, BUDGET,
                                  block_rows=BLOCK_ROWS)
    for budget_bytes in (None, 10**9):
        store = TieredPointStore(index, resident_bytes=budget_bytes,
                                 block_rows=BLOCK_ROWS)
        assert store.is_resident
        res = store.search(queries, K, BUDGET)
        _assert_bitwise_equal(res, ref)
        assert store.stats["host_bytes_fetched"] == 0
        assert store.warm_cache()["resident_fast_path"]


def test_block_cache_hits_and_lru_budget():
    index, queries = _built("squared_euclidean", False)
    # Largest budget still below the cold footprint (fast-path threshold):
    # the cache retains most bundles, so a repeat search is mostly hits.
    # Pin blocks 0-1 (2 * bn rows): they can never be evicted, so repeat
    # traffic is guaranteed hits even though full admission over a
    # partial budget makes the unpinned tail a cyclic-LRU worst case.
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS,
                             pinned_row_range=(0, 2 * BLOCK_ROWS))
    assert not store.is_resident
    store.search(queries, K, BUDGET)
    fetched = store.stats["host_bytes_fetched"]
    assert fetched > 0
    store.search(queries, K, BUDGET)
    assert store.stats["cache_hits"] > 0
    info = store.cache_info()
    assert 0 < info["blocks_cached"] <= store.num_blocks
    per_block = max(b["nbytes"] for b in store._cache.values())
    # pinned blocks may legitimately hold the cache over budget; the
    # overshoot is bounded by the pinned set plus one in-use bundle
    assert info["bytes_cached"] <= store.resident_bytes + 3 * per_block

    # A budget below ~one bundle forces refetching on every pass but the
    # cache never durably exceeds the budget by more than the single
    # in-use bundle the eviction loop must keep.
    tiny = TieredPointStore(index, resident_bytes=per_block // 2,
                            block_rows=BLOCK_ROWS)
    tiny.search(queries, K, BUDGET)
    assert tiny._cache_bytes <= tiny.resident_bytes + per_block
    tiny.search(queries, K, BUDGET)
    assert tiny.stats["fetches"] > tiny.num_blocks  # real refetch traffic


def test_warm_cache_populates_up_to_budget():
    index, _ = _built("squared_euclidean", False)
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    out = store.warm_cache()
    assert 0 < out["blocks_cached"] <= store.num_blocks
    assert out["bytes_cached"] <= store.resident_bytes
    # warming is accounting-free: per-query stats stay zero
    assert store.stats["fetches"] == 0 and store.stats["queries"] == 0


def test_fetch_timeout_surfaces_as_fetch_timeout():
    """A wedged host->device copy raises FetchTimeout (containable by the
    service ladder) instead of blocking the search forever."""
    index, queries = _built("squared_euclidean", False)

    calls = {"n": 0}

    def stuck_transfer(tiles):
        calls["n"] += 1
        if calls["n"] == 1:          # one wedged copy, then healthy
            time.sleep(0.5)
        return jax.device_put(tiles)

    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS,
                             transfer=stuck_transfer, fetch_timeout_s=0.05)
    with pytest.raises(FetchTimeout, match="exceeded"):
        store.search(queries, K, BUDGET)
    # the abandoned fetch completes in the background; a retry after the
    # stall clears is served from cache/in-flight futures and succeeds
    time.sleep(0.8)
    res = store.search(queries, K, BUDGET)
    ref = search.knn_search_batch(index, queries, K, BUDGET,
                                  block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)


def test_stage_a_keeps_cold_leaves_on_host():
    """The hot forest's cold leaves stay numpy — nothing in the store
    transfers them wholesale (only as_resident_forest may)."""
    index, queries = _built("squared_euclidean", False)
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    store.search(queries, K, BUDGET)
    for f in cold_point_fields(store._hot):
        assert isinstance(getattr(store._hot, f), np.ndarray), f


# ---------------------------------------------------------------------------
# Knob resolvers (brelint knob-contract surface)
# ---------------------------------------------------------------------------

def test_resolve_resident_bytes_validation():
    assert resolve_resident_bytes(None) is None
    assert resolve_resident_bytes(1) == 1
    assert resolve_resident_bytes(np.int64(1 << 30)) == 1 << 30
    for bad in (0, -1, 1.5, True, "1GB"):
        with pytest.raises(ValueError, match="resident_bytes"):
            resolve_resident_bytes(bad)


def test_resolve_prefetch_depth_validation():
    assert resolve_prefetch_depth(None) == DEFAULT_PREFETCH_DEPTH
    assert resolve_prefetch_depth(1) == 1
    assert resolve_prefetch_depth(64) == 64
    for bad in (0, -2, 65, 2.5, True):
        with pytest.raises(ValueError, match="prefetch_depth"):
            resolve_prefetch_depth(bad)


def test_hot_forest_preserves_calibration_and_statics():
    index, _ = _built("shannon", False)
    index = dataclasses.replace(index, calibration={"marker": 1})
    store = TieredPointStore(index, resident_bytes=_small_budget(index),
                             block_rows=BLOCK_ROWS)
    assert store.calibration == {"marker": 1}
    assert store.family_name == "shannon"
    assert store.storage == index.storage
    assert (store.n, store.d, store.m) == (index.n, index.d, index.m)
