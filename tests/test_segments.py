"""Mutation invariants of the segmented BallForest (core/segments.py).

The contract under test: a forest with live append segments and tombstones
returns BIT-IDENTICAL kNN results to a freshly rebuilt forest over the
same live points — in ``knn``, ``knn_search_batch``, and
``distributed_knn`` (1x1 mesh) — with ``exact=True`` staying truthful;
deleted ids never surface in any path; ``pad_points``/``slice_points``
round-trip a mutated view; and compaction (merge or rebuild) preserves
results and original ids.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bregman import family_names, get_family
from repro.core.index import (POINT_FIELDS, build_index, concat_points,
                              pad_points, slice_points)
from repro.core.partition import CostModel, decide_compaction
from repro.core.segments import SegmentedForest, build_segmented_index
from repro.core import search
from repro.dist import knn as dknn
from repro.dist.sharding import make_mesh

FAMILIES = family_names()
N0, N_ADD, D, M, K = 256, 44, 16, 4, 6
DELETED = (3, 7, 270)            # two sealed-segment ids, one appended id


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


def _mutated_setup(family, seed=0):
    """A segmented forest after insert+delete, plus the fresh-rebuild ref.

    Returns (segmented, fresh_forest, orig_ids, queries) where ``orig_ids``
    maps the fresh forest's input positions back to original ids.
    """
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(seed), (N0 + N_ADD, D)))
    queries = jnp.asarray(
        np.asarray(fam.sample(jax.random.PRNGKey(seed + 1), (5, D))))
    sf = build_segmented_index(data[:N0], family, m=M, num_clusters=16,
                               seed=seed)
    ids = sf.insert(data[N0:], auto_compact=False)
    assert ids.tolist() == list(range(N0, N0 + N_ADD))
    assert sf.delete(DELETED, auto_compact=False) == len(DELETED)

    live_mask = np.ones(N0 + N_ADD, bool)
    live_mask[list(DELETED)] = False
    fresh = build_index(data[live_mask], family, m=M, num_clusters=16,
                        seed=seed)
    return sf, fresh, np.arange(N0 + N_ADD)[live_mask], queries


def _fresh_result_in_orig_ids(res, orig_ids):
    return res._replace(ids=jnp.asarray(orig_ids)[res.ids])


@pytest.mark.parametrize("family", FAMILIES)
def test_exact_bit_identical_to_fresh_rebuild(family):
    """Acceptance: batched + single-query results == fresh rebuild, bitwise."""
    sf, fresh, orig_ids, queries = _mutated_setup(family)
    assert sf.live_n == N0 + N_ADD - len(DELETED)

    # One budget (= live count) for both sides: only live rows are ever
    # admitted, so the union always fits, and the refine runs the same
    # static shape on both indexes (bitwise-identical reduction order).
    budget = sf.live_n
    res = search.knn_search_batch(sf, queries, K, budget)
    ref = _fresh_result_in_orig_ids(
        search.knn_search_batch(fresh, queries, K, budget), orig_ids)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    assert bool(jnp.all(res.exact)) and bool(jnp.all(ref.exact))

    single = search.knn(sf, queries[0], K, budget)
    single_ref = search.knn(fresh, queries[0], K, budget)
    np.testing.assert_array_equal(
        np.asarray(single.ids),
        np.asarray(orig_ids)[np.asarray(single_ref.ids)])
    np.testing.assert_array_equal(np.asarray(single.dists),
                                  np.asarray(single_ref.dists))
    assert bool(single.exact) and bool(single_ref.exact)


@pytest.mark.parametrize("family", FAMILIES)
def test_approx_mode_on_mutated_forest(family):
    """§8 approx on a mutated forest: batch==single parity on the same
    index, only live points, true distances, sane recall."""
    sf, fresh, orig_ids, queries = _mutated_setup(family)
    fam = sf.family
    p = 0.9
    res = search.knn_batch(sf, queries, K, approx_p=p)
    for qi in range(queries.shape[0]):
        single = search.knn(sf, queries[qi], K, approx_p=p)
        assert int(res.num_candidates[qi]) == int(single.num_candidates)
        if bool(res.exact[qi]) and bool(single.exact):
            assert (set(np.asarray(res.ids[qi]).tolist())
                    == set(np.asarray(single.ids).tolist()))
    ids = np.asarray(res.ids)
    assert not np.isin(ids, list(DELETED)).any()
    # returned distances are the EXACT distances of the returned live points
    view = sf.view()
    id_to_row = {int(i): r for r, i in
                 enumerate(np.asarray(view.point_ids)) if int(i) >= 0}
    for qi in range(queries.shape[0]):
        rows = np.stack([np.asarray(view.data)[id_to_row[int(i)]]
                         for i in ids[qi]])
        true_d = np.asarray(fam.distance(jnp.asarray(rows), queries[qi][None]))
        np.testing.assert_allclose(np.asarray(res.dists[qi]), true_d,
                                   rtol=1e-4, atol=1e-4)
    # recall floor vs brute force over live points (p=0.9 guarantee)
    live = np.asarray(view.data)[np.asarray(view.point_ids) >= 0]
    _, bf_d = search.brute_force_knn(live, queries, K, fam)
    hits = sum(
        len(set(np.round(np.asarray(res.dists[qi]), 4).tolist())
            & set(np.round(np.asarray(bf_d[qi]), 4).tolist()))
        for qi in range(queries.shape[0]))
    assert hits >= int(0.5 * K * queries.shape[0])


def test_distributed_1x1_bit_identical(mesh):
    family = "itakura_saito"
    sf, fresh, orig_ids, queries = _mutated_setup(family)
    sharded = dknn.shard_index(sf, mesh)
    assert sharded.global_live_n == sf.live_n
    budget = sf.live_n                 # same refine shape on both sides
    res = dknn.distributed_knn(sharded, queries, family=family, k=K,
                               budget=budget, mesh=mesh)
    ref = _fresh_result_in_orig_ids(
        search.knn_search_batch(fresh, queries, K, budget), orig_ids)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    assert bool(jnp.all(res.exact))
    assert not np.isin(np.asarray(res.ids), list(DELETED)).any()


def test_deleted_true_neighbors_never_surface_any_path(mesh):
    """Delete a query's entire true top-k; every path must return the next
    tier, never a tombstoned id, and stay exact."""
    family = "squared_euclidean"
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(2), (N0 + N_ADD, D)))
    queries = jnp.asarray(
        np.asarray(fam.sample(jax.random.PRNGKey(3), (3, D))))
    sf = build_segmented_index(data[:N0], family, m=M, num_clusters=16,
                               seed=0)
    sf.insert(data[N0:], auto_compact=False)
    top_ids, _ = search.brute_force_knn(data, queries[0], K, fam)
    doomed = np.asarray(top_ids).tolist()
    sf.delete(doomed, auto_compact=False)

    live_mask = np.ones(N0 + N_ADD, bool)
    live_mask[doomed] = False
    bf_ids, bf_d = search.brute_force_knn(data[live_mask], queries, K, fam)
    bf_ids = np.arange(N0 + N_ADD)[live_mask][np.asarray(bf_ids)]

    batch = search.knn_batch(sf, queries, K)
    single = search.knn(sf, queries[0], K)
    sharded = dknn.shard_index(sf, mesh)
    dist = dknn.distributed_knn(sharded, queries, family=family, k=K,
                                budget=search.default_budget(sf.view(), K),
                                mesh=mesh)
    for res_ids in (np.asarray(batch.ids), np.asarray(single.ids)[None],
                    np.asarray(dist.ids)):
        assert not np.isin(res_ids, doomed).any()
    assert bool(jnp.all(batch.exact)) and bool(jnp.all(dist.exact))
    np.testing.assert_array_equal(np.asarray(batch.ids), bf_ids)
    np.testing.assert_allclose(np.sort(np.asarray(batch.dists), axis=1),
                               np.sort(np.asarray(bf_d), axis=1),
                               rtol=1e-5, atol=1e-5)


def test_exact_flag_truthful_under_tiny_budget():
    """Tombstones must not be counted as candidates: the retry ladder
    converges and the final exact flag is truthful."""
    sf, fresh, orig_ids, queries = _mutated_setup("itakura_saito", seed=4)
    res = search.knn_batch(sf, queries, K, budget=K)
    assert bool(jnp.all(res.exact))
    ref = _fresh_result_in_orig_ids(
        search.knn_search_batch(fresh, queries, K, fresh.n), orig_ids)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))


def test_budget_cap_escalation_skips_tombstones():
    """The ladder's retry must mask dead rows: from a starved budget on a
    mutated forest it ends exact, and no deleted id (or -1) may surface."""
    sf, fresh, orig_ids, queries = _mutated_setup("squared_euclidean",
                                                  seed=6)
    res = search.knn_batch(sf, queries, K, budget=K)
    assert bool(jnp.all(res.exact))
    ids = np.asarray(res.ids)
    assert not np.isin(ids, list(DELETED)).any() and (ids >= 0).all()
    view = sf.view()
    live = np.asarray(view.data)[np.asarray(view.point_ids) >= 0]
    _, bf_d = search.brute_force_knn(live, queries, K, sf.family)
    np.testing.assert_allclose(np.sort(np.asarray(res.dists), axis=1),
                               np.sort(np.asarray(bf_d), axis=1),
                               rtol=1e-5, atol=1e-5)


def test_budget_exceeding_n_is_clamped():
    """A pinned budget can outlive a compaction that shrank the index
    (serve-side contract); the host wrappers must clamp, not crash."""
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(9), (64, D)))
    sf = build_segmented_index(data, "squared_euclidean", m=M,
                               num_clusters=4, seed=0)
    sf.delete(range(40), auto_compact=False)
    sf.compact("merge")                       # physical n shrinks to 24
    res = search.knn_batch(sf, jnp.asarray(data[40:43]), 3, budget=512)
    np.testing.assert_array_equal(np.asarray(res.ids)[:, 0],
                                  np.arange(40, 43))
    single = search.knn(sf, data[41], 3, budget=512)
    assert int(single.ids[0]) == 41


def test_pad_slice_roundtrip_with_segments_and_tombstones():
    sf, fresh, orig_ids, queries = _mutated_setup("exponential")
    view = sf.view()
    padded = pad_points(view, 7)
    assert padded.n % 7 == 0
    res = search.knn_search_batch(padded, queries, K, view.n)
    ref = search.knn_search_batch(view, queries, K, view.n)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    h = padded.n // 2
    halves = [slice_points(padded, 0, h), slice_points(padded, h,
                                                       padded.n - h)]
    rt = concat_points(halves)
    for f in POINT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(rt, f)),
                                      np.asarray(getattr(padded, f)))


def test_compact_merge_and_rebuild_preserve_results_and_ids():
    family = "shannon"
    sf, fresh, orig_ids, queries = _mutated_setup(family)
    budget = sf.live_n                 # compaction shrinks n to live_n, so
    before = search.knn_search_batch(sf, queries, K, budget)  # shapes match

    merged = _mutated_setup(family)[0]
    assert merged.compact("merge") == "merge"
    assert not merged.segments and merged.n == merged.live_n
    after_m = search.knn_search_batch(merged, queries, K, budget)
    np.testing.assert_array_equal(np.asarray(after_m.ids),
                                  np.asarray(before.ids))
    np.testing.assert_array_equal(np.asarray(after_m.dists),
                                  np.asarray(before.dists))

    rebuilt = _mutated_setup(family)[0]
    assert rebuilt.compact("rebuild") == "rebuild"
    assert not rebuilt.segments and rebuilt.n == rebuilt.live_n
    after_r = search.knn_search_batch(rebuilt, queries, K, budget)
    np.testing.assert_array_equal(np.asarray(after_r.ids),
                                  np.asarray(before.ids))
    np.testing.assert_array_equal(np.asarray(after_r.dists),
                                  np.asarray(before.dists))
    with pytest.raises(ValueError, match="unknown compaction mode"):
        _mutated_setup(family)[0].compact("defrag")


def test_auto_compact_on_threshold():
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(5), (200, D)))
    sf = build_segmented_index(data[:100], "squared_euclidean", m=M,
                               num_clusters=8, seed=0,
                               compact_threshold=0.25)
    sf.insert(data[100:110], auto_compact=True)      # 10% — below threshold
    assert len(sf.segments) == 1
    sf.insert(data[110:160], auto_compact=True)      # ~60% appended — crosses
    assert not sf.segments and sf.n == sf.live_n == 160
    res = search.knn_batch(sf, jnp.asarray(data[:4]), 1)
    np.testing.assert_array_equal(np.asarray(res.ids).ravel(),
                                  np.arange(4))


def test_decide_compaction_cost_rule():
    model = CostModel(a=1.0, alpha=0.5, beta=1e-4, n=4096, d=64)
    # fresh index, nothing stale -> merge is free, rebuild never wins
    assert decide_compaction(model, 4, stale_fraction=0.0) == "merge"
    # hugely stale + generous amortization window -> rebuild pays off
    assert decide_compaction(model, 4, stale_fraction=50.0,
                             amortize_queries=10**9) == "rebuild"
    # the rule is monotone in stale_fraction
    flips = [decide_compaction(model, 4, stale_fraction=s,
                               amortize_queries=10**9)
             for s in (0.0, 0.5, 5.0, 50.0)]
    assert flips == sorted(flips, key=lambda x: x == "rebuild")


def test_k_validated_against_live_count():
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(6), (32, D)))
    sf = build_segmented_index(data, "squared_euclidean", m=M,
                               num_clusters=4, seed=0)
    sf.delete(range(16), auto_compact=False)
    with pytest.raises(ValueError, match="live point count"):
        search.knn_batch(sf, jnp.asarray(data[:2]), 17)
    with pytest.raises(ValueError, match="live"):
        dknn.distributed_knn(
            dknn.shard_index(sf, make_mesh((1,), ("data",))),
            jnp.asarray(data[:2]), family="squared_euclidean", k=17,
            budget=32)


def test_delete_everything_then_reinsert():
    """Full eviction (the rolled-over-corpus flow) must not crash the
    auto-compaction; a later insert revives the index."""
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(10), (48, D)))
    sf = build_segmented_index(data[:32], "squared_euclidean", m=M,
                               num_clusters=4, seed=0)
    assert sf.delete(range(32)) == 32     # auto-compact fires on empty
    assert sf.live_n == 0 and sf.n == 0 and not sf.segments
    with pytest.raises(ValueError, match="live point count"):
        search.knn_batch(sf, jnp.asarray(data[:1]), 1)
    ids = sf.insert(data[32:], auto_compact=False)
    assert ids.tolist() == list(range(32, 48))
    res = search.knn_batch(sf, jnp.asarray(data[32:35]), 1)
    np.testing.assert_array_equal(np.asarray(res.ids).ravel(), ids[:3])


def test_insert_rejects_bad_shape():
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(7), (64, D)))
    sf = build_segmented_index(data, "squared_euclidean", m=M,
                               num_clusters=4, seed=0)
    with pytest.raises(ValueError, match="expected"):
        sf.insert(np.ones((3, D + 1), np.float32))
    with pytest.raises(ValueError, match="expected"):
        sf.insert(np.ones((D,), np.float32))


def test_datastore_grow_evict_contract():
    from repro.serve.knnlm import Datastore, KNNLMHook

    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(8), (220, D)))
    store = Datastore(
        index=build_index(data[:200], "squared_euclidean", m=M,
                          num_clusters=8, seed=0),
        next_tokens=np.arange(200, dtype=np.int32) % 32, hidden_dim=D)
    hook = KNNLMHook(store=store, k=4, lam=0.5)
    logits = jnp.zeros((3, 32))
    hook(logits, jnp.asarray(data[:3]))

    new_ids = store.grow(data[200:220], np.full(20, 7, np.int32))
    assert isinstance(store.index, SegmentedForest)
    assert store.next_tokens.shape == (220,) and store.version == 1
    # the new keys are immediately retrievable and resolve to their token
    res = search.knn_batch(store.index, jnp.asarray(data[200:203]), 1)
    np.testing.assert_array_equal(np.asarray(res.ids).ravel(),
                                  new_ids[:3])
    out = hook(logits, jnp.asarray(data[200:203]))
    assert out.shape == (3, 32)
    # mixture must now lean on token 7 for an exact self-hit
    assert int(jnp.argmax(out[0])) == 7

    assert store.evict(new_ids) == 20 and store.version == 2
    res2 = search.knn_batch(store.index, jnp.asarray(data[200:203]), 1)
    assert not np.isin(np.asarray(res2.ids), new_ids).any()
    with pytest.raises(ValueError, match="one next-token per key"):
        store.grow(data[:2], np.zeros(3, np.int32))
    with pytest.raises(ValueError, match="expected"):
        store.grow(np.ones((2, D + 2), np.float32), np.zeros(2, np.int32))

    # evicting below k must degrade the hook to the pure LM distribution,
    # not raise mid-decode; auto_compact=False keeps eviction tombstone-only
    store.auto_compact = False
    store.evict(np.arange(200 - hook.k + 1))
    assert store.index.live_n < hook.k
    assert isinstance(store.index, SegmentedForest) and store.index.n == 220
    out_low = hook(logits, jnp.asarray(data[:3]))
    np.testing.assert_allclose(np.asarray(out_low), np.asarray(logits),
                               atol=0)
