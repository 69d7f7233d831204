"""Int8 storage tier: quantized-vs-fp32 parity across every search path.

The tier's contract (docs/quantization.md): an int8 index's point set IS
its decoded rows ``rows_view()``, and exact-mode search over the int8 tier
returns the EXACT kNN of that point set — identical ids (recall@k = 1.0)
to an fp32 BallForest built over the same decoded rows, in ``knn``,
``knn_search_batch``, ``distributed_knn``, and a mutated
``SegmentedForest``, for all five Bregman families.  The lossy part is the
storage round-off (bounded, applied once at ingest); the search pipeline
itself loses nothing because the Alg.-4 bounds are inflated by the stat
rounding slack and the corner stats are directed-rounded.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import quantize as qz
from repro.core import search
from repro.core.bregman import family_names, get_family
from repro.core.index import (build_index, pad_points, point_fields,
                              slice_points, tombstone_rows)
from repro.core.segments import build_segmented_index

K = 7


def _dataset(family, n=500, d=24, q=6, seed=0):
    fam = get_family(family)
    data = np.asarray(fam.sample(jax.random.PRNGKey(seed), (n, d), scale=1.0))
    queries = np.asarray(
        fam.sample(jax.random.PRNGKey(seed + 1), (q, d), scale=1.0))
    return data, queries, fam


def _decoded_oracle(view, queries, k, fam):
    """Brute-force kNN over the LIVE decoded rows -> original ids per query."""
    xhat = np.asarray(view.rows_view())
    pid = np.asarray(view.point_ids)
    live = pid >= 0
    bf_ids, bf_dists = search.brute_force_knn(xhat[live], queries, k, fam)
    return pid[live][np.asarray(bf_ids)], np.asarray(bf_dists)


def _assert_same_neighbors(ids, oracle_ids, dists=None, oracle_dists=None):
    for qi in range(oracle_ids.shape[0]):
        assert (set(np.asarray(ids[qi]).tolist())
                == set(oracle_ids[qi].tolist())), f"query {qi}"
        if dists is not None:
            np.testing.assert_allclose(
                np.sort(np.asarray(dists[qi])), np.sort(oracle_dists[qi]),
                rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Quantizer properties (the admissibility preconditions)
# ---------------------------------------------------------------------------

def test_stat_quantizer_error_bounds():
    rng = np.random.default_rng(0)
    v = jnp.asarray((rng.normal(size=(512, 8))
                     * rng.lognormal(size=(512, 1))).astype(np.float32))
    c, s, z = qz.quantize_stats(v, "nearest")
    err = np.abs(np.asarray(qz.dequantize_stats(c, s, z)) - np.asarray(v))
    # the |err| <= scale/2 bound _qb_slack relies on (+ float fuzz headroom)
    assert (err <= qz.UB_SLACK * np.asarray(s)[:, None] + 1e-6).all()

    c, s, z = qz.quantize_stats(v, "floor")
    assert (np.asarray(qz.dequantize_stats(c, s, z))
            <= np.asarray(v) + 1e-5).all()
    c, s, z = qz.quantize_stats(v, "ceil")
    assert (np.asarray(qz.dequantize_stats(c, s, z))
            >= np.asarray(v) - 1e-5).all()


def test_constant_rows_quantize_exactly():
    v = jnp.full((4, 6), 3.25, jnp.float32)
    c, s, z = qz.quantize_stats(v)
    assert np.all(np.asarray(s) == 0.0)
    np.testing.assert_array_equal(np.asarray(qz.dequantize_stats(c, s, z)),
                                  np.asarray(v))


@pytest.mark.parametrize("family", ["itakura_saito", "shannon"])
def test_dequantized_rows_stay_in_domain(family):
    fam = get_family(family)
    # rows hugging the domain boundary, where rounding could cross zero
    x = jnp.asarray(np.random.default_rng(0).uniform(
        1e-7, 2.0, size=(64, 16)).astype(np.float32))
    codes, s, z = qz.quantize_rows(x)
    xhat = np.asarray(qz.dequantize_rows(codes, s, z, fam))
    assert (xhat > 0).all()
    assert np.isfinite(np.asarray(fam.phi(jnp.asarray(xhat)))).all()


# ---------------------------------------------------------------------------
# Kernel parity (deterministic — no hypothesis gate; test_kernels.py holds
# the property sweep).  Pallas interpret vs jnp ref vs the direct math.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,q", [(64, 8, 1), (100, 28, 3), (7, 5, 2)])
def test_ub_quant_kernel_matches_ref_and_tracks_fp32(n, m, q):
    from repro.kernels import ref
    from repro.kernels.bregman_ub import bregman_ub_matrix_quant
    rng = np.random.default_rng(0)
    alpha = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    sg = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    a_q, a_s, a_z = qz.quantize_stats(alpha)
    g_q, g_s, g_z = qz.quantize_stats(sg)
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    got = bregman_ub_matrix_quant(a_q, a_s, a_z, g_q, g_s, g_z,
                                  jnp.sum(qc, -1), sd,
                                  block_n=32, block_q=4, interpret=True)
    want = ref.bregman_ub_matrix_quant(a_q, a_s, a_z, g_q, g_s, g_z, qc, sd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # The decoded-codes matrix tracks the fp32 matrix within the stat
    # rounding: alpha contributes scale/2 per subspace (M terms), the
    # Cauchy term scale/2 * sd_i per subspace — the row total of the
    # per-subspace slack _qb_slack spreads over the Alg.-4 components.
    full = ref.bregman_ub_matrix(alpha, sg, qc, sd)
    slack = (m * np.asarray(a_s)[:, None]
             + np.asarray(g_s)[:, None] * np.asarray(jnp.sum(sd, -1))[None, :])
    assert (np.abs(np.asarray(want) - np.asarray(full))
            <= 0.5 * slack + 1e-4).all()


@pytest.mark.parametrize("family", family_names())
@pytest.mark.parametrize("qn,b,d", [(1, 16, 24), (3, 33, 130)])
def test_refine_quant_kernel_parity(family, qn, b, d):
    """Fused dequantize+refine == ref == exact D_f over the decoded rows."""
    from repro.kernels import ref
    from repro.kernels.bregman_dist import bregman_refine_batch_quant
    fam = get_family(family)
    rows = fam.sample(jax.random.PRNGKey(1), (qn * b, d)).reshape(qn, b, d)
    codes, scale, zp = qz.quantize_rows(rows.reshape(-1, d))
    codes = codes.reshape(qn, b, d)
    scale, zp = scale.reshape(qn, b), zp.reshape(qn, b)
    ys = fam.sample(jax.random.PRNGKey(2), (qn, d))
    grad = fam.phi_prime(ys)
    c_y = jnp.sum(ys * grad, -1) - fam.f(ys)
    got = bregman_refine_batch_quant(codes, scale, zp, grad, c_y, family,
                                     block_b=16, block_d=64, interpret=True)
    want = ref.bregman_refine_batch_quant(codes, scale, zp, grad, c_y, family)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # exact distances over the decoded point set (the tier's contract)
    xhat = qz.dequantize_rows(
        codes.reshape(-1, d), scale.reshape(-1), zp.reshape(-1),
        fam).reshape(qn, b, d)
    direct = jax.vmap(lambda x, y: fam.distance(x, y[None]))(xhat, ys)
    np.testing.assert_allclose(np.asarray(got), np.asarray(direct),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,m,q", [(64, 8, 1), (100, 28, 3), (7, 5, 2)])
def test_prune_quant_kernel_matches_ref_and_is_conservative(n, m, q):
    """Fused int8 admit mask == ref; decoded-corner admits ⊇ fp32 admits."""
    from repro.kernels import ref
    from repro.kernels.bregman_prune import bregman_prune_mask_quant
    rng = np.random.default_rng(0)
    amin = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    gmax = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    a_q, a_s, a_z = qz.quantize_stats(amin, "floor")
    g_q, g_s, g_z = qz.quantize_stats(gmax, "ceil")
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    qb = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    got = bregman_prune_mask_quant(a_q, a_s, a_z, g_q, g_s, g_z, qc, sd, qb,
                                   block_n=32, block_q=4, interpret=True)
    want = ref.bregman_prune_mask_quant(a_q, a_s, a_z, g_q, g_s, g_z,
                                        qc, sd, qb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Directed rounding makes the decoded test CONSERVATIVE: every pair
    # the true-corner test admits, the decoded-corner test admits too.
    full = ref.bregman_prune_mask(amin, gmax, qc, sd, qb)
    assert (np.asarray(got) >= np.asarray(full)).all()


# ---------------------------------------------------------------------------
# Parity: single-query, batched, approximate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", family_names())
def test_quantized_matches_fp32_index_over_decoded_points(family):
    """recall@k == 1.0 vs the fp32 index on the same stored point set."""
    data, queries, fam = _dataset(family)
    qidx = build_index(data, family, m=4, num_clusters=16, seed=0,
                       quantize=True)
    assert qidx.storage == "int8" and qidx.data.dtype == jnp.int8

    # fp32 index over the decoded rows, restored to ORIGINAL id order so
    # both builds cluster the same input with the same seed.
    xhat = np.asarray(qidx.rows_view())
    restore = np.argsort(np.asarray(qidx.point_ids))
    fidx = build_index(xhat[restore], family, m=4, num_clusters=16, seed=0)

    res_q = search.knn_batch(qidx, queries, K)
    res_f = search.knn_batch(fidx, queries, K)
    assert bool(jnp.all(res_q.exact)) and bool(jnp.all(res_f.exact))
    _assert_same_neighbors(res_q.ids, np.asarray(res_f.ids),
                           res_q.dists, np.asarray(res_f.dists))

    oracle_ids, oracle_dists = _decoded_oracle(qidx, queries, K, fam)
    _assert_same_neighbors(res_q.ids, oracle_ids, res_q.dists, oracle_dists)

    # single-query path agrees with the batched path
    for qi in range(queries.shape[0]):
        single = search.knn(qidx, queries[qi], K)
        assert bool(single.exact)
        assert (set(np.asarray(single.ids).tolist())
                == set(oracle_ids[qi].tolist()))


@pytest.mark.parametrize("family", ["squared_euclidean", "burg"])
def test_quantized_approx_mode_runs_and_single_matches_batch(family):
    data, queries, fam = _dataset(family, n=700, seed=3)
    qidx = build_index(data, family, m=4, num_clusters=16, seed=0,
                       quantize=True)
    res = search.knn_batch(qidx, queries, K, approx_p=0.8)
    for qi in range(queries.shape[0]):
        single = search.knn(qidx, queries[qi], K, approx_p=0.8)
        assert (int(res.num_candidates[qi]) == int(single.num_candidates))
        if bool(res.exact[qi]) and bool(single.exact):
            assert (set(np.asarray(res.ids[qi]).tolist())
                    == set(np.asarray(single.ids).tolist()))


def test_quantized_streaming_blocks_match_single_shot():
    data, queries, fam = _dataset("exponential", n=600)
    qidx = build_index(data, "exponential", m=4, num_clusters=16, seed=0,
                       quantize=True)
    full = search.knn_batch(qidx, queries, 5)
    stream = search.knn_batch(qidx, queries, 5, block_rows=64)
    np.testing.assert_array_equal(np.asarray(full.ids),
                                  np.asarray(stream.ids))
    np.testing.assert_array_equal(np.asarray(full.num_candidates),
                                  np.asarray(stream.num_candidates))


def test_quantized_budget_escalation_stays_exact():
    data, queries, fam = _dataset("squared_euclidean", n=400)
    qidx = build_index(data, "squared_euclidean", m=4, num_clusters=8,
                       seed=0, quantize=True)
    res = search.knn_batch(qidx, queries, 5, budget=8)
    assert bool(jnp.all(res.exact))
    oracle_ids, _ = _decoded_oracle(qidx, queries, 5, fam)
    _assert_same_neighbors(res.ids, oracle_ids)


# ---------------------------------------------------------------------------
# Parity: distributed + segmented
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", family_names())
def test_quantized_distributed_matches_batched(family):
    from repro.dist.knn import distributed_knn, shard_index
    data, queries, fam = _dataset(family, n=300, d=16, q=4)
    qidx = build_index(data, family, m=4, num_clusters=8, seed=0,
                       quantize=True)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    sharded = shard_index(qidx, mesh, axis="data")
    res = distributed_knn(sharded, queries, family=family, k=5, budget=64)
    local = search.knn_batch(qidx, queries, 5)
    assert bool(jnp.all(res.exact))
    np.testing.assert_array_equal(
        np.sort(np.asarray(res.ids), axis=1),
        np.sort(np.asarray(local.ids), axis=1))
    np.testing.assert_allclose(
        np.sort(np.asarray(res.dists), axis=1),
        np.sort(np.asarray(local.dists), axis=1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", family_names())
def test_quantized_segmented_mutations_stay_exact(family):
    data, queries, fam = _dataset(family, n=400, seed=2)
    sf = build_segmented_index(data, family, m=4, num_clusters=16,
                               quantize=True)
    assert sf.storage == "int8"
    extra = np.asarray(
        fam.sample(jax.random.PRNGKey(9), (50, data.shape[1]), scale=1.0))
    sf.insert(extra, auto_compact=False)
    sf.delete(np.arange(0, 30), auto_compact=False)

    res = search.knn_batch(sf, queries, K)
    assert bool(jnp.all(res.exact))
    oracle_ids, oracle_dists = _decoded_oracle(sf.view(), queries, K, fam)
    _assert_same_neighbors(res.ids, oracle_ids, res.dists, oracle_dists)
    # deleted ids can never surface
    assert not (np.asarray(res.ids) < 30).any()


def test_quantized_merge_compaction_preserves_points_bit_exactly():
    data, queries, fam = _dataset("squared_euclidean", n=400)
    sf = build_segmented_index(data, "squared_euclidean", m=4,
                               num_clusters=16, quantize=True)
    extra = np.asarray(fam.sample(jax.random.PRNGKey(9), (40, 24), scale=1.0))
    sf.insert(extra, auto_compact=False)
    sf.delete(np.arange(10), auto_compact=False)
    view = sf.view()
    before = {int(i): row for i, row in
              zip(np.asarray(view.point_ids), np.asarray(view.rows_view()),
                  strict=True)
              if i >= 0}
    oracle_ids, _ = _decoded_oracle(view, queries, K, fam)

    assert sf.compact(mode="merge") == "merge"
    view2 = sf.view()
    for i, row in zip(np.asarray(view2.point_ids),
                      np.asarray(view2.rows_view()), strict=True):
        assert np.array_equal(before[int(i)], row)
    res = search.knn_batch(sf, queries, K)
    _assert_same_neighbors(res.ids, oracle_ids)


def test_quantized_rebuild_compaction_stays_exact_over_new_codes():
    data, queries, fam = _dataset("itakura_saito", n=300)
    sf = build_segmented_index(data, "itakura_saito", m=4, num_clusters=16,
                               quantize=True)
    sf.delete(np.arange(20), auto_compact=False)
    assert sf.compact(mode="rebuild") == "rebuild"
    assert sf.storage == "int8"
    res = search.knn_batch(sf, queries, K)
    assert bool(jnp.all(res.exact))
    oracle_ids, oracle_dists = _decoded_oracle(sf.view(), queries, K, fam)
    _assert_same_neighbors(res.ids, oracle_ids, res.dists, oracle_dists)


# ---------------------------------------------------------------------------
# Point-major plumbing: pad / slice / tombstone with the quant fields
# ---------------------------------------------------------------------------

def test_quantized_pad_slice_tombstone_roundtrip():
    data, queries, fam = _dataset("squared_euclidean", n=100)
    qidx = build_index(data, "squared_euclidean", m=4, num_clusters=8,
                       seed=0, quantize=True)
    assert len(point_fields(qidx)) == len(point_fields("f32")) + 10

    padded = pad_points(qidx, 64)
    assert padded.n == 128
    assert padded.data.dtype == jnp.int8
    # padded rows are search-inert and decode to the domain-safe ones-row
    np.testing.assert_array_equal(
        np.asarray(padded.point_ids[100:]), -1)
    np.testing.assert_array_equal(
        np.asarray(padded.rows_view())[100:], 1.0)
    back = slice_points(padded, 0, 100)
    for f in point_fields(qidx):
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(qidx, f)))

    dead = np.zeros(100, bool)
    dead[:7] = True
    stoned = tombstone_rows(qidx, jnp.asarray(dead))
    res = search.knn_batch(stoned, queries, 5)
    gone = set(np.asarray(qidx.point_ids)[:7].tolist())
    assert not (np.isin(np.asarray(res.ids), list(gone))).any()
