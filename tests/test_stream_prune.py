"""Streaming prune+compact parity + block-envelope invariants.

The contract under test: the streamed, envelope-gated prune+compact scan
(``core/search._stream_prune_compact``) is BIT-IDENTICAL to the
materialized mask/cumsum reference (``knn_search_batch_reference``) on
every output field, across all five Bregman families x {exact, approx} x
{fp32, int8} x {BallForest, mutated SegmentedForest, 1x1-mesh
distributed}; block envelopes always dominate their rows' per-point
corners (including after tombstone and merge); and the envelope gate
actually skips (block, query) tiles on clustered data.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bregman import family_names, get_family
from repro.core.index import (ENV_BLOCK_ROWS, build_index, corner_envelopes,
                              pad_points, tombstone_rows)
from repro.core.quantize import decoded_corner_tables
from repro.core.segments import build_segmented_index
from repro.core import search
from repro.dist import knn as dknn
from repro.dist.sharding import make_mesh

N, D, M, Q, K = 420, 16, 4, 4, 5
BLOCK_ROWS = 96          # multi-block AND misaligned with ENV_BLOCK_ROWS
P_APPROX = 0.8


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


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_stream_matches_reference_ballforest(family, quantize):
    """Exact + approx, fp32 + int8: streamed == mask/cumsum, bit for bit."""
    index, queries = _built(family, quantize)
    budget = 64
    res = search.knn_search_batch(index, queries, K, budget,
                                  block_rows=BLOCK_ROWS)
    ref = search.knn_search_batch_reference(index, queries, K, budget,
                                            block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)

    res_a = search.knn_search_batch_approx(index, queries, K, budget,
                                           jnp.float32(P_APPROX),
                                           block_rows=BLOCK_ROWS)
    ref_a = search.knn_search_batch_reference(index, queries, K, budget,
                                              p_guarantee=P_APPROX,
                                              block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_a, ref_a)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_stream_matches_reference_mutated_segmented(family, quantize):
    """Same parity over a segmented index with appends + tombstones."""
    sf = _mutated(family, quantize)
    fam = get_family(family)
    queries = jnp.asarray(np.asarray(
        fam.sample(jax.random.PRNGKey(3), (Q, D), scale=1.0)))
    budget = sf.live_n
    res = search.knn_search_batch(sf, queries, K, budget,
                                  block_rows=BLOCK_ROWS)
    ref = search.knn_search_batch_reference(sf, queries, K, budget,
                                            block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)
    assert bool(jnp.all(res.exact))
    # tombstoned ids can never surface through the streamed compaction
    gone = {1, 5, N - 30}
    assert not gone & set(np.asarray(res.ids).ravel().tolist())

    res_a = search.knn_search_batch_approx(sf, queries, K, budget,
                                           jnp.float32(P_APPROX),
                                           block_rows=BLOCK_ROWS)
    ref_a = search.knn_search_batch_reference(sf, queries, K, budget,
                                              p_guarantee=P_APPROX,
                                              block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_a, ref_a)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_stream_matches_reference_distributed_1x1(family, quantize):
    """1x1-mesh distributed == single-host streamed == reference."""
    index, queries = _built(family, quantize)
    budget = index.n          # union always fits -> no retry, one program
    mesh = make_mesh((1,), ("data",))
    sharded = dknn.shard_index(index, mesh)
    res_d = dknn.distributed_knn(sharded, queries, family=family, k=K,
                                 budget=budget, block_rows=BLOCK_ROWS)
    ref = search.knn_search_batch_reference(index, queries, K, budget,
                                            block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_d, ref)

    res_da = dknn.distributed_knn(sharded, queries, family=family, k=K,
                                  budget=budget, approx_p=P_APPROX,
                                  block_rows=BLOCK_ROWS)
    ref_a = search.knn_search_batch_reference(index, queries, K, budget,
                                              p_guarantee=P_APPROX,
                                              block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(res_da, ref_a)


# ---------------------------------------------------------------------------
# Envelope invariants
# ---------------------------------------------------------------------------

def _assert_envelopes_dominate(forest):
    """Every row's decoded corner is dominated by its block's envelope."""
    amin, gmax = (np.asarray(t) for t in decoded_corner_tables(forest))
    ea = np.asarray(forest.env_alpha_min)
    eg = np.asarray(forest.env_sqrt_gamma_max)
    n = amin.shape[0]
    assert ea.shape[0] == max(-(-n // ENV_BLOCK_ROWS), 1)
    grp = np.arange(n) // ENV_BLOCK_ROWS
    assert (ea[grp] <= amin).all()
    assert (eg[grp] >= gmax).all()


@pytest.mark.parametrize("quantize", [False, True])
def test_envelopes_dominate_after_mutations(quantize):
    sf = _mutated("squared_euclidean", quantize)
    for seg in [sf.main] + sf.segments:
        _assert_envelopes_dominate(seg)
    view = sf.view()
    _assert_envelopes_dominate(view)
    # padding appends inert envelope rows; domination must survive
    _assert_envelopes_dominate(pad_points(view, 7))
    # tombstoning leaves the tables conservatively loose, never invalid
    dead = np.zeros(view.n, bool)
    dead[::3] = True
    _assert_envelopes_dominate(tombstone_rows(view, jnp.asarray(dead)))
    # merge compaction refits them exactly
    sf.compact("merge")
    _assert_envelopes_dominate(sf.view())


def test_envelope_property_random_blocks():
    """Hypothesis sweep: corner_envelopes dominates at any n/M alignment."""
    hyp = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(n=st.integers(1, 700), m=st.integers(1, 6),
               seed=st.integers(0, 1000))
    def prop(n, m, seed):
        rng = np.random.default_rng(seed)
        amin = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        gmax = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
        ea, eg = corner_envelopes(amin, gmax)
        grp = np.arange(n) // ENV_BLOCK_ROWS
        assert (np.asarray(ea)[grp] <= np.asarray(amin)).all()
        assert (np.asarray(eg)[grp] >= np.asarray(gmax)).all()

    prop()


def test_missing_envelopes_disable_skipping_for_every_block():
    """env=None fallback must cover ALL blocks, not just block 0.

    Regression: a hand-assembled forest without envelope tables once got a
    1-row always-admit fallback, so blocks past the first sliced into the
    inert padding and were wrongly skipped (wrong ids with exact=True).
    """
    import dataclasses
    rng = np.random.default_rng(0)
    data = rng.normal(size=(2000, 24)).astype(np.float32)
    index = build_index(data, "squared_euclidean", m=4, num_clusters=16,
                        seed=0)
    bare = dataclasses.replace(index, env_alpha_min=None,
                               env_sqrt_gamma_max=None)
    queries = jnp.asarray(data[1800:1806] + 0.01)   # rows far past block 0
    res = search.knn_search_batch(bare, queries, 5, 2000, block_rows=512)
    ref = search.knn_search_batch_reference(index, queries, 5, 2000,
                                            block_rows=512)
    _assert_bitwise_equal(res, ref)


def test_block_skip_rate_positive_on_clustered_data():
    """Well-separated blobs: whole blocks must be pruned at envelope level."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(1024, 32)).astype(np.float32)
    b = rng.normal(size=(1024, 32)).astype(np.float32) + 50.0
    index = build_index(np.concatenate([a, b]), "squared_euclidean", m=4,
                        num_clusters=16, seed=0)
    queries = jnp.asarray(a[:8] + 0.01)
    res, stats = search.knn_search_batch_stats(index, queries, 5, 1024,
                                               block_rows=ENV_BLOCK_ROWS)
    assert bool(jnp.all(res.exact))
    assert stats["num_blocks"] == index.n // ENV_BLOCK_ROWS
    assert stats["block_skip_rate"] > 0.0
    # the skipped tiles must not change results
    ref = search.knn_search_batch_reference(index, queries, 5, 1024,
                                            block_rows=ENV_BLOCK_ROWS)
    _assert_bitwise_equal(res, ref)


# ---------------------------------------------------------------------------
# block_rows knob plumbing
# ---------------------------------------------------------------------------

def test_resolve_block_rows_validation():
    assert search.resolve_block_rows(None, 100) == search.DEFAULT_BLOCK_ROWS
    assert search.resolve_block_rows(64, 100) == 64
    assert search.resolve_block_rows(10_000, 100) == 10_000   # clamped later
    with pytest.raises(ValueError, match="block_rows"):
        search.resolve_block_rows(0, 100)
    with pytest.raises(ValueError, match="block_rows"):
        search.resolve_block_rows(-64, 100)
    with pytest.raises(ValueError, match="block_rows"):
        search.resolve_block_rows(4.5, 100)
    with pytest.raises(ValueError, match="empty"):
        search.resolve_block_rows(64, 0)


def test_resolve_block_rows_empty_index_fires_on_default_path():
    """Regression: the n < 1 guard must fire when block_rows is None too.

    It used to sit below the ``block_rows is None`` early-return, so the
    default-knob path (the common one) sailed past an empty index and died
    later inside the scan with an opaque shape error.
    """
    with pytest.raises(ValueError, match="empty"):
        search.resolve_block_rows(None, 0)
    with pytest.raises(ValueError, match="empty"):
        search.resolve_block_rows(None, -3, q=4, storage="f32")


def test_resolve_env_block_rows_validation():
    eb = ENV_BLOCK_ROWS
    assert search.resolve_env_block_rows(None) == eb
    assert search.resolve_env_block_rows(eb) == eb
    assert search.resolve_env_block_rows(4 * eb) == 4 * eb
    for bad in (0, eb // 2, eb + 1, 3 * eb // 2, True):
        with pytest.raises(ValueError, match="env_block_rows"):
            search.resolve_env_block_rows(bad)


def test_knn_batch_and_hook_forward_block_rows(monkeypatch):
    """The knob reaches the jit core from knn_batch and from KNNLMHook."""
    from repro.serve.knnlm import Datastore, KNNLMHook
    index, queries = _built("squared_euclidean", False)

    seen = []
    real = search._knn_search_batch_jit

    def spy(index, ys, k, budget, block_rows, env_block_rows=None):
        seen.append(block_rows)
        return real(index, ys, k, budget, block_rows, env_block_rows)

    monkeypatch.setattr(search, "_knn_search_batch_jit", spy)
    search.knn_batch(index, queries, K, budget=64, block_rows=128)
    assert seen[-1] == 128

    store = Datastore(index=index,
                      next_tokens=np.arange(N, dtype=np.int32) % 32,
                      hidden_dim=D, block_rows=96)
    hook = KNNLMHook(store=store, k=K, lam=0.5)
    hook(jnp.zeros((2, 32)), jnp.asarray(np.asarray(queries)[:2]))
    assert seen[-1] == 96          # store default
    hook = KNNLMHook(store=store, k=K, lam=0.5, block_rows=192)
    hook(jnp.zeros((2, 32)), jnp.asarray(np.asarray(queries)[:2]))
    assert seen[-1] == 192         # per-hook override wins


# ---------------------------------------------------------------------------
# Fused filter+prune scan vs the two-kernel scan vs the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_fused_scan_matches_unfused_and_reference(family, quantize):
    """The fused-kernel scan (default) == two-kernel scan == reference.

    The fused path also swaps the per-step windowed envelope gate for the
    hoisted whole-table gate, so this pins BOTH changes to bit-parity.
    """
    index, queries = _built(family, quantize)
    budget = 64
    br = search.resolve_block_rows(BLOCK_ROWS, index.n)
    eb = search.resolve_env_block_rows(None)
    fused = search._knn_search_batch_jit(index, queries, K, budget, br, eb)
    unfused = search._knn_search_batch_unfused_jit(index, queries, K,
                                                   budget, br, eb)
    ref = search.knn_search_batch_reference(index, queries, K, budget,
                                            block_rows=BLOCK_ROWS)
    _assert_bitwise_equal(fused, unfused)
    _assert_bitwise_equal(fused, ref)


# ---------------------------------------------------------------------------
# Knob sweep: every autotuner-selectable choice is results-invariant
# ---------------------------------------------------------------------------

# Autotuner candidates rescaled to the N=420 test fixture (the real
# candidate set starts at 1024 and the sweep skips br > 2n, so at test
# size every multi-block/misaligned/single-block regime is covered by):
SWEEP_BLOCK_ROWS = (32, 96, 256, N)


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("family", family_names())
def test_block_rows_choice_never_changes_results(family, quantize):
    """Bit-identical SearchResult for every block_rows the tuner may pick.

    This is the safety property that makes the autotuner table a pure
    perf knob: exact and approx searches must return the same ids/dists/
    exact/num_candidates regardless of the scan's block size.
    """
    index, queries = _built(family, quantize)
    budget = 64
    base = search.knn_search_batch(index, queries, K, budget,
                                   block_rows=search.DEFAULT_BLOCK_ROWS)
    base_a = search.knn_search_batch_approx(index, queries, K, budget,
                                            jnp.float32(P_APPROX),
                                            block_rows=search.DEFAULT_BLOCK_ROWS)
    for br in SWEEP_BLOCK_ROWS:
        got = search.knn_search_batch(index, queries, K, budget,
                                      block_rows=br)
        _assert_bitwise_equal(got, base)
        got_a = search.knn_search_batch_approx(index, queries, K, budget,
                                               jnp.float32(P_APPROX),
                                               block_rows=br)
        _assert_bitwise_equal(got_a, base_a)


@pytest.mark.parametrize("quantize", [False, True])
def test_env_block_rows_choice_never_changes_results(quantize):
    """Envelope-gate granularity is results-invariant (superset admits).

    Coarsening the gate to f*ENV_BLOCK_ROWS min/maxes envelope rows
    together — looser bounds admit a superset of blocks whose extra admit
    tiles are provably all-zero, so compaction output is unchanged.
    """
    for family in ("squared_euclidean", "itakura_saito"):
        index, queries = _built(family, quantize)
        budget = 64
        base = search.knn_search_batch(index, queries, K, budget,
                                       block_rows=BLOCK_ROWS)
        for eb in (ENV_BLOCK_ROWS, 2 * ENV_BLOCK_ROWS, 4 * ENV_BLOCK_ROWS):
            got = search.knn_search_batch(index, queries, K, budget,
                                          block_rows=BLOCK_ROWS,
                                          env_block_rows=eb)
            _assert_bitwise_equal(got, base)


# ---------------------------------------------------------------------------
# Rank -> row routing of one block vs the binary-search oracle
# ---------------------------------------------------------------------------

def _rows_by_rank_searchsorted(admit, t_ranks):
    """The binary search on the admit prefix-sum the scan routed by before:
    the oracle of the sort that replaced it."""
    bn = admit.shape[0]
    csum = jnp.cumsum(admit, axis=0)
    ranks = jnp.arange(1, t_ranks + 1, dtype=jnp.int32)
    rows = jax.vmap(lambda c: jnp.searchsorted(c, ranks, side="left"))(csum.T)
    return jnp.minimum(rows, bn - 1).astype(jnp.int32)


ROUTE_BN, ROUTE_Q = BLOCK_ROWS, 6


def _admit_tile(pattern):
    bn, q = ROUTE_BN, ROUTE_Q
    rows = np.arange(bn)[:, None]
    rng = np.random.default_rng(11)
    tiles = {
        "all_zero": np.zeros((bn, q)),
        "all_one": np.ones((bn, q)),
        "single_row": np.broadcast_to(rows == 37, (bn, q)),
        "alternating": np.broadcast_to(rows % 2 == 0, (bn, q)),
        "last_8_rows": np.broadcast_to(rows >= bn - 8, (bn, q)),
    }
    if pattern in tiles:
        return jnp.asarray(tiles[pattern], jnp.int32)
    density = float(pattern.split("_")[1])
    return jnp.asarray(rng.random((bn, q)) < density, jnp.int32)


ROUTE_PATTERNS = ("all_zero", "all_one", "single_row", "alternating",
                  "random_0.01", "random_0.5", "random_0.99", "last_8_rows")


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("budget", [4 * BLOCK_ROWS, 20],
                         ids=["budget_over_bn", "budget_under_bn"])
@pytest.mark.parametrize("pattern", ROUTE_PATTERNS)
def test_rows_by_rank_matches_searchsorted(pattern, budget, impl,
                                           monkeypatch):
    """The sort routing lists the same rows at every rank a fill writes.

    Ranks below the block's admit count match the binary search exactly,
    the rest stay inside the block, and ``_fill_block_slots`` returns the
    same (sel, count) as with the binary search: from an empty carry,
    mid-budget, near the budget's end and past it.
    """
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    admit = _admit_tile(pattern)
    bn, q = admit.shape
    t_ranks = min(bn, budget)
    got = np.asarray(jax.jit(search._rows_by_rank, static_argnums=1)(
        admit, t_ranks))
    want = np.asarray(_rows_by_rank_searchsorted(admit, t_ranks))
    assert got.shape == want.shape == (q, t_ranks)
    tot = np.asarray(admit).sum(axis=0)
    for j in range(q):
        live = min(tot[j], t_ranks)
        np.testing.assert_array_equal(got[j, :live], want[j, :live])
    assert got.min() >= 0 and got.max() < bn

    count = jnp.asarray([0, 3, budget // 2, budget - 5, budget, budget + 7],
                        jnp.int32)[:q]
    sel = jnp.arange(q * budget, dtype=jnp.int32).reshape(q, budget)
    off = jnp.int32(5 * bn)
    got_sel, got_count = jax.jit(search._fill_block_slots, static_argnums=4)(
        sel, count, admit, off, budget)
    # The same fill routed by the binary search, run eagerly so the patched
    # routing is what it calls.
    monkeypatch.setattr(search, "_rows_by_rank", _rows_by_rank_searchsorted)
    want_sel, want_count = search._fill_block_slots(sel, count, admit, off,
                                                    budget)
    np.testing.assert_array_equal(np.asarray(got_sel), np.asarray(want_sel))
    np.testing.assert_array_equal(np.asarray(got_count),
                                  np.asarray(want_count))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("budget", [N, 20],
                         ids=["budget_over_bn", "budget_under_bn"])
def test_stream_routing_matches_reference_per_kernel_impl(budget, quantize,
                                                          impl, monkeypatch):
    """The whole streamed pipeline, kernels as ``impl`` runs them, equals
    the materialized mask's binary-search compaction bit for bit, with
    budgets above a block's rows and below them (an overflowing one)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    index, queries = _built("squared_euclidean", quantize)
    br = search.resolve_block_rows(BLOCK_ROWS, index.n)

    def run(streaming):
        # A fresh function per call: the kernel path is fixed at trace time.
        return jax.jit(lambda ix, ys: search._knn_search_batch_core(
            ix, ys, K, budget, None, br, streaming=streaming))(index, queries)

    got, want = run(True), run(False)
    _assert_bitwise_equal(got, want)
    assert bool(jnp.all(got.exact)) == (budget == N)
