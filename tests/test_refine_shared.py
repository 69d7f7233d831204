"""The refine's shared-rows form: one read of the table for every query.

Kernel level: the (n, d) form of ``bregman_refine_batch[_quant]`` in the
Pallas interpreter against the jnp reference, for every family, both
stored orders of the table and a ragged last row tile.  Search level: the
shape rule ``dense_refine`` around its threshold, the shared pass against
the per-query gather (bit for bit on the reference backend, whose two
forms compute each (query, row) distance alike), tombstoned rows, and the
``dense`` launch records and counter.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as qz
from repro.core import search
from repro.core.bregman import family_names, get_family
from repro.core.index import build_index
from repro.core.segments import build_segmented_index
from repro.kernels import bregman_dist, ref
from repro.serve.faults import VirtualClock
from repro.serve.retrieval import RetrievalService, ServiceConfig

N_ROWS, BLOCK = 300, 128            # three row tiles, the last one ragged
GATHER = re.compile(r'bp\.refine/([^"]*/)?gather/')  # the row gather scope


def _queries(fam, q, d):
    ys = fam.sample(jax.random.PRNGKey(2), (q, d))
    grad = fam.phi_prime(ys)
    return ys, grad, jnp.sum(ys * grad, -1) - fam.f(ys)


@pytest.mark.parametrize("q", [1, 32])
@pytest.mark.parametrize("d", [256, 400])
@pytest.mark.parametrize("storage", ["f32", "int8"])
@pytest.mark.parametrize("family", family_names())
def test_shared_rows_kernel_matches_ref(family, storage, d, q):
    """(n, d) rows shared by q queries -> (q, n), as the reference and the
    per-query form compute them; d = 400 reads the table column-major."""
    assert bregman_dist.stored_by_columns(N_ROWS, d) == (d == 400)
    fam = get_family(family)
    rows = fam.sample(jax.random.PRNGKey(1), (N_ROWS, d))
    ys, grad, c_y = _queries(fam, q, d)
    if storage == "int8":
        codes, scale, zp = qz.quantize_rows(rows)
        got = bregman_dist.bregman_refine_batch_quant(
            codes, scale, zp, grad, c_y, family, block_b=BLOCK,
            interpret=True)
        want = ref.bregman_refine_batch_quant(codes, scale, zp, grad, c_y,
                                              family)
        rows = qz.dequantize_rows(codes, scale, zp, fam)
    else:
        got = bregman_dist.bregman_refine_batch(rows, grad, c_y, family,
                                                block_b=BLOCK, interpret=True)
        want = ref.bregman_refine_batch(rows, grad, c_y, family)
    assert got.shape == (q, N_ROWS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    direct = jax.vmap(lambda y: fam.distance(rows, y[None]))(ys)
    np.testing.assert_allclose(np.asarray(got), np.asarray(direct),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("storage", ["f32", "int8"])
@pytest.mark.parametrize("family", family_names())
def test_shared_rows_ref_equals_gathered_ref_bitwise(family, storage):
    """The reference's shared form gives each (query, row) the very bits
    its per-query form gives the same row gathered, so a search on the
    reference backend answers alike on either path."""
    fam = get_family(family)
    rows = fam.sample(jax.random.PRNGKey(1), (N_ROWS, 40))
    _, grad, c_y = _queries(fam, 6, 40)
    sel = jax.random.randint(jax.random.PRNGKey(3), (6, 77), 0, N_ROWS)
    if storage == "int8":
        codes, scale, zp = qz.quantize_rows(rows)
        shared = jax.jit(ref.bregman_refine_batch_quant, static_argnums=5)(
            codes, scale, zp, grad, c_y, family)
        gathered = jax.jit(
            lambda c, s, z, g, cy, i: ref.bregman_refine_batch_quant(
                jnp.take(c, i, 0), jnp.take(s, i), jnp.take(z, i), g, cy,
                family))(codes, scale, zp, grad, c_y, sel)
    else:
        shared = jax.jit(ref.bregman_refine_batch, static_argnums=3)(
            rows, grad, c_y, family)
        gathered = jax.jit(
            lambda r, g, cy, i: ref.bregman_refine_batch(
                jnp.take(r, i, 0), g, cy, family))(rows, grad, c_y, sel)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(shared), np.asarray(sel), 1),
        np.asarray(gathered))


def test_shared_rows_query_blocks(monkeypatch):
    """A batch over the kernel's query block takes several grid steps over
    each row tile and returns every query's row."""
    monkeypatch.setattr(bregman_dist, "SHARED_QUERY_BLOCK", 8)
    fam = get_family("exponential")
    rows = fam.sample(jax.random.PRNGKey(1), (N_ROWS, 24))
    _, grad, c_y = _queries(fam, 19, 24)
    got = bregman_dist.bregman_refine_batch(rows, grad, c_y, fam.name,
                                            block_b=BLOCK, interpret=True)
    want = ref.bregman_refine_batch(rows, grad, c_y, fam.name)
    assert got.shape == (19, N_ROWS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# The path rule and the search-level switch
# ---------------------------------------------------------------------------

def test_dense_refine_rule():
    """passes * n <= q * budget, with passes = ceil(q / chunk) and chunk the
    queries whose (chunk, n) float32 block fits REFINE_GATHER_BYTES."""
    n = 1_000_000
    assert search.dense_refine(32, 62_500, n)       # 2M slots >= n
    assert search.dense_refine(32, n, n)
    assert search.dense_refine(64, 62_500, n)
    assert not search.dense_refine(32, 31_249, n)
    assert search.dense_refine(1, n, n)
    assert not search.dense_refine(1, n - 1, n)
    # 256 MB holds 67 rows of n float32: 100 queries take two passes.
    assert not search.dense_refine(100, 19_999, n)
    assert search.dense_refine(100, 20_000, n)


@pytest.fixture(scope="module", params=["f32", "int8"])
def index(request):
    fam = get_family("exponential")
    data = np.asarray(fam.sample(jax.random.PRNGKey(4), (N_ROWS, 24)))
    return build_index(data, fam.name, m=4, num_clusters=16, seed=0,
                       quantize=request.param == "int8")


def _search(index, ys, budget, streaming, gather=False):
    """One batch launch, traced afresh so the kernel backend and a forced
    gather (``gather=True``) are the ones it runs; also its HLO text."""
    br = search.resolve_block_rows(BLOCK, index.n)
    with pytest.MonkeyPatch.context() as mp:
        if gather:
            mp.setattr(search, "dense_refine", lambda *a: False)
        fn = jax.jit(lambda ix, ys: search._knn_search_batch_core(
            ix, ys, 7, budget, None, br, streaming=streaming))
        return fn(index, ys), fn.lower(index, ys).compile().as_text()


@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streamed", "reference"])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("passes", [1, 3])
@pytest.mark.parametrize("side", ["under", "at", "over"])
def test_shared_pass_matches_gather_around_threshold(index, side, passes,
                                                     impl, streaming,
                                                     monkeypatch):
    """Just under, at and just over the rule's threshold a launch returns
    the gather's ids, exactness and union sizes, and its distances: bit for
    bit on the reference backend, to rounding in the kernels.  Where the
    kernels take the shared pass no candidate row is gathered."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    q = 5
    if passes == 3:                     # chunks of two queries
        monkeypatch.setattr(search, "REFINE_GATHER_BYTES", 8 * N_ROWS)
    threshold = -(-passes * N_ROWS // q)
    budget = threshold + {"under": -1, "at": 0, "over": 1}[side]
    dense = side != "under"
    assert search.dense_refine(q, budget, N_ROWS) == dense
    ys = jnp.asarray(index.family.sample(jax.random.PRNGKey(5),
                                         (q, index.d)))
    got, text = _search(index, ys, budget, streaming)
    want, want_text = _search(index, ys, budget, streaming, gather=True)
    assert bool(GATHER.search(text)) == (not dense)
    assert GATHER.search(want_text)
    for field in ("ids", "exact", "num_candidates"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)))
    if impl == "ref":
        np.testing.assert_array_equal(np.asarray(got.dists),
                                      np.asarray(want.dists))
    else:
        np.testing.assert_allclose(np.asarray(got.dists),
                                   np.asarray(want.dists),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_single_query_takes_shared_pass_at_budget_n(index, impl,
                                                    monkeypatch):
    """The q = 1 path reads the whole table at budget n only, and returns
    the batch path's neighbours."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    y = jnp.asarray(index.family.sample(jax.random.PRNGKey(8), (index.d,)))
    assert search.dense_refine(1, index.n, index.n)
    assert not search.dense_refine(1, index.n - 1, index.n)
    # The jitted entry point's body, traced afresh for this backend.
    body = search._knn_search_jit.__wrapped__
    one = jax.jit(lambda ix, y: body(ix, y, 7, index.n))
    text = one.lower(index, y).compile().as_text()
    assert not re.search(r'/gather/', text)
    res = one(index, y)
    batch, _ = _search(index, y[None], index.n, True)
    np.testing.assert_array_equal(np.asarray(res.ids),
                                  np.asarray(batch.ids[0]))
    np.testing.assert_allclose(np.asarray(res.dists),
                               np.asarray(batch.dists[0]), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_budget_n_launch_never_returns_tombstoned_rows(impl, monkeypatch):
    """A budget-n launch on an index with deleted rows (``point_ids < 0`` in
    its view) reads the whole table in a shared pass and returns only live
    ids, the gather's."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    rng = np.random.default_rng(3)
    data = rng.random((N_ROWS, 16)).astype(np.float32) + 0.1
    sf = build_segmented_index(data, "shannon", m=4)
    dead = np.arange(0, N_ROWS, 3)
    sf.delete(dead, auto_compact=False)
    view = search._as_forest(sf)
    assert int(jnp.sum(view.point_ids < 0)) == len(dead)
    ys = data[:6] + 0.01                # the nearest rows include dead ones
    k = 12
    assert search.dense_refine(6, view.n, view.n)
    res = search.knn_search_batch(sf, ys, k, view.n)
    ids = np.asarray(res.ids)
    assert bool(jnp.all(res.exact))
    assert not np.isin(ids, dead).any() and (ids >= 0).all()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(search, "dense_refine", lambda *a: False)
        want = jax.jit(lambda ix, ys: search._knn_search_batch_core(
            ix, ys, k, view.n, None, search.resolve_block_rows(None, view.n)
        ))(view, jnp.asarray(ys))
    np.testing.assert_array_equal(ids, np.asarray(want.ids))


# ---------------------------------------------------------------------------
# Launch records and the service counter
# ---------------------------------------------------------------------------

def test_knn_batch_records_dense_launches(index):
    """knn_batch's records flag the launches the rule sends to the shared
    pass: not the 8-slot first launch of 10 queries over 300 rows, the
    escalated one; the full-scan escalation is never flagged."""
    fam = index.family
    ys = np.asarray(fam.sample(jax.random.PRNGKey(6), (10, index.d)))
    _, stats = search.knn_batch(index, ys, 5, budget=8, return_stats=True)
    flags = [r["dense"] for r in stats.launches]
    assert flags == [search.dense_refine(10, r["budget"], index.n)
                     for r in stats.launches]
    assert flags[0] is False and flags[-1] is True
    _, stats = search.knn_batch(index, ys, 5, budget=8, max_doublings=0,
                                return_stats=True)
    assert stats.escalated_to_scan and stats.launches[-1]["dense"] is False


def test_service_counts_dense_refines():
    """Each service launch records ``dense`` from the rule over the launched
    (bucketed) rows, and ``counters["dense_refines"]`` counts them."""
    rng = np.random.default_rng(0)
    data = rng.random((N_ROWS, 16)).astype(np.float32) + 0.1
    index = build_segmented_index(data, "shannon", m=4)
    svc = RetrievalService(ServiceConfig(buckets=(4,)),
                           clock=VirtualClock())
    svc.register_tenant("t", index)
    n = search._as_forest(index).n
    queries = rng.random((3, 16)).astype(np.float32) + 0.1
    before = dict(svc.counters)
    ticket = svc.submit("t", queries, 5)
    svc.run_until_drained()
    launches = ticket.response.meta["launches"]
    assert ticket.response.quality == "exact" and len(launches) >= 2
    flags = [x["dense"] for x in launches]
    assert flags == [search.dense_refine(4, x["budget"], n) for x in launches]
    assert flags[0] is False and flags[-1] is True
    assert svc.counters["dense_refines"] - before["dense_refines"] == sum(
        flags)
