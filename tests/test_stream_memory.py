"""Memory regression guard for the streamed batched pipeline.

Compiles ``knn_search_batch``'s jit core at a serving-sized shape
(n = 65536, q = 128) against abstract (ShapeDtypeStruct) index arrays —
no data, no k-means — and walks the optimized HLO with
``launch/hlo_analysis`` to assert the historical O(n * q) intermediates
(the (n, q) Theorem-3 bool mask, the (q, n) int32 compaction cumsum)
never come back: no instruction in the compiled module may produce an
(n, q)-sized tensor.  Where the backend exposes a compiled memory
analysis, peak temp-buffer bytes are additionally bounded by a
constant * block_rows * q budget (plus the O(n * M) index-table
reshapes, which scale with the INDEX, not with n * q).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import ENV_BLOCK_ROWS, BallForest
from repro.core.transform import make_partition
from repro.core import search
from repro.launch import hlo_analysis as ha

N, Q, D, M, C, K = 65536, 128, 32, 8, 64, 8
BUDGET = 256
BLOCK_ROWS = 4096
S = 1024                      # beta sample size (unused by the exact path)


def _forest_spec(n=N, d=D, m=M, c=C):
    """A shape-only fp32 BallForest for aval lowering."""
    part = make_partition(d, m)
    w = part.width
    ne = -(-n // ENV_BLOCK_ROWS)
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    return BallForest(
        family_name="squared_euclidean",
        partition=part,
        num_clusters=c,
        data=sds((n, d), f32),
        point_ids=sds((n,), jnp.int32),
        alpha=sds((n, m), f32),
        sqrt_gamma=sds((n, m), f32),
        assign=sds((n, m), jnp.int32),
        alpha_min=sds((m, c), f32),
        sqrt_gamma_max=sds((m, c), f32),
        counts=sds((m, c), jnp.int32),
        centers=sds((m, c, w), f32),
        beta_samples=sds((S,), f32),
        alpha_min_pt=sds((n, m), f32),
        sqrt_gamma_max_pt=sds((n, m), f32),
        gamma_edges=sds((m, 3), f32),
        env_alpha_min=sds((ne, m), f32),
        env_sqrt_gamma_max=sds((ne, m), f32),
    )


def _forbidden_shapes(n, q):
    return {(n, q), (q, n)}


def _instr_shapes(txt):
    comps, _ = ha.parse_computations(txt)
    for instrs in comps.values():
        for instr in instrs:
            for _, shape in instr.out:
                yield instr, tuple(shape)


def _compile(core_jit, n, q, budget, block_rows):
    forest = _forest_spec(n=n)
    ys = jax.ShapeDtypeStruct((q, D), jnp.float32)
    return core_jit.lower(forest, ys, K, budget, block_rows).compile()


@pytest.fixture(scope="module")
def compiled_stream():
    return _compile(search._knn_search_batch_jit, N, Q, BUDGET, BLOCK_ROWS)


def test_no_point_query_sized_intermediates(compiled_stream):
    """THE guard: nothing in the module is (n, q)-shaped, or n*q-sized."""
    bad = []
    nq = N * Q
    for instr, shape in _instr_shapes(compiled_stream.as_text()):
        numel = int(np.prod(shape)) if shape else 1
        if shape in _forbidden_shapes(N, Q) or numel >= nq:
            bad.append((instr.opcode, shape))
    assert not bad, f"(n, q)-sized intermediates re-materialized: {bad[:5]}"


def test_detector_catches_reference_pipeline():
    """Sanity: the same detector DOES flag the mask/cumsum reference."""
    n, q = 4096, 32
    compiled = _compile(search._knn_search_batch_ref_jit, n, q, 64, n)
    hits = [shape for _, shape in _instr_shapes(compiled.as_text())
            if shape in _forbidden_shapes(n, q)]
    assert hits, "reference path no longer materializes (n, q) — update test"


def test_peak_temp_bytes_bounded(compiled_stream):
    """Peak temps ~ C1 * block_rows * q + C2 * n * M, never ~ n * q.

    The n * M term covers XLA's padded copies of the (n, M) index tables
    the two scans stream (layout copies of the INPUT, scaling with the
    index like the index itself) — the point of the streamed pipeline is
    that nothing scales with n * q.
    """
    try:
        mem = compiled_stream.memory_analysis()
        temp = int(mem.temp_size_in_bytes)
    except (AttributeError, NotImplementedError, TypeError) as e:
        pytest.skip(f"backend exposes no memory_analysis ({e})")
    # Measured 8.9 MB on this container (vs 69.9 MB for the reference
    # pipeline at the same shape); the bound leaves headroom for layout
    # copies across jax/XLA versions while still rejecting any
    # per-pair-scaling intermediate.
    bound = 16 * BLOCK_ROWS * Q * 4 + 6 * N * M * 4
    assert temp <= bound, (
        f"temp bytes {temp} exceed the streaming bound {bound} "
        f"(5-byte-per-pair mask/cumsum would be {5 * N * Q})")
    # and strictly under even a 2-byte-per-pair footprint (the old
    # mask/cumsum pipeline held ~5 bytes per point-query pair)
    assert temp < 2 * N * Q


def test_tiered_stage_a_never_materializes_cold_tiers():
    """Tiered Stage A (filter + bounds + envelope gate over HOT tables)
    compiles with NO n-sized allocation for the cold tiers.

    The TieredPointStore keeps the (n, d) point table and the (n, m)
    per-point corner tables cold (host numpy); Stage A's jit sees them
    only as unused leaves of the hot forest and ``keep_unused=False``
    prunes them, so at compile time the module must contain no (n, d)
    instruction and nothing >= n * d elements.  The hot (n, M) filter
    tables (n * M = 524288 elements here) remain, by design.
    """
    from repro.core import tiered

    forest = _forest_spec()
    ys = jax.ShapeDtypeStruct((Q, D), jnp.float32)
    pg = jax.ShapeDtypeStruct((), jnp.float32)
    compiled = tiered._stage_a_jit.lower(
        forest, ys, K, BLOCK_ROWS, None, pg, False).compile()

    nd = N * D
    bad = [(instr.opcode, shape)
           for instr, shape in _instr_shapes(compiled.as_text())
           if shape == (N, D) or (int(np.prod(shape)) if shape else 1) >= nd]
    assert not bad, f"cold-tier-sized allocations in Stage A: {bad[:5]}"

    # the hot tables themselves do appear — the guard is not vacuous
    hot_sized = [shape for _, shape in _instr_shapes(compiled.as_text())
                 if shape and int(np.prod(shape)) >= N * M]
    assert hot_sized, "no (n, M)-sized hot tables found — shapes changed?"


def test_streamed_results_match_reference_at_compile_shape_small():
    """The compile-shape guard plus a small real-data parity anchor."""
    rng = np.random.default_rng(0)
    data = rng.normal(size=(2048, D)).astype(np.float32)
    from repro.core.index import build_index
    index = build_index(data, "squared_euclidean", m=M, num_clusters=16,
                        seed=0)
    ys = jnp.asarray(rng.normal(size=(16, D)).astype(np.float32))
    res = search.knn_search_batch(index, ys, K, 256, block_rows=512)
    ref = search.knn_search_batch_reference(index, ys, K, 256,
                                            block_rows=512)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))


@pytest.mark.parametrize("family", ["itakura_saito", "exponential"])
def test_build_p_transform_holds_no_view_sized_temporary(family):
    """``build_index``'s P-transform is one program: the generator terms
    fuse into the per-subspace sums, so no (n, M, w) temporary exists.
    Run op by op, each term was a table-sized buffer whose release waited
    on the device, and the build's memory peak moved with how far the host
    had dispatched ahead."""
    from repro.core.bregman import get_family
    from repro.core.transform import p_transform_views

    n, m, w = N, 50, 8                      # Fonts' M and subspace width
    compiled = p_transform_views.lower(
        jax.ShapeDtypeStruct((n, m, w), jnp.float32),
        jax.ShapeDtypeStruct((m, w), jnp.float32),
        family=get_family(family)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < n * m * w * 4 // 8
