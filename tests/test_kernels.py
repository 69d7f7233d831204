"""Pallas kernels (interpret=True) vs pure-jnp ref oracles: shape/dtype sweeps."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ref
from repro.kernels.bregman_ub import bregman_ub_matrix, bregman_ub_matrix_quant
from repro.kernels.bregman_fused import (bregman_filter_prune,
                                         bregman_filter_prune_quant)
from repro.kernels.bregman_prune import (bregman_prune_mask,
                                         bregman_prune_mask_quant)
from repro.kernels.bregman_dist import bregman_refine
from repro.kernels.pccp_corr import pccp_correlation
from repro.kernels.flash_attention import flash_attention
from repro.core import quantize as qz
from repro.core.bregman import get_family

# NOTE: the DETERMINISTIC parity tests for the quantized kernels live in
# tests/test_quantized.py, outside this module's hypothesis gate, so they
# run wherever jax runs; only the property sweep below needs hypothesis.


# ---------------------------------------------------------------------------
# bregman_ub
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,q", [(64, 8, 1), (100, 28, 3), (513, 50, 5),
                                   (32, 1, 1), (7, 5, 2)])
def test_ub_kernel_shapes(n, m, q):
    rng = np.random.default_rng(0)
    alpha = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    sg = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    got = bregman_ub_matrix(alpha, sg, jnp.sum(qc, -1), sd,
                            block_n=32, block_q=4, interpret=True)
    want = ref.bregman_ub_matrix(alpha, sg, qc, sd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_ub_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    alpha = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    sg = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    got = bregman_ub_matrix(alpha, sg, jnp.sum(qc, -1), sd, interpret=True)
    want = ref.bregman_ub_matrix(alpha, sg, qc, sd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_ub_quant_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    a_q, a_s, a_z = qz.quantize_stats(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32))
    g_q, g_s, g_z = qz.quantize_stats(
        jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32))
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    got = bregman_ub_matrix_quant(a_q, a_s, a_z, g_q, g_s, g_z,
                                  jnp.sum(qc, -1), sd, interpret=True)
    want = ref.bregman_ub_matrix_quant(a_q, a_s, a_z, g_q, g_s, g_z, qc, sd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# bregman_prune (Theorem-3 admit mask)
# ---------------------------------------------------------------------------

def _prune_inputs(rng, n, m, q):
    amin = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    gmax = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    # bounds near the lb distribution so both mask values actually occur
    qb = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    return amin, gmax, qc, sd, qb


@pytest.mark.parametrize("n,m,q", [(64, 8, 1), (100, 28, 3), (257, 50, 5),
                                   (32, 1, 1), (7, 5, 2)])
def test_prune_kernel_shapes(n, m, q):
    rng = np.random.default_rng(0)
    amin, gmax, qc, sd, qb = _prune_inputs(rng, n, m, q)
    # Admission ORs over m subspaces, so N(0, 1) bounds admit every pair at
    # m = 50.  Set each (query, subspace) bound at the lower-bound quantile
    # that admits about half the points overall: p_i = 1 - 0.5 ** (1 / m).
    lb = (np.asarray(amin)[:, :, None] + np.asarray(qc).T[None]
          - np.asarray(gmax)[:, :, None] * np.asarray(sd).T[None])
    qb = jnp.asarray(np.quantile(lb, 1.0 - 0.5 ** (1.0 / m), axis=0).T,
                     jnp.float32)
    got = bregman_prune_mask(amin, gmax, qc, sd, qb,
                             block_n=32, block_q=4, interpret=True)
    want = ref.bregman_prune_mask(amin, gmax, qc, sd, qb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32
    # non-degenerate case: both admitted and pruned pairs exist
    if n * q >= 500:
        assert 0 < int(np.asarray(got).sum()) < n * q


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_prune_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    amin, gmax, qc, sd, qb = _prune_inputs(rng, n, m, q)
    got = bregman_prune_mask(amin, gmax, qc, sd, qb, interpret=True)
    want = ref.bregman_prune_mask(amin, gmax, qc, sd, qb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_prune_quant_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    a_q, a_s, a_z = qz.quantize_stats(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32), "floor")
    g_q, g_s, g_z = qz.quantize_stats(
        jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32), "ceil")
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    qb = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    got = bregman_prune_mask_quant(a_q, a_s, a_z, g_q, g_s, g_z,
                                   qc, sd, qb, interpret=True)
    want = ref.bregman_prune_mask_quant(a_q, a_s, a_z, g_q, g_s, g_z,
                                        qc, sd, qb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# bregman_fused (one-pass filter UB + Theorem-3 admit)
# ---------------------------------------------------------------------------

def _fused_inputs(rng, n, m, q):
    alpha = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    sg = jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32)
    amin, gmax, qc, sd, qb = _prune_inputs(rng, n, m, q)
    return alpha, sg, amin, gmax, qc, sd, qb


@pytest.mark.parametrize("n,m,q", [(64, 8, 1), (100, 28, 3), (257, 50, 5),
                                   (32, 1, 1), (7, 5, 2)])
def test_fused_kernel_shapes(n, m, q):
    """Fused (ub, admit) == (ub kernel, prune kernel) at odd shapes.

    ``ub`` is allclose to the standalone UB kernel; ``admit`` must be
    BIT-IDENTICAL to the standalone prune kernel (the streaming scan's
    compaction consumes it, so any drift changes SearchResult).
    """
    rng = np.random.default_rng(0)
    alpha, sg, amin, gmax, qc, sd, qb = _fused_inputs(rng, n, m, q)
    qsum = jnp.sum(qc, -1)
    ub, admit = bregman_filter_prune(alpha, sg, amin, gmax, qsum, qc, sd, qb,
                                     block_n=32, block_q=4, interpret=True)
    ub_ref, admit_ref = ref.bregman_filter_prune(alpha, sg, amin, gmax,
                                                 qc, sd, qb)
    np.testing.assert_allclose(np.asarray(ub), np.asarray(ub_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(admit), np.asarray(admit_ref))
    assert admit.dtype == jnp.int32
    # the admit half must match the standalone prune kernel bit for bit
    solo = bregman_prune_mask(amin, gmax, qc, sd, qb,
                              block_n=32, block_q=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(admit), np.asarray(solo))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_fused_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    alpha, sg, amin, gmax, qc, sd, qb = _fused_inputs(rng, n, m, q)
    ub, admit = bregman_filter_prune(alpha, sg, amin, gmax,
                                     jnp.sum(qc, -1), qc, sd, qb,
                                     interpret=True)
    ub_ref, admit_ref = ref.bregman_filter_prune(alpha, sg, amin, gmax,
                                                 qc, sd, qb)
    np.testing.assert_allclose(np.asarray(ub), np.asarray(ub_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(admit), np.asarray(admit_ref))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 200), m=st.integers(1, 40), q=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_fused_quant_kernel_property(n, m, q, seed):
    rng = np.random.default_rng(seed)
    a_q, a_s, a_z = qz.quantize_stats(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32))
    g_q, g_s, g_z = qz.quantize_stats(
        jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32))
    am_q, am_s, am_z = qz.quantize_stats(
        jnp.asarray(rng.normal(size=(n, m)), jnp.float32), "floor")
    gm_q, gm_s, gm_z = qz.quantize_stats(
        jnp.asarray(np.abs(rng.normal(size=(n, m))), jnp.float32), "ceil")
    qc = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    sd = jnp.asarray(np.abs(rng.normal(size=(q, m))), jnp.float32)
    qb = jnp.asarray(rng.normal(size=(q, m)), jnp.float32)
    ub, admit = bregman_filter_prune_quant(
        a_q, a_s, a_z, g_q, g_s, g_z, am_q, am_s, am_z, gm_q, gm_s, gm_z,
        jnp.sum(qc, -1), qc, sd, qb, interpret=True)
    ub_ref, admit_ref = ref.bregman_filter_prune_quant(
        a_q, a_s, a_z, g_q, g_s, g_z, am_q, am_s, am_z, gm_q, gm_s, gm_z,
        qc, sd, qb)
    np.testing.assert_allclose(np.asarray(ub), np.asarray(ub_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(admit), np.asarray(admit_ref))
    # bit-parity with the standalone quantized prune kernel
    solo = bregman_prune_mask_quant(am_q, am_s, am_z, gm_q, gm_s, gm_z,
                                    qc, sd, qb, interpret=True)
    np.testing.assert_array_equal(np.asarray(admit), np.asarray(solo))


# ---------------------------------------------------------------------------
# bregman_dist (refinement)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["squared_euclidean", "itakura_saito",
                                    "exponential", "burg", "shannon"])
@pytest.mark.parametrize("b,d", [(16, 24), (100, 128), (33, 300)])
def test_refine_kernel(family, b, d):
    fam = get_family(family)
    key = jax.random.PRNGKey(1)
    rows = fam.sample(key, (b, d))
    y = fam.sample(jax.random.PRNGKey(2), (d,))
    grad = fam.phi_prime(y)
    c_y = jnp.sum(y * grad) - fam.f(y)
    got = bregman_refine(rows, grad, c_y, family,
                         block_b=16, block_d=64, interpret=True)
    want = ref.bregman_refine(rows, grad, c_y, family)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # also against the direct definition
    direct = fam.distance(rows, y[None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(direct),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# pccp_corr
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(100, 8), (257, 40), (64, 129)])
def test_corr_kernel(n, d):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    got = pccp_correlation(x, block_d=16, block_n=64, interpret=True)
    want = ref.pccp_correlation(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kh,sq,skv,d,causal,window",
    [
        (2, 4, 4, 64, 64, 32, True, None),      # MHA causal
        (1, 8, 2, 64, 64, 32, True, None),      # GQA 4:1
        (2, 4, 1, 32, 32, 16, True, None),      # MQA
        (1, 4, 4, 64, 64, 32, False, None),     # bidirectional (encoder)
        (1, 4, 2, 64, 64, 32, True, 16),        # sliding window
        (2, 4, 2, 1, 96, 32, True, None),       # decode: 1 new token vs cache
        (1, 2, 2, 48, 48, 32, True, None),      # non-pow2 seq (padding path)
    ],
)
def test_flash_attention(b, h, kh, sq, skv, d, causal, window, dtype):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, sq, d), dtype)
    k = jax.random.normal(kk, (b, kh, skv, d), dtype)
    v = jax.random.normal(kv, (b, kh, skv, d), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)
