"""Pallas TPU kernel — fused Cauchy upper-bound filter (paper Alg. 1/4).

Computes, for every point tile, the total upper bound

    ub[n, q] = rowsum(alpha)[n] + qsum[q] + sqrt_gamma[n, :] . sqrt_delta[q, :]

i.e. a (n, M) x (M, q) matmul with a fused rank-1 bias — the filter phase of
BrePartition collapsed onto the MXU (DESIGN.md §3.1).  The VMEM tile
(``block_n`` x M_padded) is the TPU analogue of the paper's disk page.

Tiling: grid over n; the M (subspace) axis is kept whole per tile — M is a
few dozen in practice (paper Table 4: 22..50), padded to the 128 lane width
by the ops wrapper.  Queries are tiled along the lane axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bounds import F32_PRECISION


def _kernel(alpha_ref, sg_ref, qsum_ref, sd_ref, out_ref):
    alpha = alpha_ref[...]              # (bn, M)
    sg = sg_ref[...]                    # (bn, M)
    qsum = qsum_ref[...]                # (1, bq)
    sd = sd_ref[...]                    # (M, bq)
    rowsum = jnp.sum(alpha, axis=-1, keepdims=True)          # (bn, 1)
    cauchy = jnp.dot(sg, sd, precision=F32_PRECISION,
                         preferred_element_type=jnp.float32)  # MXU
    out_ref[...] = (rowsum + qsum + cauchy).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def bregman_ub_matrix(
    alpha: jax.Array,        # (n, M)
    sqrt_gamma: jax.Array,   # (n, M)
    qsum: jax.Array,         # (q,)  sum over subspaces of qconst
    sqrt_delta: jax.Array,   # (q, M)
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(n, q) UB totals.  Pads n/q/M to tile multiples, strips after."""
    n, m = alpha.shape
    q = qsum.shape[0]
    bn = min(block_n, max(8, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    a = jnp.pad(alpha, ((0, n_pad), (0, m_pad)))
    sg = jnp.pad(sqrt_gamma, ((0, n_pad), (0, m_pad)))
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T      # (M, q)
    qs = jnp.pad(qsum, (0, q_pad))[None, :]                   # (1, q)
    np_, mp = a.shape
    qp = qs.shape[1]

    out = pl.pallas_call(
        _kernel,
        grid=(np_ // bn, qp // bq),
        in_specs=[
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, qp), jnp.float32),
        interpret=interpret,
    )(a, sg, qs, sd)
    return out[:n, :q]


def _make_quant_kernel(m_real: int):
    def kernel(aq_ref, sgq_ref, as_ref, az_ref, gs_ref, gz_ref,
               qsum_ref, sd_ref, sdsum_ref, out_ref):
        aq = aq_ref[...].astype(jnp.float32)          # (bn, M) decoded codes
        sgq = sgq_ref[...].astype(jnp.float32)
        a_s, a_z = as_ref[...], az_ref[...]           # (bn, 1) row decode
        g_s, g_z = gs_ref[...], gz_ref[...]
        # Per-row affine factored out of both reductions: the HBM stream is
        # int8 codes + four f32 scalars per row, not two (M,) f32 tables.
        rowsum = a_s * jnp.sum(aq, axis=-1, keepdims=True) + m_real * a_z
        cauchy = (g_s * jnp.dot(sgq, sd_ref[...], precision=F32_PRECISION,
                                preferred_element_type=jnp.float32)
                  + g_z * sdsum_ref[...])             # (bn, bq)
        out_ref[...] = (rowsum + qsum_ref[...] + cauchy).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "block_q", "interpret"))
def bregman_ub_matrix_quant(
    alpha_q: jax.Array,      # (n, M) int8 codes
    alpha_scale: jax.Array,  # (n,)  per-row affine decode for alpha
    alpha_zp: jax.Array,     # (n,)
    sg_q: jax.Array,         # (n, M) int8 codes
    sg_scale: jax.Array,     # (n,)
    sg_zp: jax.Array,        # (n,)
    qsum: jax.Array,         # (q,)  sum over subspaces of qconst
    sqrt_delta: jax.Array,   # (q, M)
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(n, q) UB totals from the int8 filter tables (kernels/ref.py oracle).

    Same tiling as :func:`bregman_ub_matrix`; the per-row decode rides as
    (bn, 1) scalar columns.  int8 VMEM tiles want a 32-row sublane, so the
    row block floors at 32 (padded rows are stripped after).
    """
    n, m = alpha_q.shape
    q = qsum.shape[0]
    bn = min(block_n, max(32, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    def pad_rows(a, fill=0):
        return jnp.pad(a, ((0, n_pad),) + ((0, m_pad),) * (a.ndim - 1),
                       constant_values=fill)

    aq = pad_rows(alpha_q)
    sgq = pad_rows(sg_q)
    a_s = pad_rows(alpha_scale)[:, None]
    a_z = pad_rows(alpha_zp)[:, None]
    g_s = pad_rows(sg_scale)[:, None]
    g_z = pad_rows(sg_zp)[:, None]
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T      # (M, q)
    qs = jnp.pad(qsum, (0, q_pad))[None, :]                   # (1, q)
    sds = jnp.pad(jnp.sum(sqrt_delta, -1), (0, q_pad))[None, :]
    np_, mp = aq.shape
    qp = qs.shape[1]

    out = pl.pallas_call(
        _make_quant_kernel(m),
        grid=(np_ // bn, qp // bq),
        in_specs=[
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, qp), jnp.float32),
        interpret=interpret,
    )(aq, sgq, a_s, a_z, g_s, g_z, qs, sd, sds)
    return out[:n, :q]
