"""Pallas TPU kernel — fused Cauchy UB filter + Theorem-3 admit per block.

The streaming batched pipeline runs two scans over the same row blocks
(core/search): the filter scan computes the (block, q) upper-bound tile
(Alg. 1/4) and the prune scan recomputes per-point lower bounds against
the Alg.-4 searching bounds (Theorem 3).  Run separately, the UB tile is
materialized to HBM by the filter kernel and the prune kernel starts from
a cold VMEM tile.  This kernel computes BOTH tiles in one VMEM-resident
pass over a row block:

    ub[n, q]    = rowsum(alpha)[n] + qsum[q] + sqrt_gamma[n, :] . sd[q, :]
    admit[n, q] = any_i ( amin[n, i] + qconst[q, i]
                          - gmax[n, i] * sd[q, i] <= qb[q, i] )

so the query operand tile ``sd`` (transposed, (M, q)) is read from VMEM
once and feeds both the MXU contraction and the per-subspace admit loop,
and the UB values never round-trip through HBM between the two phases —
the prune scan gets them as a byproduct (core/search uses them for the
``tau_admit`` telemetry: the tightest upper bound among admitted rows).

The UB part is a (bn, M) x (M, bq) matmul with a fused rank-1 bias on the
MXU; the admit part is the per-subspace OR-accumulate loop of
``bregman_prune.py`` (the (bn, M, q) lower-bound tensor never exists).
The int8 variant streams BOTH table pairs as codes (1 byte/entry) with
four decode scalars per row each, and keeps the Cauchy contraction
MXU-aligned by factoring the per-row affine out of the dot:

    sg_hat . sd = g_s * (sg_q . sd) + g_z * sum(sd)

Tiling, padding, and sentinels match the unfused kernels exactly
(bregman_ub.py / bregman_prune.py) so the fused path is bit-identical to
the two-kernel path — the parity tests in tests/test_kernels.py pin this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bounds import F32_PRECISION

from .bregman_prune import _PAD_AMIN, admit_tile


def _make_kernel(m_real: int):
    def kernel(alpha_ref, sg_ref, amin_ref, gmax_ref,
               qsum_ref, qc_ref, sd_ref, qb_ref, ub_ref, admit_ref):
        sd = sd_ref[...]                    # (Mp, bq) — shared by both phases
        alpha = alpha_ref[...]              # (bn, Mp)
        sg = sg_ref[...]
        rowsum = jnp.sum(alpha, axis=-1, keepdims=True)          # (bn, 1)
        cauchy = jnp.dot(sg, sd, precision=F32_PRECISION,
                         preferred_element_type=jnp.float32)  # MXU
        ub_ref[...] = (rowsum + qsum_ref[...] + cauchy).astype(ub_ref.dtype)

        admit_ref[...] = admit_tile(m_real, amin_ref[...], gmax_ref[...],
                                    qc_ref, sd_ref, qb_ref)

    return kernel


def _make_quant_kernel(m_real: int):
    def kernel(aq_ref, sgq_ref, as_ref, az_ref, gs_ref, gz_ref,
               amq_ref, gmq_ref, ams_ref, amz_ref, gms_ref, gmz_ref,
               qsum_ref, qc_ref, sd_ref, sdsum_ref, qb_ref,
               ub_ref, admit_ref):
        sd = sd_ref[...]                                 # (Mp, bq)
        aq = aq_ref[...].astype(jnp.float32)             # (bn, Mp) codes
        sgq = sgq_ref[...].astype(jnp.float32)
        a_s, a_z = as_ref[...], az_ref[...]              # (bn, 1) row decode
        g_s, g_z = gs_ref[...], gz_ref[...]
        # Per-row affine factored out of both reductions (bregman_ub.py):
        # the code matmul stays a clean int8-upcast MXU contraction.
        rowsum = a_s * jnp.sum(aq, axis=-1, keepdims=True) + m_real * a_z
        cauchy = (g_s * jnp.dot(sgq, sd, precision=F32_PRECISION,
                                preferred_element_type=jnp.float32)
                  + g_z * sdsum_ref[...])                # (bn, bq)
        ub_ref[...] = (rowsum + qsum_ref[...] + cauchy).astype(ub_ref.dtype)

        # Fused per-row affine decode of the corner codes (directed-rounded
        # at encode, so the decoded bound is conservative).
        amin = amq_ref[...].astype(jnp.float32) * ams_ref[...] + amz_ref[...]
        gmax = gmq_ref[...].astype(jnp.float32) * gms_ref[...] + gmz_ref[...]
        admit_ref[...] = admit_tile(m_real, amin, gmax, qc_ref, sd_ref, qb_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "block_q",
                                             "interpret"))
def bregman_filter_prune(
    alpha: jax.Array,        # (n, M) filter table
    sqrt_gamma: jax.Array,   # (n, M) filter table
    amin: jax.Array,         # (n, M) per-point corner alpha_min
    gmax: jax.Array,         # (n, M) per-point corner sqrt_gamma_max
    qsum: jax.Array,         # (q,)  sum over subspaces of qconst
    qconst: jax.Array,       # (q, M)
    sqrt_delta: jax.Array,   # (q, M)
    qb: jax.Array,           # (q, M) Alg.-4 searching bounds
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(ub (n, q) f32, admit (n, q) int32) in one pass.  Pads to tiles."""
    n, m = alpha.shape
    q = qsum.shape[0]
    bn = min(block_n, max(8, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    a = jnp.pad(alpha, ((0, n_pad), (0, m_pad)))
    sg = jnp.pad(sqrt_gamma, ((0, n_pad), (0, m_pad)))
    am = jnp.pad(amin, ((0, n_pad), (0, m_pad)), constant_values=_PAD_AMIN)
    gm = jnp.pad(gmax, ((0, n_pad), (0, m_pad)))
    qc = jnp.pad(qconst, ((0, q_pad), (0, m_pad))).T          # (M, q)
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T
    qbt = jnp.pad(qb, ((0, q_pad), (0, m_pad))).T
    qsm = jnp.pad(qsum, (0, q_pad))[None, :]                  # (1, q)
    np_, mp = a.shape
    qp = qc.shape[1]

    ub, admit = pl.pallas_call(
        _make_kernel(m),
        grid=(np_ // bn, qp // bq),
        in_specs=[
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, qp), jnp.float32),
            jax.ShapeDtypeStruct((np_, qp), jnp.int32),
        ],
        interpret=interpret,
    )(a, sg, am, gm, qsm, qc, sd, qbt)
    return ub[:n, :q], admit[:n, :q]


@functools.partial(jax.jit, static_argnames=("block_n", "block_q",
                                             "interpret"))
def bregman_filter_prune_quant(
    alpha_q: jax.Array,      # (n, M) int8 filter codes
    alpha_scale: jax.Array,  # (n,)
    alpha_zp: jax.Array,     # (n,)
    sg_q: jax.Array,         # (n, M) int8 filter codes
    sg_scale: jax.Array,     # (n,)
    sg_zp: jax.Array,        # (n,)
    amin_q: jax.Array,       # (n, M) int8 corner codes (floor-rounded)
    amin_scale: jax.Array,   # (n,)
    amin_zp: jax.Array,      # (n,)
    gmax_q: jax.Array,       # (n, M) int8 corner codes (ceil-rounded)
    gmax_scale: jax.Array,   # (n,)
    gmax_zp: jax.Array,      # (n,)
    qsum: jax.Array,         # (q,)
    qconst: jax.Array,       # (q, M)
    sqrt_delta: jax.Array,   # (q, M)
    qb: jax.Array,           # (q, M)
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused (ub, admit) from the int8 tables.  Padded rows decode to the
    PAD_CORNER sentinel (zero scale, +BIG alpha_min zero-point) and fail
    every admission; int8 VMEM tiles want a 32-row sublane, so the row
    block floors at 32.
    """
    n, m = alpha_q.shape
    q = qsum.shape[0]
    bn = min(block_n, max(32, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    def pad_rows(arr, fill=0):
        return jnp.pad(arr, ((0, n_pad),) + ((0, m_pad),) * (arr.ndim - 1),
                       constant_values=fill)

    aq = pad_rows(alpha_q)
    sgq = pad_rows(sg_q)
    a_s = pad_rows(alpha_scale)[:, None]
    a_z = pad_rows(alpha_zp)[:, None]
    g_s = pad_rows(sg_scale)[:, None]
    g_z = pad_rows(sg_zp)[:, None]
    amq = pad_rows(amin_q)
    gmq = pad_rows(gmax_q)
    am_s = pad_rows(amin_scale)[:, None]
    am_z = pad_rows(amin_zp, fill=_PAD_AMIN)[:, None]
    gm_s = pad_rows(gmax_scale)[:, None]
    gm_z = pad_rows(gmax_zp)[:, None]
    qc = jnp.pad(qconst, ((0, q_pad), (0, m_pad))).T          # (M, q)
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T
    qbt = jnp.pad(qb, ((0, q_pad), (0, m_pad))).T
    qsm = jnp.pad(qsum, (0, q_pad))[None, :]                  # (1, q)
    sds = jnp.pad(jnp.sum(sqrt_delta, -1), (0, q_pad))[None, :]
    np_, mp = aq.shape
    qp = qc.shape[1]

    row_tile = pl.BlockSpec((bn, mp), lambda i, j: (i, 0))
    row_col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    q_tile = pl.BlockSpec((mp, bq), lambda i, j: (0, j))
    q_row = pl.BlockSpec((1, bq), lambda i, j: (0, j))
    ub, admit = pl.pallas_call(
        _make_quant_kernel(m),
        grid=(np_ // bn, qp // bq),
        in_specs=[
            row_tile, row_tile, row_col, row_col, row_col, row_col,
            row_tile, row_tile, row_col, row_col, row_col, row_col,
            q_row, q_tile, q_tile, q_row, q_tile,
        ],
        out_specs=[
            pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, qp), jnp.float32),
            jax.ShapeDtypeStruct((np_, qp), jnp.int32),
        ],
        interpret=interpret,
    )(aq, sgq, a_s, a_z, g_s, g_z, amq, gmq, am_s, am_z, gm_s, gm_z,
      qsm, qc, sd, sds, qbt)
    return ub[:n, :q], admit[:n, :q]
