"""Pallas TPU kernel — fused Theorem-3 per-point admission mask.

For a tile of points and a tile of queries, recompute the tuple-space
cluster lower bound from the per-point corner stats and emit the admit
mask in one VMEM-resident pass:

    lb[n, i, j] = amin[n, i] + qconst[j, i] - gmax[n, i] * sqrt_delta[j, i]
    admit[n, j] = any_i ( lb[n, i, j] <= qb[j, i] )

The (bn, M, q) lower-bound tensor never exists: the subspace axis is an
in-kernel ``fori_loop`` (M is a few dozen — paper Table 4), each iteration
an outer broadcast of a (bn, 1) point column against a (1, bq) query row
with an OR-accumulate, so the only tile that leaves the kernel is the
(bn, bq) int32 mask the streaming compaction consumes
(core/search._stream_prune_compact).

The quantized variant streams int8 corner CODES plus four per-row decode
scalars and dequantizes the tile on-chip — the corner codes were
directed-rounded at encode (core/quantize.py), so the decoded bound is
conservative with no slack term.  Query operands arrive TRANSPOSED,
(M, q), so the per-subspace row is a dynamic sublane read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def admit_tile(m_real: int, amin, gmax, qc_ref, sd_ref, qb_ref):
    """(bn, bq) int32 OR over the real subspaces of ``lb_i <= qb_i``.

    ``amin``/``gmax`` are decoded (bn, Mp) point tiles; the query refs are
    (Mp, bq).  A ``fori_loop`` keeps one subspace's (bn, bq) tile live at a
    time; a statically unrolled loop kept every (bn, 1) column live and ran
    out of VMEM at the paper's M (37 for Deep, 50 for Fonts).  Column i is
    read by a lane-masked max (exact: only lane i survives), the query row
    by a dynamic sublane slice.  Padded lanes (i >= m_real) carry zeros,
    which would admit everything (0 <= 0), so the loop stops at m_real.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, amin.shape, 1)

    def body(i, hit):
        on = lane == i
        a = jnp.max(jnp.where(on, amin, -jnp.inf), axis=1, keepdims=True)
        g = jnp.max(jnp.where(on, gmax, -jnp.inf), axis=1, keepdims=True)
        lb = a + qc_ref[pl.ds(i, 1), :] - g * sd_ref[pl.ds(i, 1), :]
        return hit | (lb <= qb_ref[pl.ds(i, 1), :]).astype(jnp.int32)

    init = jnp.zeros((amin.shape[0], qc_ref.shape[1]), jnp.int32)
    return jax.lax.fori_loop(0, m_real, body, init)


def _make_kernel(m_real: int):
    def kernel(amin_ref, gmax_ref, qc_ref, sd_ref, qb_ref, out_ref):
        out_ref[...] = admit_tile(m_real, amin_ref[...], gmax_ref[...],
                                  qc_ref, sd_ref, qb_ref)

    return kernel


def _make_quant_kernel(m_real: int):
    def kernel(amq_ref, gmq_ref, as_ref, az_ref, gs_ref, gz_ref,
               qc_ref, sd_ref, qb_ref, out_ref):
        # Fused per-row affine decode: the HBM stream is int8 codes plus
        # four f32 scalars per row, never a fp32 corner table.
        amin = amq_ref[...].astype(jnp.float32) * as_ref[...] + az_ref[...]
        gmax = gmq_ref[...].astype(jnp.float32) * gs_ref[...] + gz_ref[...]
        out_ref[...] = admit_tile(m_real, amin, gmax, qc_ref, sd_ref, qb_ref)

    return kernel


# Padded point rows must never admit: +BIG alpha_min pushes the lower bound
# beyond any finite searching bound (mirrors core/index.PAD_CORNER).
_PAD_AMIN = 1e30


@functools.partial(jax.jit, static_argnames=("block_n", "block_q",
                                             "interpret"))
def bregman_prune_mask(
    amin: jax.Array,         # (n, M) per-point corner alpha_min
    gmax: jax.Array,         # (n, M) per-point corner sqrt_gamma_max
    qconst: jax.Array,       # (q, M)
    sqrt_delta: jax.Array,   # (q, M)
    qb: jax.Array,           # (q, M) Alg.-4 searching bounds
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(n, q) int32 Theorem-3 admit mask.  Pads n/q/M to tiles, strips after."""
    n, m = amin.shape
    q = qconst.shape[0]
    bn = min(block_n, max(8, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    a = jnp.pad(amin, ((0, n_pad), (0, m_pad)), constant_values=_PAD_AMIN)
    g = jnp.pad(gmax, ((0, n_pad), (0, m_pad)))
    qc = jnp.pad(qconst, ((0, q_pad), (0, m_pad))).T       # (M, q)
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T
    qbt = jnp.pad(qb, ((0, q_pad), (0, m_pad))).T
    np_, mp = a.shape
    qp = qc.shape[1]

    out = pl.pallas_call(
        _make_kernel(m),
        grid=(np_ // bn, qp // bq),
        in_specs=[
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, mp), lambda i, j: (i, 0)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, qp), jnp.int32),
        interpret=interpret,
    )(a, g, qc, sd, qbt)
    return out[:n, :q]


@functools.partial(jax.jit, static_argnames=("block_n", "block_q",
                                             "interpret"))
def bregman_prune_mask_quant(
    amin_q: jax.Array,       # (n, M) int8 corner codes (floor-rounded)
    amin_scale: jax.Array,   # (n,)
    amin_zp: jax.Array,      # (n,)
    gmax_q: jax.Array,       # (n, M) int8 corner codes (ceil-rounded)
    gmax_scale: jax.Array,   # (n,)
    gmax_zp: jax.Array,      # (n,)
    qconst: jax.Array,       # (q, M)
    sqrt_delta: jax.Array,   # (q, M)
    qb: jax.Array,           # (q, M)
    *,
    block_n: int = 512,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(n, q) int32 admit mask from int8 corner tables (kernels/ref oracle).

    Padded point rows get (scale 0, zp +BIG) for alpha_min — the int8
    analogue of the PAD_CORNER sentinel — so they fail every admission.
    int8 VMEM tiles want a 32-row sublane, so the row block floors at 32.
    """
    n, m = amin_q.shape
    q = qconst.shape[0]
    bn = min(block_n, max(32, n))
    bq = min(block_q, max(1, q))
    n_pad = -n % bn
    q_pad = -q % bq
    m_pad = -m % 128 if not interpret else 0

    def pad_rows(a, fill=0):
        return jnp.pad(a, ((0, n_pad),) + ((0, m_pad),) * (a.ndim - 1),
                       constant_values=fill)

    aq = pad_rows(amin_q)
    gq = pad_rows(gmax_q)
    a_s = pad_rows(amin_scale)[:, None]
    a_z = pad_rows(amin_zp, fill=_PAD_AMIN)[:, None]
    g_s = pad_rows(gmax_scale)[:, None]
    g_z = pad_rows(gmax_zp)[:, None]
    qc = jnp.pad(qconst, ((0, q_pad), (0, m_pad))).T
    sd = jnp.pad(sqrt_delta, ((0, q_pad), (0, m_pad))).T
    qbt = jnp.pad(qb, ((0, q_pad), (0, m_pad))).T
    np_, mp = aq.shape
    qp = qc.shape[1]

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
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
            pl.BlockSpec((mp, bq), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, qp), jnp.int32),
        interpret=interpret,
    )(aq, gq, a_s, a_z, g_s, g_z, qc, sd, qbt)
    return out[:n, :q]
