"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's signature; tests assert allclose between
kernel (interpret=True on CPU) and these references across shape/dtype
sweeps, and hypothesis drives the property tests on top of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bounds import F32_PRECISION
from repro.core.bregman import get_family
from repro.core import quantize as qz

Array = jax.Array


def bregman_ub_matrix(alpha: Array, sqrt_gamma: Array, qconst: Array,
                      sqrt_delta: Array) -> Array:
    """UB totals for a query batch.  (n,M),(n,M),(q,M),(q,M) -> (n,q)."""
    return (jnp.sum(alpha, -1)[:, None] + jnp.sum(qconst, -1)[None, :]
            + jnp.dot(sqrt_gamma, sqrt_delta.T, precision=F32_PRECISION))


def bregman_ub_matrix_quant(alpha_q: Array, alpha_scale: Array,
                            alpha_zp: Array, sg_q: Array, sg_scale: Array,
                            sg_zp: Array, qconst: Array,
                            sqrt_delta: Array) -> Array:
    """UB totals from the int8 filter tables.  Codes (n, M) int8, per-row
    affine decode (n,), queries (q, M) -> (n, q).

    The per-row affine factors out of both reductions, so only the int8
    codes are streamed at full (n, M) width:

        rowsum(alpha_hat)  = alpha_scale * rowsum(alpha_q) + M * alpha_zp
        sg_hat . sd        = sg_scale * (sg_q . sd) + sg_zp * sum(sd)
    """
    m = alpha_q.shape[1]
    arow = alpha_scale * jnp.sum(alpha_q.astype(jnp.float32), -1) + m * alpha_zp
    qsum = jnp.sum(qconst, -1)                       # (q,)
    sdsum = jnp.sum(sqrt_delta, -1)                  # (q,)
    cauchy = (sg_scale[:, None] * jnp.dot(sg_q.astype(jnp.float32),
                                          sqrt_delta.T,
                                          precision=F32_PRECISION)
              + sg_zp[:, None] * sdsum[None, :])
    return arow[:, None] + qsum[None, :] + cauchy


def bregman_prune_mask(amin: Array, gmax: Array, qconst: Array,
                       sqrt_delta: Array, qb: Array) -> Array:
    """Theorem-3 per-point admit mask.  (n,M)x3 query (q,M) -> (n,q) int32.

    Admit point x for query y iff SOME subspace's tuple-space cluster
    lower bound (evaluated through the per-point corner view) is within
    that subspace's Alg.-4 searching bound — core/search._corner_admit,
    as a kernel oracle.  The (n, M, q) intermediate is fine here: the
    reference is only ever called on one block_rows-sized tile.
    """
    lb = (amin[:, :, None] + qconst.T[None, :, :]
          - gmax[:, :, None] * sqrt_delta.T[None, :, :])     # (n, M, q)
    return jnp.any(lb <= qb.T[None, :, :], axis=1).astype(jnp.int32)


def bregman_prune_mask_quant(amin_q: Array, amin_scale: Array,
                             amin_zp: Array, gmax_q: Array,
                             gmax_scale: Array, gmax_zp: Array,
                             qconst: Array, sqrt_delta: Array,
                             qb: Array) -> Array:
    """Admit mask from int8 corner codes + per-row affine decode.

    Decoding goes through core/quantize.dequantize_stats itself, so the
    (directed-rounded, conservative) corner values match what every other
    consumer of the int8 corner tables sees.
    """
    amin = qz.dequantize_stats(amin_q, amin_scale, amin_zp)
    gmax = qz.dequantize_stats(gmax_q, gmax_scale, gmax_zp)
    return bregman_prune_mask(amin, gmax, qconst, sqrt_delta, qb)


def bregman_filter_prune(alpha: Array, sqrt_gamma: Array, amin: Array,
                         gmax: Array, qconst: Array, sqrt_delta: Array,
                         qb: Array) -> tuple[Array, Array]:
    """Fused filter+prune oracle: (ub (n, q), admit (n, q)).

    Composes the two single-phase oracles verbatim, so the fused kernel's
    bit-parity with the two-kernel path is checked against EXACTLY the
    arithmetic the unfused pipeline runs — by construction, not by
    tolerance.
    """
    return (bregman_ub_matrix(alpha, sqrt_gamma, qconst, sqrt_delta),
            bregman_prune_mask(amin, gmax, qconst, sqrt_delta, qb))


def bregman_filter_prune_quant(alpha_q: Array, alpha_scale: Array,
                               alpha_zp: Array, sg_q: Array, sg_scale: Array,
                               sg_zp: Array, amin_q: Array, amin_scale: Array,
                               amin_zp: Array, gmax_q: Array,
                               gmax_scale: Array, gmax_zp: Array,
                               qconst: Array, sqrt_delta: Array,
                               qb: Array) -> tuple[Array, Array]:
    """Fused (ub, admit) oracle over the int8 filter + corner code tables."""
    return (bregman_ub_matrix_quant(alpha_q, alpha_scale, alpha_zp,
                                    sg_q, sg_scale, sg_zp,
                                    qconst, sqrt_delta),
            bregman_prune_mask_quant(amin_q, amin_scale, amin_zp,
                                     gmax_q, gmax_scale, gmax_zp,
                                     qconst, sqrt_delta, qb))


def bregman_refine_batch_quant(codes: Array, scale: Array, zp: Array,
                               grad: Array, c_y: Array, family: str) -> Array:
    """Fused dequantize + exact D_f over int8 candidate rows.

    (q,b,d) int8 codes + (q,b) per-row scale/zp -> (q,b), or shared (n,d)
    codes + (n,) scale/zp -> (q,n).  Decoding goes through
    core/quantize.dequantize_rows itself, so the distances are exact over
    the int8 tier's point set by construction.
    """
    rows = qz.dequantize_rows(codes, scale, zp, get_family(family))
    return bregman_refine_batch(rows, grad, c_y, family)


def bregman_refine(rows: Array, grad: Array, c_y: Array, family: str) -> Array:
    """Exact D_f for selected rows.  (b,d),(d,),() -> (b,)."""
    fam = get_family(family)
    fx = jnp.sum(fam.phi(rows), axis=-1)
    return fx - jnp.dot(rows, grad, precision=F32_PRECISION) + c_y


def bregman_refine_batch(rows: Array, grad: Array, c_y: Array,
                         family: str) -> Array:
    """Exact D_f per query's candidate rows.  (q,b,d),(q,d),(q,) -> (q,b).

    Shared rows (n,d) -> (q,n), one query at a time.  Both forms reduce each
    (query, row) term over d in the same elementwise way, so a row's
    distance is bit for bit the same whichever form computes it.
    """
    fam = get_family(family)
    fx = jnp.sum(fam.phi(rows), axis=-1)                  # (q, b) or (n,)
    if rows.ndim == 2:
        return jax.lax.map(
            lambda a: fx - jnp.sum(rows * a[0], axis=-1) + a[1],
            (grad, c_y))
    cross = jnp.sum(rows * grad[:, None, :], axis=-1)     # (q, b)
    return fx - cross + c_y[:, None]


def pccp_correlation(x: Array) -> Array:
    """|Pearson| correlation matrix with zeroed diagonal.  (n,d) -> (d,d)."""
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    std = jnp.sqrt(jnp.mean(xc * xc, axis=0))
    std = jnp.where(std < 1e-12, 1.0, std)
    corr = (xc.T @ xc) / (x.shape[0] * std[:, None] * std[None, :])
    corr = jnp.abs(corr)
    return corr * (1.0 - jnp.eye(x.shape[1], dtype=x.dtype))


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int | None = None, scale: float | None = None) -> Array:
    """Naive GQA attention oracle.

    q: (B, H, Sq, D); k/v: (B, KH, Skv, D) with H % KH == 0.
    ``window``: sliding-window size (local attention) if given.
    """
    b, h, sq, d = q.shape
    kh = k.shape[1]
    rep = h // kh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    skv = k.shape[2]
    qi = jnp.arange(sq)[:, None] + (skv - sq)   # align ends (decode offsets)
    ki = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= qi >= ki
    if window is not None:
        mask &= (qi - ki) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
