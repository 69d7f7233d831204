"""Dispatching jit wrappers around the Pallas kernels.

Backend policy: on TPU the compiled Pallas kernels run; on CPU the
pure-jnp references run by default so that jitted programs (including the
512-device dry-run) lower through stock XLA, and ``impl='interpret'``
forces the Pallas interpreter for kernel validation.

Set env ``REPRO_KERNEL_IMPL`` to 'pallas' | 'interpret' | 'ref' to override.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref
from . import bregman_ub as _ub
from . import bregman_dist as _dist
from . import bregman_fused as _fused
from . import bregman_prune as _prune
from . import pccp_corr as _corr
from . import flash_attention as _flash


def _impl(override: str | None = None) -> str:
    if override:
        return override
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# BrePartition filter + refine
# ---------------------------------------------------------------------------

def bregman_ub_matrix(alpha, sqrt_gamma, qconst, sqrt_delta, impl=None):
    """(n, q) UB totals for a query batch."""
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_ub_matrix(alpha, sqrt_gamma, qconst, sqrt_delta)
    qsum = jnp.sum(qconst, axis=-1)
    return _ub.bregman_ub_matrix(alpha, sqrt_gamma, qsum, sqrt_delta,
                                 interpret=(mode == "interpret"))


def bregman_ub_matrix_quant(alpha_q, alpha_scale, alpha_zp, sg_q, sg_scale,
                            sg_zp, qconst, sqrt_delta, impl=None):
    """(n, q) UB totals from the int8 filter tables (per-row affine decode)."""
    if qconst.ndim != 2 or sqrt_delta.ndim != 2:
        raise ValueError(
            "bregman_ub_matrix_quant wants (q, M) query batches, got "
            f"{qconst.shape}/{sqrt_delta.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_ub_matrix_quant(alpha_q, alpha_scale, alpha_zp,
                                           sg_q, sg_scale, sg_zp,
                                           qconst, sqrt_delta)
    qsum = jnp.sum(qconst, axis=-1)
    return _ub.bregman_ub_matrix_quant(alpha_q, alpha_scale, alpha_zp,
                                       sg_q, sg_scale, sg_zp, qsum,
                                       sqrt_delta,
                                       interpret=(mode == "interpret"))


def bregman_prune_block(amin, gmax, qconst, sqrt_delta, qb, impl=None):
    """Theorem-3 admit mask for a row block.  (n,M)x2, (q,M)x3 -> (n,q) int32.

    The per-point stage of the streaming prune+compact scan
    (core/search._stream_prune_compact): one fused corner-compare pass per
    block, no (n, M, q) lower-bound tensor outside the kernel.
    """
    if qconst.ndim != 2 or sqrt_delta.ndim != 2 or qb.ndim != 2:
        raise ValueError(
            "bregman_prune_block wants (q, M) query operands, got "
            f"{qconst.shape}/{sqrt_delta.shape}/{qb.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_prune_mask(amin, gmax, qconst, sqrt_delta, qb)
    return _prune.bregman_prune_mask(amin, gmax, qconst, sqrt_delta, qb,
                                     interpret=(mode == "interpret"))


def bregman_prune_block_quant(amin_q, amin_scale, amin_zp, gmax_q,
                              gmax_scale, gmax_zp, qconst, sqrt_delta, qb,
                              impl=None):
    """Admit mask from int8 corner codes (per-row affine, directed-rounded)."""
    if qconst.ndim != 2 or sqrt_delta.ndim != 2 or qb.ndim != 2:
        raise ValueError(
            "bregman_prune_block_quant wants (q, M) query operands, got "
            f"{qconst.shape}/{sqrt_delta.shape}/{qb.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_prune_mask_quant(
            amin_q, amin_scale, amin_zp, gmax_q, gmax_scale, gmax_zp,
            qconst, sqrt_delta, qb)
    return _prune.bregman_prune_mask_quant(
        amin_q, amin_scale, amin_zp, gmax_q, gmax_scale, gmax_zp,
        qconst, sqrt_delta, qb, interpret=(mode == "interpret"))


def bregman_filter_prune_block(alpha, sqrt_gamma, amin, gmax, qconst,
                               sqrt_delta, qb, impl=None):
    """Fused filter UB + Theorem-3 admit for a row block -> (ub, admit).

    One VMEM-resident pass computes the (n, q) f32 upper-bound tile AND the
    (n, q) int32 admit mask (core/search._stream_prune_compact's fused
    path): the UB values never round-trip through HBM between the filter
    and prune phases, and the transposed ``sqrt_delta`` tile is read once
    for both.  Callers that only need the admit mask discard ``ub`` — in
    ``ref`` mode XLA dead-code-eliminates the matmul; on TPU the kernel
    computes it in the same pass (that is the point).
    """
    if qconst.ndim != 2 or sqrt_delta.ndim != 2 or qb.ndim != 2:
        raise ValueError(
            "bregman_filter_prune_block wants (q, M) query operands, got "
            f"{qconst.shape}/{sqrt_delta.shape}/{qb.shape}")
    if alpha.shape != amin.shape:
        raise ValueError(
            "filter and corner tables must share (n, M), got "
            f"{alpha.shape} vs {amin.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_filter_prune(alpha, sqrt_gamma, amin, gmax,
                                        qconst, sqrt_delta, qb)
    qsum = jnp.sum(qconst, axis=-1)
    return _fused.bregman_filter_prune(alpha, sqrt_gamma, amin, gmax, qsum,
                                       qconst, sqrt_delta, qb,
                                       interpret=(mode == "interpret"))


def bregman_filter_prune_block_quant(alpha_q, alpha_scale, alpha_zp, sg_q,
                                     sg_scale, sg_zp, amin_q, amin_scale,
                                     amin_zp, gmax_q, gmax_scale, gmax_zp,
                                     qconst, sqrt_delta, qb, impl=None):
    """Fused (ub, admit) from int8 filter + corner codes (per-row affine)."""
    if qconst.ndim != 2 or sqrt_delta.ndim != 2 or qb.ndim != 2:
        raise ValueError(
            "bregman_filter_prune_block_quant wants (q, M) query operands, "
            f"got {qconst.shape}/{sqrt_delta.shape}/{qb.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_filter_prune_quant(
            alpha_q, alpha_scale, alpha_zp, sg_q, sg_scale, sg_zp,
            amin_q, amin_scale, amin_zp, gmax_q, gmax_scale, gmax_zp,
            qconst, sqrt_delta, qb)
    qsum = jnp.sum(qconst, axis=-1)
    return _fused.bregman_filter_prune_quant(
        alpha_q, alpha_scale, alpha_zp, sg_q, sg_scale, sg_zp,
        amin_q, amin_scale, amin_zp, gmax_q, gmax_scale, gmax_zp,
        qsum, qconst, sqrt_delta, qb, interpret=(mode == "interpret"))


def bregman_refine(rows, grad, c_y, family: str, impl=None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_refine(rows, grad, c_y, family)
    return _dist.bregman_refine(rows, grad, c_y, family,
                                interpret=(mode == "interpret"))


def bregman_refine_batch(rows, grad, c_y, family: str, impl=None):
    """Per-query exact distances.  (q,b,d),(q,d),(q,) -> (q,b); rows
    (n,d) shared by every query -> (q,n)."""
    if rows.ndim not in (2, 3) or grad.ndim != 2:
        raise ValueError(
            "bregman_refine_batch wants (q,b,d) or shared (n,d) rows with "
            f"(q,d) grads, got {rows.shape}/{grad.shape}; use "
            "bregman_refine for one query")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_refine_batch(rows, grad, c_y, family)
    return _dist.bregman_refine_batch(rows, grad, c_y, family,
                                      interpret=(mode == "interpret"))


def bregman_refine_batch_quant(codes, scale, zp, grad, c_y, family: str,
                               impl=None):
    """Fused dequantize + exact distances.  (q,b,d) int8,(q,b),(q,b) -> (q,b);
    shared (n,d) codes with (n,) decode scalars -> (q,n)."""
    if (codes.ndim not in (2, 3) or scale.ndim != codes.ndim - 1
            or grad.ndim != 2):
        raise ValueError(
            "bregman_refine_batch_quant wants (q,b,d) codes with (q,b) "
            "decode rows, or shared (n,d) codes with (n,) ones, got "
            f"{codes.shape}/{scale.shape}/{grad.shape}")
    mode = _impl(impl)
    if mode == "ref":
        return ref.bregman_refine_batch_quant(codes, scale, zp, grad, c_y,
                                              family)
    return _dist.bregman_refine_batch_quant(codes, scale, zp, grad, c_y,
                                            family,
                                            interpret=(mode == "interpret"))


def pccp_correlation(x, impl=None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.pccp_correlation(x)
    return _corr.pccp_correlation(x, interpret=(mode == "interpret"))


def flash_attention(q, k, v, *, causal=True, window=None, scale=None, impl=None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.attention(q, k, v, causal=causal, window=window, scale=scale)
    return _flash.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=(mode == "interpret"))
