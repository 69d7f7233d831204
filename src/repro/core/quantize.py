"""Int8 storage tier for the BallForest: per-row quantizers + error bounds.

Memory is the binding constraint at "millions of users" scale: the (n, d)
point table plus the four (n, M) filter/corner tables are all fp32, and the
filter matmul's HBM traffic is what the batched pipeline streams per query
block.  This module provides the lossy-storage side of the fix; the search
pipeline stays *provably admissible* because every bound the pruning math
consumes is inflated (or directly rounded) to cover the quantization error —
the same bound-slack tactic used to survive a missing triangle inequality in
approximate Bregman search (Abdullah et al.) and decomposable-divergence
kd-trees (Pham & Wagner).  See docs/quantization.md for the derivation.

Contract (the one sentence everything below serves):

    The int8 index's point set IS the dequantized rows ``x_hat``; search
    over the int8 tier returns the EXACT kNN of ``x_hat`` — identical ids
    and distances to a fp32 BallForest built over the same ``x_hat``.

Three quantizer shapes, all per-row (so mutation never needs global refits
and a row's error bound travels with the row):

* **data rows** — affine int8 over each (d,) row: ``x_hat = codes * scale
  + zp``, clamped into the family domain.  Refinement dequantizes only the
  surviving candidate rows (kernels/bregman_dist.bregman_refine_batch_quant).
* **filter stats** (``alpha``/``sqrt_gamma``) — affine int8 over each (M,)
  row, round-to-nearest, so ``|stat_hat - stat| <= scale/2``.  The Alg.-4
  searching bounds are inflated by :data:`UB_SLACK` * (alpha_scale +
  sqrt_gamma_scale * sqrt_delta_i) maximized over the filter's top-k rows
  — enough to cover the worst-case rounding of any row that determined the
  k-th upper bound (core/search.py `_qb_slack`).
* **corner stats** (``alpha_min_pt``/``sqrt_gamma_max_pt``) — affine int8
  with DIRECTED rounding: alpha_min floors, sqrt_gamma_max ceils, so the
  dequantized corner is always on the conservative side of the true corner
  and the Theorem-3 cluster lower bound can only get smaller.  No
  query-time slack needed for the prune.

Quantizing a row of identical values stores ``scale = 0`` (codes all zero,
``zp`` carries the exact value), which doubles as the search-inert fill:
a tombstoned/padded int8 row has zero scales — contributing nothing to any
bound slack — and sentinel zero-points (core/index.inert_fill).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .bregman import BregmanFamily

Array = jax.Array

# Families whose generator domain is the open positive axis; dequantized
# rows are clamped to >= DOMAIN_EPS there (matching BregmanFamily.project)
# so rounding can never push a stored point out of the domain.
POSITIVE_FAMILIES = frozenset({"itakura_saito", "burg", "shannon"})
DOMAIN_EPS = 1e-6

# Half-step rounding bound with a small float-evaluation safety margin; the
# factor multiplies a stored per-row scale, so the slack it adds to the
# Alg.-4 bounds is ~the quantization step — negligible against the bounds
# themselves, but enough to absorb fp32 round-off in the dequant chain.
UB_SLACK = 0.5 * (1.0 + 1e-3)

# Affine range: codes live in [-127, 127] (255 levels).  The symmetric
# range keeps the directed-rounding headroom: a ceil can land on +127 and a
# floor on -128 without leaving int8.
_LEVELS = 254.0
# Directed rounding needs the row extremes strictly inside the code range
# so float fuzz in (v - zp) / scale cannot ceil past +127.
_DIRECTED_PAD = 1.0 + 1e-6


def _row_affine(v: Array, pad: float = 1.0) -> tuple[Array, Array]:
    """Per-row (scale, zero_point) covering [min, max] of the trailing axis.

    Constant rows get ``scale = 0`` — codes are zero and ``zp`` is exact.
    """
    lo = jnp.min(v, axis=-1)
    hi = jnp.max(v, axis=-1)
    zp = 0.5 * (hi + lo)
    scale = (hi - lo) * (pad / _LEVELS)
    return scale, zp


def _encode(v: Array, scale: Array, zp: Array, rounding: str) -> Array:
    div = jnp.where(scale > 0, scale, 1.0)
    t = (v - zp[..., None]) / div[..., None]
    if rounding == "nearest":
        t = jnp.round(t)
    elif rounding == "floor":
        t = jnp.floor(t)
    elif rounding == "ceil":
        t = jnp.ceil(t)
    else:
        raise ValueError(f"unknown rounding {rounding!r}")
    return jnp.clip(t, -128, 127).astype(jnp.int8)


def quantize_rows(x: Array) -> tuple[Array, Array, Array]:
    """Affine int8 per (d,) row: (codes (n, d) int8, scale (n,), zp (n,))."""
    x = jnp.asarray(x, jnp.float32)
    scale, zp = _row_affine(x)
    return _encode(x, scale, zp, "nearest"), scale, zp


def dequantize_rows(codes: Array, scale: Array, zp: Array,
                    family: BregmanFamily) -> Array:
    """``x_hat``: the int8 tier's point set, clamped into the family domain.

    This expression is THE definition of the stored points — the refine
    kernels (ref, Pallas, interpret) reproduce it term for term so the
    distances they report are exact over ``x_hat``.
    """
    x = codes.astype(jnp.float32) * scale[..., None] + zp[..., None]
    return clamp_domain(x, family.name)


def clamp_domain(x: Array, family_name: str) -> Array:
    """Domain projection shared by dequantize_rows and the refine kernels."""
    if family_name in POSITIVE_FAMILIES:
        return jnp.maximum(x, DOMAIN_EPS)
    return x


def quantize_stats(v: Array, rounding: str = "nearest",
                   ) -> tuple[Array, Array, Array]:
    """Affine int8 per (M,) stat row: (codes int8, scale (n,), zp (n,)).

    ``rounding='nearest'`` (filter stats): ``|dequant - v| <= scale / 2``.
    ``rounding='floor'``/``'ceil'`` (corner stats): the dequantized value is
    <= / >= the true value — conservative by construction, so the pruning
    lower bound needs no query-time correction.
    """
    v = jnp.asarray(v, jnp.float32)
    pad = 1.0 if rounding == "nearest" else _DIRECTED_PAD
    scale, zp = _row_affine(v, pad=pad)
    return _encode(v, scale, zp, rounding), scale, zp


def dequantize_stats(codes: Array, scale: Array, zp: Array) -> Array:
    """Per-row affine decode for the (n, M) stat tables."""
    return codes.astype(jnp.float32) * scale[..., None] + zp[..., None]


def decoded_corner_tables(forest) -> tuple[Array, Array]:
    """Full (n, M) fp32 corner tables of an index (decoded in the int8 tier).

    The int8 corners were DIRECTED-rounded at encode (alpha_min floored,
    sqrt_gamma_max ceiled), so the values returned here are conservative
    and every consumer — the per-point Theorem-3 test, and the block
    envelopes reduced over these exact values (core/index.corner_envelopes)
    — needs no further slack.  Duck-typed over anything with the
    BallForest corner fields so core/index.py and core/search.py share one
    decode.
    """
    amin, gmax = forest.alpha_min_pt, forest.sqrt_gamma_max_pt
    if forest.storage == "int8":
        amin = dequantize_stats(amin, forest.amin_scale, forest.amin_zp)
        gmax = dequantize_stats(gmax, forest.gmax_scale, forest.gmax_zp)
    return amin, gmax


def ub_slack(alpha_scale: Array, sg_scale: Array, sqrt_delta: Array) -> Array:
    """Alg.-4 bound inflation from filter-stat scales — THE slack formula.

    ``alpha_scale``/``sg_scale`` are the (…,) per-query maxima of the
    stat scales over the filter's top-k rows; ``sqrt_delta`` is (…, M).
    Returns the (…, M) componentwise inflation whose row sum dominates
    the worst-case decoded-vs-true UB error of any row that could have
    determined the k-th bound (docs/quantization.md).  Shared by the
    batched and distributed bound computations so the
    admissibility-critical expression exists exactly once.
    """
    return UB_SLACK * (alpha_scale[..., None]
                       + sg_scale[..., None] * sqrt_delta)


def encode_corner_tables(alpha_min_pt: Array,
                         sqrt_gamma_max_pt: Array) -> dict:
    """Directed-rounded int8 corner fields (the Theorem-3 invariant).

    alpha_min FLOORS and sqrt_gamma_max CEILS — the one rule that keeps
    the decoded cluster lower bound conservative.  Every site that
    (re-)encodes corners goes through here so the direction can never be
    transposed in one copy.  Returns the BallForest field dict.
    """
    am_q, am_s, am_z = quantize_stats(alpha_min_pt, "floor")
    gm_q, gm_s, gm_z = quantize_stats(sqrt_gamma_max_pt, "ceil")
    return {"alpha_min_pt": am_q, "amin_scale": am_s, "amin_zp": am_z,
            "sqrt_gamma_max_pt": gm_q, "gmax_scale": gm_s, "gmax_zp": gm_z}


def encode_stat_tables(alpha: Array, sqrt_gamma: Array, alpha_min_pt: Array,
                       sqrt_gamma_max_pt: Array) -> dict:
    """Int8 field dict for all four (n, M) stat tables of a point block.

    Filter stats round to nearest (covered by :func:`ub_slack` at query
    time); corners go through :func:`encode_corner_tables`.
    """
    a_q, a_s, a_z = quantize_stats(alpha, "nearest")
    g_q, g_s, g_z = quantize_stats(sqrt_gamma, "nearest")
    return {"alpha": a_q, "alpha_scale": a_s, "alpha_zp": a_z,
            "sqrt_gamma": g_q, "sg_scale": g_s, "sg_zp": g_z,
            **encode_corner_tables(alpha_min_pt, sqrt_gamma_max_pt)}
