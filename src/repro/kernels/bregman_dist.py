"""Pallas TPU kernel — fused exact Bregman refinement distance.

    D_f(x, y) = sum_j phi(x_j)  -  x . phi'(y)  +  c_y

for a tile of candidate rows: the elementwise generator runs on the VPU and
the gradient inner product on the MXU, accumulated over d-tiles so the VMEM
working set is (block_b x block_d) regardless of dimensionality.  The
generator phi is selected statically per Bregman family (closure), so each
family compiles its own fused kernel.

The batch kernels take the candidate rows in one of two shapes:

* per query, (q, b, d): each query's own gathered candidates -> (q, b);
* shared, (n, d): one table that every query of the batch refines against
  -> (q, n).  Each row tile is read from HBM once for the whole batch: its
  sum_j phi(x_j) (and the int8 decode) is computed once, and the cross
  term of every query is one (q, d) x (d, rows) MXU product with the row
  axis on lanes.  A ragged last row tile is read past the table's end and
  its columns are dropped, so the table is never padded or copied.  Where
  the TPU stores the table column-major (:func:`stored_by_columns`), the
  kernel reads it as the (d, n) array it is, with no transpose at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bounds import F32_PRECISION
from repro.core.bregman import get_family
from repro.core.quantize import DOMAIN_EPS, POSITIVE_FAMILIES

# phi implementations usable inside the kernel (elementwise, mask-aware:
# padded columns carry x=0 AND grad=0; `mask` zeroes the phi contribution).
_PHIS = {
    "squared_euclidean": lambda x: 0.5 * x * x,
    "itakura_saito": lambda x: -jnp.log(jnp.maximum(x, 1e-30)),
    "exponential": jnp.exp,
    "burg": lambda x: x - jnp.log(jnp.maximum(x, 1e-30)),
    "shannon": lambda x: x * jnp.log(jnp.maximum(x, 1e-30)),
}


def bregman_refine(
    rows: jax.Array,    # (b, d) candidate points
    grad: jax.Array,    # (d,)   phi'(y)
    c_y: jax.Array,     # ()     sum_j (y_j phi'(y_j) - phi(y_j))
    family: str,
    *,
    block_b: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Exact D_f(rows[i], y) -> (b,): the q=1 slice of the batch kernel.

    Delegating keeps ONE kernel body (accumulation, family-specific safe
    padding) serving both entry points.
    """
    return bregman_refine_batch(
        rows[None], grad[None], c_y[None], family,
        block_b=block_b, block_d=block_d, interpret=interpret)[0]


def _cross(x, grad):
    """(bb, bd) . (1, bd) -> (bb, 1): the refine's x . phi'(y) term."""
    return jax.lax.dot_general(x, grad, (((1,), (1,)), ((), ())),
                               precision=F32_PRECISION,
                               preferred_element_type=jnp.float32)


# f32 bytes of one shared-form row tile (rows x whole d): the double-buffered
# input, its transpose and phi of it stay well inside a v5e core's VMEM.
SHARED_TILE_BYTES = 1 << 20
# Queries per grid step of the shared form; a larger batch takes more steps
# over the same row tile, which stays resident between them.
SHARED_QUERY_BLOCK = 256


def stored_by_columns(n: int, d: int) -> bool:
    """Whether a TPU's default layout keeps an (n, d) table column-major.

    XLA orders the two dimensions so that the (8, 128) tiles pad less: a d
    off the 128-lane grid (Fonts' 400, not Deep's 256) is stored as (d, n).
    Reading the table in its stored order keeps XLA from copying it into
    the other before the kernel; a wrong guess costs that copy, never a
    wrong answer.
    """
    def padded(major, minor):
        return -(-major // 8) * 8 * (-(-minor // 128) * 128)
    return padded(d, n) < padded(n, d)


def _tile_dists(phi, xt, grad, c):
    """Distances of one (d, bb) row tile, rows on lanes, to a query block.

    (d, bb) rows, (qb, d) phi'(y), (qb, 1) c_y -> (qb, bb)."""
    fx = jnp.sum(phi(xt), axis=0, keepdims=True)               # (1, bb)
    cross = jnp.dot(grad, xt, precision=F32_PRECISION,
                    preferred_element_type=jnp.float32)        # (qb, bb)
    return fx - cross + c


def _shared_call(make_kernel, table, row_vectors, grad, c_y, block_b,
                 interpret):
    """Run a shared-form kernel over an (n, d) ``table`` and (n,)
    ``row_vectors`` -> (q, n).

    ``make_kernel(by_columns)`` builds the body for a table tile of
    (d, bb) (the table stored column-major) or (bb, d).  Grid: row tiles
    outer, query blocks inner, so each row tile is fetched once however
    many query blocks the batch has.
    """
    n, d = table.shape
    q = grad.shape[0]
    if block_b is None:
        block_b = max(128, SHARED_TILE_BYTES // (4 * d) // 128 * 128)
    bb = n if n <= block_b else block_b
    qb = q if q <= SHARED_QUERY_BLOCK else SHARED_QUERY_BLOCK
    q_pad = -q % qb
    g = jnp.pad(grad, ((0, q_pad), (0, 0)))
    c = jnp.pad(c_y, (0, q_pad))[:, None]
    by_columns = stored_by_columns(n, d)
    if by_columns:
        table = table.T
        table_spec = pl.BlockSpec((d, bb), lambda i, qi: (0, i))
    else:
        table_spec = pl.BlockSpec((bb, d), lambda i, qi: (i, 0))
    # Per-row vectors ride as (1, n) rows, one lane per table row.
    lanes = pl.BlockSpec((1, bb), lambda i, qi: (0, i))
    out = pl.pallas_call(
        make_kernel(by_columns),
        grid=(pl.cdiv(n, bb), (q + q_pad) // qb),
        in_specs=[table_spec] + [lanes] * len(row_vectors) + [
            pl.BlockSpec((qb, d), lambda i, qi: (qi, 0)),
            pl.BlockSpec((qb, 1), lambda i, qi: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((qb, bb), lambda i, qi: (qi, i)),
        out_shape=jax.ShapeDtypeStruct((q + q_pad, n), jnp.float32),
        interpret=interpret,
    )(table, *[v[None] for v in row_vectors], g, c)
    return out[:q] if q_pad else out


def _make_shared_kernel(phi):
    def make(by_columns):
        def kernel(rows_ref, grad_ref, c_ref, out_ref):
            x = rows_ref[...]
            xt = x if by_columns else x.T                      # (d, bb)
            out_ref[...] = _tile_dists(phi, xt, grad_ref[...], c_ref[...])

        return kernel

    return make


def _make_batch_kernel(phi):
    def kernel(rows_ref, grad_ref, mask_ref, acc_ref):
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        rows = rows_ref[0]                         # (bb, bd)
        grad = grad_ref[0]                         # (1, bd) — this query's tile
        mask = mask_ref[...]                       # (1, bd)
        fx = jnp.sum(phi(rows) * mask, axis=-1, keepdims=True)      # VPU
        acc_ref[0] += fx - _cross(rows, grad)      # (bb, 1)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("family", "block_b", "block_d", "interpret")
)
def bregman_refine_batch(
    rows: jax.Array,    # (q, b, d) per-query candidate rows, or (n, d) shared
    grad: jax.Array,    # (q, d)    per-query phi'(y)
    c_y: jax.Array,     # (q,)      per-query additive constant
    family: str,
    *,
    block_b: int | None = None,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Exact D_f(rows[q, i], y_q) -> (q, b): one call refines the whole batch.

    The query axis rides the grid's outermost dimension, so every query's
    candidate tile reuses the same compiled body with its own grad/c_y tile —
    the batched analogue of :func:`bregman_refine` (one program, q x b rows).

    Shared rows (n, d): D_f(rows[i], y_q) -> (q, n), each row read once for
    every query (module docstring).  ``block_b`` is the row tile (default:
    256 per query, ~``SHARED_TILE_BYTES`` of whole rows shared); the shared
    form reads whole rows, so ``block_d`` tiles the per-query form only.
    """
    fam = get_family(family)
    phi = _PHIS[fam.name]
    if rows.ndim == 2:
        return _shared_call(_make_shared_kernel(phi), rows, (), grad, c_y,
                            block_b, interpret)
    q, b, d = rows.shape
    bb = min(block_b or 256, max(8, b))
    bd = min(block_d, max(128 if not interpret else 8, d))
    b_pad, d_pad = -b % bb, -d % bd

    # Padded columns: rows padded with a domain-safe value, masked out of phi;
    # grad padded with 0 so the matmul ignores them.
    safe = 1.0 if fam.name in ("itakura_saito", "burg", "shannon") else 0.0
    r = jnp.pad(rows, ((0, 0), (0, b_pad), (0, d_pad)), constant_values=safe)
    # Per-query operands carry a unit middle axis so each block's last two
    # dims are (1, bd): a (1, bd) block of a (q, d) array breaks the TPU's
    # (8, 128) tiling rule.
    g = jnp.pad(grad, ((0, 0), (0, d_pad)))[:, None, :]
    mask = jnp.pad(jnp.ones((1, d), rows.dtype), ((0, 0), (0, d_pad)))
    _, bp, dp = r.shape

    out = pl.pallas_call(
        _make_batch_kernel(phi),
        grid=(q, bp // bb, dp // bd),
        in_specs=[
            pl.BlockSpec((1, bb, bd), lambda qi, i, j: (qi, i, j)),
            pl.BlockSpec((1, 1, bd), lambda qi, i, j: (qi, 0, j)),
            pl.BlockSpec((1, bd), lambda qi, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bb, 1), lambda qi, i, j: (qi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, bp, 1), jnp.float32),
        interpret=interpret,
    )(r, g, mask)
    return out[:, :b, 0] + c_y[:, None]


def _make_quant_shared_kernel(phi, positive: bool):
    def make(by_columns):
        def kernel(codes_ref, scale_ref, zp_ref, grad_ref, c_ref, out_ref):
            # The decode of core/quantize.dequantize_rows, once per row
            # tile, with each row's (scale, zp) on its lane.
            x = codes_ref[...].astype(jnp.float32)
            xt = x if by_columns else x.T                      # (d, bb)
            xt = xt * scale_ref[...] + zp_ref[...]
            if positive:
                xt = jnp.maximum(xt, DOMAIN_EPS)
            out_ref[...] = _tile_dists(phi, xt, grad_ref[...], c_ref[...])

        return kernel

    return make


def _make_quant_batch_kernel(phi, positive: bool):
    def kernel(codes_ref, scale_ref, zp_ref, grad_ref, mask_ref, acc_ref):
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Fused dequantize: the only HBM read of the candidate rows is the
        # int8 codes; the affine decode (+ the domain clamp shared with
        # core/quantize.dequantize_rows) happens on-chip per tile.
        x = codes_ref[0].astype(jnp.float32)       # (bb, bd)
        x = x * scale_ref[0] + zp_ref[0]           # (bb, 1) row decode
        if positive:
            x = jnp.maximum(x, DOMAIN_EPS)
        grad = grad_ref[0]                         # (1, bd)
        mask = mask_ref[...]                       # (1, bd)
        fx = jnp.sum(phi(x) * mask, axis=-1, keepdims=True)          # VPU
        acc_ref[0] += fx - _cross(x, grad)         # (bb, 1)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("family", "block_b", "block_d", "interpret")
)
def bregman_refine_batch_quant(
    codes: jax.Array,   # (q, b, d) int8 candidate-row codes, or (n, d) shared
    scale: jax.Array,   # (q, b)    per-row affine scale, or (n,) shared
    zp: jax.Array,      # (q, b)    per-row affine zero-point, or (n,) shared
    grad: jax.Array,    # (q, d)    per-query phi'(y)
    c_y: jax.Array,     # (q,)      per-query additive constant
    family: str,
    *,
    block_b: int | None = None,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Dequantize + exact D_f over int8 candidate rows -> (q, b).

    The int8-tier sibling of :func:`bregman_refine_batch`: same grid, the
    row tile arrives as codes plus two per-row decode scalars, and the
    dequantized values match ``core/quantize.dequantize_rows`` bit for bit
    so the reported distances are exact over the stored point set.
    Padded rows decode via (scale 0, zp 1) to the domain-safe ones-row;
    padded columns carry code 0 with grad/mask 0.  Shared codes (n, d) with
    (n,) decode scalars -> (q, n), each tile decoded once for every query;
    the scalars ride as (1, n) rows, one lane per table row.
    """
    fam = get_family(family)
    phi = _PHIS[fam.name]
    positive = fam.name in POSITIVE_FAMILIES
    if codes.ndim == 2:
        return _shared_call(_make_quant_shared_kernel(phi, positive), codes,
                            (scale, zp), grad, c_y, block_b, interpret)
    q, b, d = codes.shape
    bb = min(block_b or 256, max(32 if not interpret else 8, b))
    bd = min(block_d, max(128 if not interpret else 8, d))
    b_pad, d_pad = -b % bb, -d % bd

    r = jnp.pad(codes, ((0, 0), (0, b_pad), (0, d_pad)))
    # Decode scalars ride as (bb, 1) columns and grad as a (1, bd) row of a
    # unit middle axis, so every block obeys the (8, 128) tiling rule.
    s = jnp.pad(scale, ((0, 0), (0, b_pad)))[:, :, None]
    z = jnp.pad(zp, ((0, 0), (0, b_pad)), constant_values=1.0)[:, :, None]
    g = jnp.pad(grad, ((0, 0), (0, d_pad)))[:, None, :]
    mask = jnp.pad(jnp.ones((1, d), jnp.float32), ((0, 0), (0, d_pad)))
    _, bp, dp = r.shape

    out = pl.pallas_call(
        _make_quant_batch_kernel(phi, positive),
        grid=(q, bp // bb, dp // bd),
        in_specs=[
            pl.BlockSpec((1, bb, bd), lambda qi, i, j: (qi, i, j)),
            pl.BlockSpec((1, bb, 1), lambda qi, i, j: (qi, i, 0)),
            pl.BlockSpec((1, bb, 1), lambda qi, i, j: (qi, i, 0)),
            pl.BlockSpec((1, 1, bd), lambda qi, i, j: (qi, 0, j)),
            pl.BlockSpec((1, bd), lambda qi, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bb, 1), lambda qi, i, j: (qi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, bp, 1), jnp.float32),
        interpret=interpret,
    )(r, s, z, g, mask)
    return out[:, :b, 0] + c_y[:, None]
