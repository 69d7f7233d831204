"""The plain reference: exact k nearest rows under a Bregman divergence.

D_f(x, y) = sum_i f(x_i) - f(y_i) - f'(y_i) (x_i - y_i), evaluated term by
term against every stored row (no matrix product, so no reduced-precision
pass), then top-k.  It imports nothing of the
program under test and takes nothing it made: the rows are the
benchmark's own, and a stored-int8 deployment's point set is decoded
here from the documented per-row affine code (``decode``).

``storage`` names the point set and its arithmetic: ``f32`` and ``int8``
are the configurations' own; ``bf16`` and ``int4`` are the controls, the
next precision below each, which the comparison must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import POSITIVE_FAMILIES, family_name

# Positive-domain families clamp decoded rows here (the domain's edge).
DOMAIN_EPS = 1e-6
# Per-row affine codes: int8 keeps 255 levels in [-127, 127], int4 15 in
# [-7, 7]; a row of equal values stores scale 0 and decodes exactly.
CODE_RANGE = {"int8": (254.0, -128, 127), "int4": (14.0, -8, 7)}

FAMILIES = {
    "exponential": (jnp.exp, jnp.exp),
    "squared_euclidean": (lambda x: x * x, lambda x: 2.0 * x),
    "itakura_saito": (lambda x: -jnp.log(x), lambda x: -1.0 / x),
    "burg": (lambda x: x - jnp.log(x), lambda x: 1.0 - 1.0 / x),
    "shannon": (lambda x: x * jnp.log(x), lambda x: jnp.log(x) + 1.0),
}


def decode(rows, storage: str, family: str):
    """The point set a deployment stores: the rows, or their decoded codes."""
    if storage in ("f32", "bf16"):
        return rows
    levels, lo_code, hi_code = CODE_RANGE[storage]
    lo = jnp.min(rows, axis=-1)
    hi = jnp.max(rows, axis=-1)
    zp = 0.5 * (hi + lo)
    scale = (hi - lo) * (1.0 / levels)
    div = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round((rows - zp[:, None]) / div[:, None]),
                     lo_code, hi_code)
    x = codes * scale[:, None] + zp[:, None]
    if family_name(family) in POSITIVE_FAMILIES:
        x = jnp.maximum(x, DOMAIN_EPS)
    return x


@functools.partial(jax.jit, static_argnames=("k", "family", "low"))
def _topk(points, ys, k: int, family: str, low: bool):
    phi, dphi = FAMILIES[family]
    if low:                     # bf16 terms, f32 accumulation
        points = points.astype(jnp.bfloat16)
        ys = ys.astype(jnp.bfloat16)
    f_x = phi(points)

    def one(y):
        terms = f_x - phi(y) - dphi(y) * (points - y)
        d = jnp.sum(terms, axis=-1, dtype=jnp.float32)
        neg, idx = jax.lax.top_k(-d, k)
        return idx, -neg

    return jax.lax.map(one, ys)


@functools.partial(jax.jit, static_argnames=("k", "family"))
def _judge(points, ys, ids, k: int, family: str):
    """Each query's f32 top-k distances and the f32 D_f of the rows
    ``ids`` (q, k) it is judged on, both read from one distance vector."""
    phi, dphi = FAMILIES[family]
    f_x = phi(points)

    def one(args):
        y, own = args
        d = jnp.sum(f_x - phi(y) - dphi(y) * (points - y), axis=-1)
        return -jax.lax.top_k(-d, k)[0], jnp.take(d, own)

    return jax.lax.map(one, (ys, ids))


@functools.partial(jax.jit, static_argnames=("family",))
def _term_scale(ys, family: str):
    phi, dphi = FAMILIES[family]
    return jnp.sum(jnp.abs(phi(ys)) + jnp.abs(ys * dphi(ys)), axis=-1)


class Reference:
    """Exact top-k over one deployment's point set, on the default device."""

    def __init__(self, rows, storage: str, family: str):
        self.family = family_name(family)
        self.storage = storage
        self.points = decode(jnp.asarray(rows, jnp.float32), storage,
                             self.family)

    def topk(self, ys, k: int):
        """(ids (q, k), dists (q, k)), ascending, as numpy."""
        ids, d = _topk(self.points, jnp.asarray(ys, jnp.float32), k=k,
                       family=self.family, low=self.storage == "bf16")
        return np.asarray(ids), np.asarray(d)

    def judge(self, ys, ids, k: int):
        """(reference top-k distances, D_f of the rows ``ids``), (q, k)
        each, from the same f32 evaluation; ``ids`` must be in range."""
        ref_d, own = _judge(self.points, jnp.asarray(ys, jnp.float32),
                            jnp.asarray(ids, jnp.int32), k=k,
                            family=self.family)
        return np.asarray(ref_d), np.asarray(own)

    def term_scale(self, ys):
        """Per query sum |f(y)| + |y f'(y)|: the size of the terms whose
        cancellation bounds any f32 evaluation of D_f near the query."""
        return np.asarray(_term_scale(jnp.asarray(ys, jnp.float32),
                                      family=self.family))
