"""Cauchy upper/lower bounds and search-bound determination (Theorems 1-3, Alg. 1 & 4).

Per subspace ``i``:

  UB_i(x, y) = alpha_x + alpha_y + beta_yy + sqrt(gamma_x * delta_y)
             >= D_f(x_i., y_i.)                                    (Theorem 1)
  LB_i(x, y) = alpha_x + alpha_y + beta_yy - sqrt(gamma_x * delta_y)
             <= D_f(x_i., y_i.)

(the LB uses the other side of Cauchy-Schwarz on the cross term
``beta_xy = -sum_j x_j f'(y)_j``, i.e. ``|beta_xy| <= sqrt(gamma_x delta_y)``;
the paper only needs the UB, the LB powers our branch-free ball pruning —
DESIGN.md §3.3).

Summing over subspaces bounds the full distance (Theorem 2).  The k-th
smallest total UB yields per-subspace searching bounds ``qb`` (Alg. 4); the
union of subspace range queries with those bounds provably contains the true
kNN (Theorem 3).

MXU form: because ``sqrt(gamma_x*delta_y) = sqrt(gamma_x)*sqrt(delta_y)``
elementwise over subspaces, the (n x q) total-UB matrix is

    UB_total = rowsum(alpha_x)[:, None] + rowsum(qconst)[None, :]
             + sqrt_gamma @ sqrt_delta^T

one (n, M) x (M, q) matmul plus rank-1 bias — see kernels/bregman_ub.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# Precision of the f32 contractions exact search rests on: the Cauchy upper
# bounds (filter and Alg.-4 bounds) and the refine cross term.  A TPU's
# default f32 dot takes bf16 passes, which can pull an "upper" bound below
# the true distance and cost the refine's split form its digits.
F32_PRECISION = jax.lax.Precision.HIGHEST


def ub_components(p: dict, q: dict) -> Array:
    """Per-subspace upper bounds UB_i. Shapes broadcast: p (..., M), q (..., M)."""
    return p["alpha"] + q["qconst"] + p["sqrt_gamma"] * q["sqrt_delta"]


def lb_components(p: dict, q: dict) -> Array:
    """Per-subspace lower bounds LB_i (other Cauchy side)."""
    return p["alpha"] + q["qconst"] - p["sqrt_gamma"] * q["sqrt_delta"]


def ub_total(p: dict, q: dict) -> Array:
    return jnp.sum(ub_components(p, q), axis=-1)


def ub_matrix(p: dict, q: dict) -> Array:
    """Total upper bounds for all (point, query) pairs in MXU matmul form.

    p fields: (n, M); q fields: (qn, M).  Returns (n, qn).
    """
    bias_p = jnp.sum(p["alpha"], axis=-1)          # (n,)
    bias_q = jnp.sum(q["qconst"], axis=-1)         # (qn,)
    cauchy = jnp.dot(p["sqrt_gamma"], q["sqrt_delta"].T,
                     precision=F32_PRECISION)      # (n, qn) — the MXU matmul
    return bias_p[:, None] + bias_q[None, :] + cauchy


def kth_smallest_ub(p: dict, q: dict, k: int) -> tuple[Array, Array]:
    """Alg. 4 — index and value of the k-th smallest total UB for one query.

    p fields (n, M), q fields (M,).  Returns (kth_index, kth_value).
    """
    totals = ub_total(p, {k_: v[None, :] for k_, v in q.items() if v.ndim == 1})
    neg_vals, idx = jax.lax.top_k(-totals, k)
    return idx[-1], -neg_vals[-1]


def qb_determine(p: dict, q: dict, k: int) -> dict:
    """Alg. 4 — per-subspace searching bounds from the k-th smallest total UB.

    Args:
      p: data tuples with fields of shape (n, M).
      q: one query triple with fields of shape (M,).
    Returns dict with
      qb:  (M,) per-subspace searching bounds (components of the k-th UB)
      tau: () the k-th smallest total UB (global refinement threshold)
      kth: () index of the k-th point.
    """
    q1 = {name: v[None, :] for name, v in q.items() if v.ndim == 1}
    comp = ub_components(p, q1)                     # (n, M)
    totals = jnp.sum(comp, axis=-1)                 # (n,)
    neg_vals, idx = jax.lax.top_k(-totals, k)
    kth = idx[-1]
    qb = comp[kth]                                  # (M,)
    return {"qb": qb, "tau": -neg_vals[-1], "kth": kth}


def refine_distance(x: Array, q: dict, family, y: Array | None = None) -> Array:
    """Exact D_f(x, y) in the fused "rowsum(f) - x . f'(y) + c_y" form.

    ``D_f(x,y) = sum_j f(x_j) - x . grad + c_y`` with
    ``c_y = sum_j (y_j grad_j - f(y_j))``.  The matmul-friendly split lets the
    refinement kernel run the gradient inner product on the MXU
    (kernels/bregman_dist.py).  ``q`` must carry 'grad' (d,) and 'f_y' ().
    ``y`` is unused (kept for signature parity with the oracle).
    """
    grad = q["grad"]
    c_y = jnp.sum(q["_y_grad"], axis=-1) if "_y_grad" in q else q["c_y"]
    fx = jnp.sum(family.phi(x), axis=-1)
    return fx - jnp.dot(x, grad, precision=F32_PRECISION) + c_y


def query_refine_constants(y: Array, family) -> dict:
    """Precompute grad/f'(y) and the additive constant for refine_distance."""
    grad = family.phi_prime(y)
    c_y = jnp.sum(y * grad, axis=-1) - family.f(y)
    return {"grad": grad, "c_y": c_y}
