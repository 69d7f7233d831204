"""Dimensionality partitioning layout + P/Q transforms (paper Alg. 2 & 3).

A :class:`Partition` is the static description of how the ``d`` original
dimensions are dealt into ``M`` subspaces of width ``w = ceil(d/M)``.
Padded slots (when ``M*w > d``) carry ``mask = 0`` and contribute nothing to
any transform — this keeps the Cauchy bound *tight* instead of the loose
"pad with a neutral element" alternative (DESIGN.md §6).

Transforms (Theorem 1 notation):

* data tuple  ``P(x) = (alpha_x, gamma_x)`` per subspace, where
  ``alpha_x = sum_j f(x_ij)`` and ``gamma_x = sum_j x_ij^2``;
* query triple ``Q(y) = (alpha_y, beta_yy, delta_y)`` per subspace, where
  ``alpha_y = -sum_j f(y_ij)``, ``beta_yy = sum_j y_ij f'(y_ij)`` and
  ``delta_y = sum_j f'(y_ij)^2``.

TPU adaptation: we additionally store ``sqrt(gamma_x)`` so that the filter's
Cauchy term ``sqrt(gamma_x * delta_y) = sqrt(gamma_x) * sqrt(delta_y)``
becomes a plain inner product over subspaces — the whole filter phase is one
(n, M) x (M, q) matmul on the MXU (see kernels/bregman_ub.py).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bregman import BregmanFamily

Array = jax.Array


@dataclasses.dataclass(frozen=True, eq=False)
class Partition:
    """Static partition layout: which original dim sits in which subspace slot.

    Hash/eq are content-based so a Partition can ride in pytree aux data
    (static side of jit caches).
    """

    d: int
    num_subspaces: int                 # M
    width: int                         # w = ceil(d / M)
    idx: np.ndarray                    # (M, w) int32 indices into the original dims
    mask: np.ndarray                   # (M, w) float32, 0 for padded slots

    def __eq__(self, other):
        return (
            isinstance(other, Partition)
            and self.d == other.d
            and self.num_subspaces == other.num_subspaces
            and np.array_equal(self.idx, other.idx)
            and np.array_equal(self.mask, other.mask)
        )

    def __hash__(self):
        return hash((self.d, self.num_subspaces, self.width,
                     self.idx.tobytes(), self.mask.tobytes()))

    @property
    def m(self) -> int:
        return self.num_subspaces

    def gather(self, x: Array) -> Array:
        """(…, d) -> (…, M, w) subspace view (padded slots refer to dim 0)."""
        return jnp.take(x, jnp.asarray(self.idx), axis=-1)

    def subspace_mask(self) -> Array:
        return jnp.asarray(self.mask)

    def permutation(self) -> np.ndarray:
        """Flat order of the real dims, subspace-major (for layout decisions)."""
        flat_idx = self.idx.reshape(-1)
        flat_mask = self.mask.reshape(-1)
        return flat_idx[flat_mask > 0]


def make_partition(d: int, m: int, order: np.ndarray | None = None) -> Partition:
    """Build a partition of ``d`` dims into ``m`` subspaces.

    ``order`` is the dim order to deal from (contiguous baseline when None;
    the PCCP order from core/partition.py otherwise).  Dims are dealt
    contiguously in ``order``: subspace ``i`` takes ``order[i*w:(i+1)*w]``.
    """
    if m < 1 or m > d:
        raise ValueError(f"need 1 <= M <= d, got M={m}, d={d}")
    if order is None:
        order = np.arange(d)
    order = np.asarray(order, dtype=np.int32)
    if order.shape != (d,) or len(np.unique(order)) != d:
        raise ValueError("order must be a permutation of range(d)")
    w = -(-d // m)  # ceil
    idx = np.zeros((m, w), dtype=np.int32)
    mask = np.zeros((m, w), dtype=np.float32)
    for i in range(m):
        chunk = order[i * w:(i + 1) * w]
        idx[i, : len(chunk)] = chunk
        mask[i, : len(chunk)] = 1.0
    return Partition(d=d, num_subspaces=m, width=w, idx=idx, mask=mask)


# ---------------------------------------------------------------------------
# Transforms
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("family",))
def p_transform_views(xs: Array, mask: Array, family: BregmanFamily) -> dict:
    """Alg. 2 on a PRE-GATHERED (..., M, w) subspace view.

    The P-tuple depends on a point only through its subspace view, so
    callers that already hold the view — the streaming-insert path
    (core/segments.py) transforms new points with the SEALED partition's
    gathered view — share this math with :func:`p_transform`, mirroring
    the :func:`q_transform_views` split on the query side.

    One jitted program, so the elementwise terms fuse into the reductions.
    Run op by op at build size, each term was a table-sized temporary, and
    how many of them were still held when the host had dispatched ahead
    varied from run to run, and with it the build's memory peak.
    """
    alpha = jnp.sum(family.phi(xs) * mask, axis=-1)
    gamma = jnp.sum(xs * xs * mask, axis=-1)
    return {"alpha": alpha, "gamma": gamma, "sqrt_gamma": jnp.sqrt(gamma)}


def p_transform(x: Array, part: Partition, family: BregmanFamily) -> dict:
    """Alg. 2 — transform data points into per-subspace tuples.

    Args:
      x: (..., d) data points.
    Returns dict with
      alpha: (..., M)   sum of f over the subspace dims
      gamma: (..., M)   sum of squares over the subspace dims
      sqrt_gamma: (..., M)  precomputed sqrt for the MXU filter form
    """
    return p_transform_views(part.gather(x), part.subspace_mask(), family)


def q_transform_views(ys: Array, mask: Array, family: BregmanFamily) -> dict:
    """Alg. 3 on a PRE-GATHERED (..., M, w) subspace view.

    The per-subspace triples depend on the query only through its subspace
    view, so distributed callers (dist/knn.py) gather once on the host and
    ship the view to every shard; this is the shared math.  Returns the
    per-subspace fields of :func:`q_transform` (everything except the
    original-order refinement constants).
    """
    g = family.phi_prime(ys)
    alpha = -jnp.sum(family.phi(ys) * mask, axis=-1)
    beta_yy = jnp.sum(ys * g * mask, axis=-1)
    delta = jnp.sum(g * g * mask, axis=-1)
    return {
        "alpha": alpha,
        "beta_yy": beta_yy,
        "delta": delta,
        "qconst": alpha + beta_yy,
        "sqrt_delta": jnp.sqrt(delta),
    }


def q_transform(y: Array, part: Partition, family: BregmanFamily) -> dict:
    """Alg. 3 — transform query points into per-subspace triples.

    Returns dict with
      alpha: (..., M)      -sum f(y)
      beta_yy: (..., M)    sum y * f'(y)
      delta: (..., M)      sum f'(y)^2
      qconst: (..., M)     alpha + beta_yy (the per-subspace additive constant)
      sqrt_delta: (..., M) sqrt for the MXU filter form
      grad: (..., d)       f'(y) in ORIGINAL dim order (for refinement)
      f_y: (...)           f(y) over all dims (for refinement constant)
    """
    q = q_transform_views(part.gather(y), part.subspace_mask(), family)
    q["grad"] = family.phi_prime(y)
    q["f_y"] = family.f(y)
    return q
