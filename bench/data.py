"""The benchmark's seeded vectors, made on the device in one jitted call.

The structure follows the repository's Table-4 stand-ins
(``repro.data.pipeline.make_vectors``): a 16-component mixture whose
components each have a low-rank covariance (rank d/8), folded
non-negative, with per-component energy scales (a component factor in
[0.5, 3) times a per-coordinate log-normal), and for the exponential
family a rescale to 5 / p99.5 so that e^x stays in a sane band.  The
queries are further draws from the same mixture, held out of the index.

The stored rows are one fixed draw (``MIXTURE_SEED``), like a published
dataset; ``--seed`` shuffles their order and draws the queries, all
through ``jax.random``.  So every seed stores the same set of rows in
another order: the same correlations, hence the same PCCP partition, the
same compiled programs and the same memory, with other row ids, other
k-means clusters and other queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

COMPONENTS = 16
MIXTURE_SEED = 0
# Entries the p99.5 of the exponential rescale is taken over; below this
# many entries it is taken over all of them.
QUANTILE_SAMPLE = 1 << 22
POSITIVE_FAMILIES = ("itakura_saito", "burg", "shannon")
ALIASES = {"ed": "exponential", "isd": "itakura_saito",
           "se": "squared_euclidean"}


def family_name(name: str) -> str:
    return ALIASES.get(name.lower(), name.lower())


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size (threefry key data)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _draw(key, mixture, count: int, d: int):
    """``count`` rows of the mixture and the component of each."""
    rank = max(d // 8, 4)
    centers, scales, factors = mixture
    k_mix, k_z, k_noise = jax.random.split(key, 3)
    mix = jax.random.randint(k_mix, (count,), 0, COMPONENTS)
    z = jax.random.normal(k_z, (count, rank))
    base = 0.1 * jax.random.normal(k_noise, (count, d)) + centers[mix]

    def add_component(c, acc):
        low = z @ factors[c].T                          # (count, d)
        return acc + jnp.where((mix == c)[:, None], low, 0.0)

    x = jax.lax.fori_loop(0, COMPONENTS, add_component, base)
    return jnp.abs(x) * scales[mix], mix


@functools.partial(jax.jit, static_argnames=("n", "d", "num_queries",
                                             "family"))
def _generate(kd, n: int, d: int, num_queries: int, family: str):
    seed_key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    k_rows, k_quantile, k_scales, k_centers, k_factors = jax.random.split(
        jax.random.key(MIXTURE_SEED), 5)
    rank = max(d // 8, 4)
    mixture = (
        jnp.abs(jax.random.normal(k_centers, (COMPONENTS, d))) * 2.0,
        (jax.random.uniform(k_scales, (COMPONENTS, 1), minval=0.5,
                            maxval=3.0)
         * jnp.exp(0.5 * jax.random.normal(jax.random.fold_in(k_scales, 1),
                                           (COMPONENTS, d)))),
        jax.random.normal(k_factors, (COMPONENTS, d, rank)) / np.sqrt(rank))
    k_order, k_queries = jax.random.split(seed_key)
    rows, mix = _draw(k_rows, mixture, n, d)
    queries, _ = _draw(k_queries, mixture, num_queries, d)
    if family in POSITIVE_FAMILIES:
        rows, queries = jnp.abs(rows) + 0.1, jnp.abs(queries) + 0.1
    if family == "exponential":
        flat = rows.reshape(-1)
        if flat.size > QUANTILE_SAMPLE:
            flat = jnp.take(flat, jax.random.randint(
                k_quantile, (QUANTILE_SAMPLE,), 0, flat.size))
        scale = 5.0 / jnp.maximum(jnp.percentile(flat, 99.5), 1e-9)
        rows, queries = rows * scale, queries * scale
    order = jax.random.permutation(k_order, n)
    return rows[order], queries, mix[order]


def generate(seed: int, n: int, d: int, num_queries: int, family: str,
             with_components: bool = False):
    """(rows (n, d), queries (num_queries, d)) as device float32 arrays."""
    rows, queries, mix = _generate(jnp.asarray(key_data(seed)), n=n, d=d,
                                   num_queries=num_queries,
                                   family=family_name(family))
    if with_components:
        return rows, queries, mix
    return rows, queries
