"""The retrieval kernels compile for a TPU v5e chip that is described, not
attached.

The Pallas interpreter accepts shapes and VMEM footprints that the chip's
compiler refuses (unaligned blocks, too much fast memory), so every kernel
of the search path is compiled here at the paper datasets' widths and
partition counts.  Nothing runs: a passing compile says nothing about
results or times.  The topology is described inside a fixture, never at
import, because only one process may hold the TPU library at a time.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.search import resolve_block_rows
from repro.kernels import bregman_dist, bregman_fused, bregman_prune, bregman_ub

Q = 64
# (n, d, M): Audio, Deep and Fonts from the paper (BrePartition §7), and
# GIST's 1M x 960 with M at the 64 the prune loop must reach.
SHAPES = [(54_387, 192, 28), (1_000_000, 256, 37), (745_000, 400, 50),
          (1_000_000, 960, 64)]


KERNELS = ["ub", "ub_quant", "prune", "prune_quant", "fused", "fused_quant",
           "refine", "refine_quant"]
# The refine is the one kernel whose body depends on the Bregman family
# (its generator phi and pad value), so it is compiled for each family a
# benchmark deployment runs: Deep's exponential and Fonts' Itakura-Saito.
CASES = ([(kernel, "exponential") for kernel in KERNELS]
         + [(kernel, "itakura_saito") for kernel in ("refine", "refine_quant")])


def _operands(kernel, family, bn, d, m):
    """(function, [(shape, dtype)]) of one kernel launch on a row block."""
    f32, i8 = jnp.float32, jnp.int8
    rows, row, qm = (bn, m), (bn,), (Q, m)
    quant_rows = [(rows, i8), (row, f32), (row, f32)]
    return {
        "ub": (bregman_ub.bregman_ub_matrix,
               [(rows, f32), (rows, f32), ((Q,), f32), (qm, f32)]),
        "ub_quant": (bregman_ub.bregman_ub_matrix_quant,
                     quant_rows * 2 + [((Q,), f32), (qm, f32)]),
        "prune": (bregman_prune.bregman_prune_mask,
                  [(rows, f32)] * 2 + [(qm, f32)] * 3),
        "prune_quant": (bregman_prune.bregman_prune_mask_quant,
                        quant_rows * 2 + [(qm, f32)] * 3),
        "fused": (bregman_fused.bregman_filter_prune,
                  [(rows, f32)] * 4 + [((Q,), f32)] + [(qm, f32)] * 3),
        "fused_quant": (bregman_fused.bregman_filter_prune_quant,
                        quant_rows * 4 + [((Q,), f32)] + [(qm, f32)] * 3),
        "refine": (functools.partial(bregman_dist.bregman_refine_batch,
                                     family=family),
                   [((Q, bn, d), f32), ((Q, d), f32), ((Q,), f32)]),
        "refine_quant": (functools.partial(
                             bregman_dist.bregman_refine_batch_quant,
                             family=family),
                         [((Q, bn, d), i8), ((Q, bn), f32), ((Q, bn), f32),
                          ((Q, d), f32), ((Q,), f32)]),
    }[kernel]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without the chip, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n,d,m", SHAPES, ids=[f"d{d}-M{m}"
                                                for _, d, m in SHAPES])
@pytest.mark.parametrize("kernel,family", CASES, ids=[
    k if f == "exponential" else f"{k}-{f}" for k, f in CASES])
def test_kernel_compiles_for_v5e(kernel, family, n, d, m, one_chip,
                                 no_persistent_cache):
    storage = "int8" if kernel.endswith("quant") else "f32"
    bn = resolve_block_rows(None, n, q=Q, storage=storage)
    fn, specs = _operands(kernel, family, bn, d, m)
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
