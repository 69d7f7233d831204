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
import re

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


# The refine's shared-rows form at the (n, d) of Deep and Fonts, where it
# reads the whole table once for a 32- or 64-query launch.
SHARED = [(1_000_000, 256, "exponential"), (745_000, 400, "itakura_saito")]


@pytest.mark.parametrize("q", [32, 64])
@pytest.mark.parametrize("storage", ["f32", "int8"])
@pytest.mark.parametrize("n,d,family", SHARED,
                         ids=[f"d{d}-{f}" for _, d, f in SHARED])
def test_shared_refine_compiles_for_v5e(n, d, family, storage, q, one_chip,
                                        no_persistent_cache):
    """The shared form compiles under its kernel's own name and reads the
    table in its stored layout: no copy, transpose or pad of the table."""
    f32, i8 = jnp.float32, jnp.int8
    if storage == "int8":
        fn = functools.partial(bregman_dist.bregman_refine_batch_quant,
                               family=family)
        specs = [((n, d), i8), ((n,), f32), ((n,), f32), ((q, d), f32),
                 ((q,), f32)]
    else:
        fn = functools.partial(bregman_dist.bregman_refine_batch,
                               family=family)
        specs = [((n, d), f32), ((q, d), f32), ((q,), f32)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    name = fn.func.__name__
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and f"%{name}." in calls[0]
    assert f"[{q},{n}]" in calls[0].split("=")[1]
    table = f"[{n},{d}]"
    moved = [ln for ln in text.splitlines() if "=" in ln
             and table in ln.split("=")[1].split("(")[0]
             and re.search(r"%(copy|transpose|pad|fusion)", ln.split("=")[0])]
    assert not moved, moved


@pytest.mark.parametrize("n,d", [(1_000_000, 256), (745_000, 400),
                                 (54_387, 192), (1_000_000, 960), (300, 400),
                                 (100, 500), (1_000, 1_000)])
def test_stored_by_columns_matches_v5e_layout(n, d, one_chip,
                                              no_persistent_cache):
    """The shared refine's guess of a table's stored order is the order a
    v5e's default layout gives it."""
    arg = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda x: x + x).lower(arg).compile().as_text()
    order = re.search(rf"\[{n},{d}\]\{{(\d),(\d)", text).groups()
    assert bregman_dist.stored_by_columns(n, d) == (order == ("0", "1"))
