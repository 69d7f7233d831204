"""Batched-query throughput of the fused knn_search_batch pipeline.

Measures queries/sec for q in {1, 8, 64, 256} on one synthetic dataset so
BENCH json tracks batch throughput over time.

Two streaming-specific columns ride every fused row (and the BENCH
trajectory): ``skip_rate`` — the fraction of (block, query) tiles pruned
by the corner-envelope gate before their per-point admit work — and
``peak_bytes`` — the compiled program's temp-buffer high-water mark
(XLA ``memory_analysis``, -1 where the backend hides it), next to
``mask_bytes``, the ~5 n*q bytes the retired mask/cumsum pipeline held at
the same shape.  A large-n clustered shape exercises exactly the regime
that used to thrash on the (n, q) mask and now skips whole blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bregman import get_family
from repro.core.index import build_index
from repro.core import search

from .common import Row, timeit

BATCH_SIZES = (1, 8, 64, 256)


def _peak_temp_bytes(index, ys, k, budget, block_rows):
    """Temp high-water mark of the compiled fused program (-1 if hidden)."""
    try:
        compiled = search._knn_search_batch_jit.lower(
            index, ys, k, budget,
            search.resolve_block_rows(block_rows, index.n)).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except Exception:  # noqa: BLE001 — backend-dependent introspection
        return -1


def _stream_cols(index, ys, k, budget, block_rows=None):
    """Streaming telemetry columns for one fused shape.

    ``skip_rate``: measured fraction of (block, query) tiles the envelope
    gate rejected (each provably contributes no candidate); a block's
    per-point kernel still runs if any OTHER query admits it, so the
    compute actually avoided is ``block_skip_rate`` (whole blocks every
    query rejected).  ``peak_bytes``: the compiled program's total temp
    high-water mark (includes the refine gather both pipelines share).
    ``pair_bytes`` vs ``mask_bytes``: the per-point-query-pair
    intermediates of the prune+compact phase alone — what streaming
    removed — O(block_rows * q) streamed vs the retired ~5-byte (n, q)
    mask + (q, n) cumsum.
    """
    _, stats = search.knn_search_batch_stats(index, ys, k, budget,
                                             block_rows=block_rows)
    n, q = index.n, ys.shape[0]
    return {
        "skip_rate": round(stats["block_skip_rate"], 3),
        "block_skip_rate": round(stats["whole_block_skip_rate"], 3),
        "peak_bytes": _peak_temp_bytes(index, ys, k, budget, block_rows),
        "pair_bytes": 8 * stats["block_rows"] * q,
        "mask_bytes": 5 * n * q,      # the retired (n,q)+(q,n) intermediates
    }


def run(scale: float = 1.0):
    n = max(512, int(8192 * scale))
    d, m, k = 64, 8, 10
    fam = get_family("squared_euclidean")
    data = np.asarray(fam.sample(jax.random.PRNGKey(0), (n, d), scale=1.0))
    index = build_index(data, "squared_euclidean", m=m, num_clusters=64,
                        seed=0)
    budget = search.default_budget(index, k)

    rows = []
    for q in BATCH_SIZES:
        ys = jnp.asarray(np.asarray(
            fam.sample(jax.random.PRNGKey(1), (q, d), scale=1.0)))
        us_fused = timeit(
            lambda: search.knn_search_batch(index, ys, k, budget), repeats=5)
        rows.append(Row("batch_search", f"fused_q{q}", us_fused,
                        {"n": n, "qps": round(q / (us_fused / 1e6), 1),
                         **_stream_cols(index, ys, k, budget)}))

    # Large-n clustered shape: the regime that used to hold ~5 n*q bytes of
    # mask/cumsum (OOM/thrash territory as n*q grows) and where spatial
    # locality lets the envelope gate skip whole blocks.  The baseline here
    # is the kept mask/cumsum reference pipeline at the same shape, so the
    # json tracks streamed-vs-materialized directly.  Well-separated blobs
    # + blocks of ~1/32 of the table mean most blocks are blob-pure and
    # queries sitting on one blob let the gate drop the rest.
    n_l = max(4096, int(131072 * scale))
    q_l = 64
    rng = np.random.default_rng(2)
    # Blobs shifted on EVERY dim: the paper's P-tuple bound prunes by
    # per-subspace stats, so separation must be visible in each subspace
    # (an all-dims shift survives any partition) for Theorem 3 — and hence
    # the envelope gate — to drop other blobs' blocks wholesale.  128
    # small blobs keep each query's union (~ its own blob) serving-sized,
    # so the refine gather does not drown the prune-phase comparison.
    blob = rng.integers(0, 128, size=n_l)
    data_l = (rng.normal(size=(n_l, d)).astype(np.float32)
              + (6.0 * blob).astype(np.float32)[:, None])
    index_l = build_index(data_l, "squared_euclidean", m=m,
                          num_clusters=min(256, n_l // 16), seed=0)
    ys_l = jnp.asarray(data_l[np.where(blob == 0)[0][:q_l]] + 0.01)
    q_l = int(ys_l.shape[0])     # blob 0 may hold < 64 rows at small scales
    # A union is ~ the query's blob; cover it so both pipelines run exact
    # at identical static shapes, and scan in blob-fraction-sized blocks.
    budget_l = search.fitted_budget(index_l, k, n_l // 64)
    # Blob-fraction-sized blocks at full scale; floored at 2048 because on
    # the CPU ref backend each scan step has a fixed dispatch cost that
    # dwarfs sub-2k blocks (on TPU the floor is the VMEM tile, not this).
    br_l = max(2048, n_l // 32)
    us_ref = timeit(lambda: search.knn_search_batch_reference(
        index_l, ys_l, k, budget_l, block_rows=br_l), repeats=3)
    us_str = timeit(lambda: search.knn_search_batch(
        index_l, ys_l, k, budget_l, block_rows=br_l), repeats=3)
    try:
        ref_peak = int(search._knn_search_batch_ref_jit.lower(
            index_l, ys_l, k, budget_l,
            br_l).compile().memory_analysis().temp_size_in_bytes)
    except Exception:  # noqa: BLE001 — backend-dependent introspection
        ref_peak = -1
    rows.append(Row("batch_search", f"large_n_masked_q{q_l}", us_ref,
                    {"n": n_l, "qps": round(q_l / (us_ref / 1e6), 1),
                     "peak_bytes": ref_peak}))
    rows.append(Row("batch_search", f"large_n_streamed_q{q_l}", us_str,
                    {"n": n_l, "qps": round(q_l / (us_str / 1e6), 1),
                     "speedup": round(us_ref / us_str, 2),
                     **_stream_cols(index_l, ys_l, k, budget_l, br_l)}))

    # Fused vs unfused scan at the same large-n shape: the streamed row
    # above runs the fused filter+prune kernel with the hoisted envelope
    # gate; this A/B pins the old per-step gate + standalone prune kernel
    # so the BENCH trajectory tracks the fusion win in isolation (identical
    # results — tests/test_stream_prune.py asserts bit-parity).
    us_unf = timeit(lambda: search._knn_search_batch_unfused_jit(
        index_l, ys_l, k, budget_l, br_l), repeats=3)
    rows.append(Row("batch_search", f"large_n_unfused_q{q_l}", us_unf,
                    {"n": n_l, "qps": round(q_l / (us_unf / 1e6), 1),
                     "fused_speedup": round(us_unf / us_str, 2)}))

    # Tuned vs default block size: block_rows=None consults the checked-in
    # autotuner table (launch/autotune.py); DEFAULT_BLOCK_ROWS is what a
    # caller got before the table existed.  tuned_speedup > 1 means the
    # sweep's pick beats the hardcoded default at this shape.
    br_tuned = search.resolve_block_rows(None, index_l.n, q=q_l,
                                         storage=index_l.storage)
    us_def = timeit(lambda: search.knn_search_batch(
        index_l, ys_l, k, budget_l,
        block_rows=search.DEFAULT_BLOCK_ROWS), repeats=3)
    us_tuned = timeit(lambda: search.knn_search_batch(
        index_l, ys_l, k, budget_l), repeats=3)
    rows.append(Row("batch_search", f"large_n_tuned_q{q_l}", us_tuned,
                    {"n": n_l, "block_rows": br_tuned,
                     "default_block_rows": search.DEFAULT_BLOCK_ROWS,
                     "qps": round(q_l / (us_tuned / 1e6), 1),
                     "tuned_speedup": round(us_def / us_tuned, 2)}))
    return rows


if __name__ == "__main__":
    for row in run():
        print(row.csv())
