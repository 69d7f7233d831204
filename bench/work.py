"""The least work each search phase must do for one launch.

These count what the phase has to do for the launch's queries and
candidates, not what an implementation moves, so that a kernel's share of
its roofline can never pass 100% for any implementation of the phase.

* Filter + prune.  The filter ranks every row by its Alg.-4 upper bound,
  so it reads each row's P-tuple (alpha, sqrt_gamma) over the M subspaces
  once.  The Theorem-3 prune must read the corner pair (alpha_min,
  sqrt_gamma_max) of every row it admits: at least the rows of the
  largest query's union.  Each (row, query, subspace) term costs at least
  one multiply-add in each phase.
* Refine.  Each distinct candidate row is read once (a row shared by all
  queries is still read once), and each (query, candidate) pair costs at
  least one multiply-add per coordinate (the x . f'(y) term).

Stored int8 tables also carry a float32 scale and zero point per row and
table.  Operations are held to the chip's highest peak for the storage
(bf16 FLOP/s for float32, int8 OP/s for int8), bytes to the HBM bandwidth;
the least time is the larger of the two.
"""

from __future__ import annotations

ELEM_BYTES = {"f32": 4, "int8": 1}
# Per-row decode bytes of one int8 table: a float32 scale and zero point.
DECODE_BYTES = {"f32": 0, "int8": 8}


def filter_prune(n: int, q: int, m: int, storage: str, union: int):
    """(ops, bytes) of the filter over all n rows and the prune over the
    ``union`` rows that some query admits."""
    per_row = 2 * (m * ELEM_BYTES[storage] + DECODE_BYTES[storage])
    ops = 2 * m * q * (n + union)
    return ops, per_row * (n + union)


def refine(d: int, storage: str, distinct_rows: int, pairs: int):
    """(ops, bytes) of refining ``pairs`` (query, candidate) pairs over
    ``distinct_rows`` rows."""
    per_row = d * ELEM_BYTES[storage] + DECODE_BYTES[storage]
    return 2 * d * pairs, per_row * distinct_rows


def least_seconds(ops: float, nbytes: float, peaks: dict,
                  storage: str) -> float:
    peak_ops = (peaks["int8_ops_per_s"] if storage == "int8"
                else peaks["bf16_flops_per_s"])
    return max(ops / peak_ops, nbytes / peaks["hbm_bytes_per_s"])


def launch_seconds(launch: dict, shape: dict, peaks: dict) -> dict:
    """Least seconds of each phase of one launch.

    ``launch``: ``q`` query rows answered, ``budget`` and the per-query
    Theorem-3 union sizes ``num_candidates``.  ``shape``: ``n``, ``d``,
    ``m``, ``storage``.
    """
    n, storage = shape["n"], shape["storage"]
    cands = [min(int(c), n) for c in launch["num_candidates"]]
    union = max(cands)                      # the union is at least this
    refined = [min(c, launch["budget"]) for c in cands]
    fp = filter_prune(n, launch["q"], shape["m"], storage, union)
    rf = refine(shape["d"], storage, max(refined), sum(refined))
    return {"filter_prune": least_seconds(*fp, peaks, storage),
            "refine": least_seconds(*rf, peaks, storage)}
