"""Deterministic synthetic data: LM token batches + the paper's vector sets.

Determinism contract (what makes checkpoint-restart bit-exact):

* every batch is a pure function of ``(seed, step)`` — nothing is consumed
  from a stateful iterator, so skipping to step k after a restore replays
  the identical stream (tested in tests/test_checkpoint.py);
* sharding: the batch is built shard-by-shard with
  ``jax.make_array_from_callback``; each data shard derives its slice from
  global indices, so the same (seed, step) produces the same GLOBAL batch
  on any mesh shape — elastic restarts keep the stream stable.

Vector datasets reproduce the *statistical shape* of the paper's six
benchmarks (Table 4) — correlated Gaussian mixtures so PCCP has structure
to find; real downloads are unavailable offline (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bregman import get_family


# ---------------------------------------------------------------------------
# LM token stream
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TokenStreamConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    # markov-ish structure so the LM has something to learn
    num_patterns: int = 512
    pattern_len: int = 16


def _batch_np(cfg: TokenStreamConfig, step: int, rows: np.ndarray):
    """Generate the given global row indices of batch ``step`` (pure)."""
    out_tok = np.empty((len(rows), cfg.seq_len + 1), np.int32)
    pat_rng = np.random.default_rng(cfg.seed ^ 0x5EED)
    patterns = pat_rng.integers(
        0, cfg.vocab_size, (cfg.num_patterns, cfg.pattern_len))
    for i, r in enumerate(rows):
        rng = np.random.default_rng(
            (cfg.seed * 1_000_003 + step) * 131_071 + int(r))
        seq = []
        while len(seq) < cfg.seq_len + 1:
            pid = int(rng.integers(cfg.num_patterns))
            seq.extend(patterns[pid])
            if rng.random() < 0.1:  # noise token breaks pure copying
                seq.append(int(rng.integers(cfg.vocab_size)))
        out_tok[i] = seq[: cfg.seq_len + 1]
    return out_tok


def token_batch(cfg: TokenStreamConfig, step: int, mesh: Mesh | None = None,
                mrope: bool = False) -> dict:
    """Batch dict {tokens, labels, positions} for ``step`` (global arrays).

    With a mesh, arrays are built shard-wise (batch -> pod/data axes).
    """
    b, s = cfg.global_batch, cfg.seq_len

    def make(shape, gen):
        if mesh is None or np.prod(mesh.devices.shape) == 1:
            return jnp.asarray(gen(np.arange(b)))
        pts = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        sh = NamedSharding(mesh, P(pts if len(pts) > 1 else pts[0]
                                   if pts else None))

        def cb(index):
            rows = np.arange(b)[index[0]]
            return gen(rows)

        return jax.make_array_from_callback(shape, sh, cb)

    toks = make((b, s + 1), lambda rows: _batch_np(cfg, step, rows))
    pos = np.arange(s, dtype=np.int32)[None, :].repeat(b, 0)
    if mrope:
        pos = np.repeat(pos[..., None], 3, axis=-1)
    return {
        "tokens": toks[:, :-1],
        "labels": toks[:, 1:],
        "positions": jnp.asarray(pos),
    }


# ---------------------------------------------------------------------------
# Paper vector datasets (Table 4 stand-ins)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VectorDatasetSpec:
    name: str
    n: int
    d: int
    measure: str          # bregman family alias
    paper_m: int          # the paper's reported partition count


PAPER_DATASETS = {
    "audio": VectorDatasetSpec("audio", 54_387, 192, "ed", 28),
    "fonts": VectorDatasetSpec("fonts", 745_000, 400, "isd", 50),
    "deep": VectorDatasetSpec("deep", 1_000_000, 256, "ed", 37),
    "sift": VectorDatasetSpec("sift", 11_164_866, 128, "ed", 22),
    "normal": VectorDatasetSpec("normal", 50_000, 200, "ed", 25),
    "uniform": VectorDatasetSpec("uniform", 50_000, 200, "isd", 21),
}


def make_vectors(spec: VectorDatasetSpec, scale: float = 1.0,
                 seed: int = 0) -> np.ndarray:
    """Correlated mixture with the dataset's (n, d) scaled by ``scale``.

    Structure matches the paper's real datasets, not a centered Gaussian:
    SIFT/Audio/Deep/Fonts features are NON-NEGATIVE (histograms / spectral
    energies) with strongly heterogeneous magnitudes across clusters.
    That heterogeneity is what the Cauchy ball bounds discriminate on —
    centered equal-norm blobs are the bound's degenerate worst case (all
    points at the same radius).  k Gaussian blobs with low-rank covariance
    (inter-dim correlations for PCCP), folded positive, with per-cluster
    energy scales spanning ~6x.
    """
    n = max(int(spec.n * scale), 64)
    d = spec.d
    # A stable digest of the name: str hash() is salted per process, which
    # made the "same" seeded dataset differ between processes.
    name_seed = int.from_bytes(
        hashlib.blake2b(spec.name.encode(), digest_size=4).digest(), "little")
    rng = np.random.default_rng(seed + name_seed % (1 << 30))
    if spec.name == "uniform":
        data = rng.uniform(0.0, 100.0, (n, d))
    elif spec.name == "normal":
        data = rng.normal(size=(n, d))
    else:
        k = 16
        rank = max(d // 8, 4)
        centers = np.abs(rng.normal(size=(k, d))) * 2.0
        # per-cluster x per-dim energy pattern: heterogeneity must show up
        # INSIDE every subspace for the per-subspace bounds to discriminate
        scales = (rng.uniform(0.5, 3.0, size=(k, 1))
                  * np.exp(0.5 * rng.normal(size=(k, d))))
        mix = rng.integers(0, k, n)
        factors = rng.normal(size=(k, d, rank)) / np.sqrt(rank)
        z = rng.normal(size=(n, rank))
        # Low-rank term per mixture component: one (rows, rank) x (rank, d)
        # product each, never an (n, d, rank) gather (65 GB at Deep's n).
        data = 0.1 * rng.normal(size=(n, d))
        for c in range(k):
            rows = mix == c
            data[rows] += centers[c] + z[rows] @ factors[c].T
        data = np.abs(data) * scales[mix]
    fam = get_family(spec.measure)
    if fam.name in ("itakura_saito", "burg", "shannon"):
        data = np.abs(data) + 0.1
    if fam.name == "exponential":
        # keep e^x terms in a numerically sane band: the tuple-split form
        # fx - x.grad + c_y cancels catastrophically in f32 beyond |x|~6
        data = 5.0 * data / max(np.percentile(data, 99.5), 1e-9)
    return data.astype(np.float32)


def make_queries(spec: VectorDatasetSpec, num: int = 50, scale: float = 1.0,
                 data_seed: int = 0, seed: int = 1) -> np.ndarray:
    """The paper's protocol: 50 points randomly drawn from the dataset."""
    data = make_vectors(spec, scale=scale, seed=data_seed)
    rng = np.random.default_rng(seed)
    idx = rng.choice(data.shape[0], size=min(num, data.shape[0]),
                     replace=False)
    return data[idx]
