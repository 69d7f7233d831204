"""kNN-LM over a Bregman datastore — the paper's technique as a first-class
serving feature.

A datastore maps LM hidden states h_t to the token that FOLLOWED them in a
reference corpus (Khandelwal et al. 2020).  At decode time the current
hidden state queries the store's k nearest neighbors and the LM distribution
is interpolated with the kNN distribution:

    p(y) = (1 - lam) * p_LM(y) + lam * softmax_over_knn(-D(h, h_i) / T)

Euclidean kNN is standard; exp-family embeddings motivate Bregman
divergences, and this is precisely the paper's workload: hundreds of
dimensions (d_model), millions of keys, exact-or-guaranteed retrieval.
BrePartition's partition-filter-refine pipeline (core/search.py) serves the
queries; the distributed path (dist/knn.py) shards the datastore over
(pod, data) with subspaces on the model axis.

``build_datastore`` runs teacher-forced prefills over a corpus and records
(hidden, next_token) pairs; ``KNNLMHook`` plugs into serve/engine.py's
``logits_hook``.  ``Datastore.grow``/``Datastore.evict`` mutate the store
online via the segmented index (core/segments.py) — streaming ingestion
and retirement with no rebuild and no serving pause (see
docs/index_updates.md for the contract).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as bp_search
from repro.core.index import BallForest, build_index
from repro.core.segments import SegmentedForest

Array = jax.Array


@dataclasses.dataclass
class Datastore:
    """kNN-LM key/value store over a BrePartition index.

    ``index`` is a BallForest, or — after the first :meth:`grow`/
    :meth:`evict` — the mutable SegmentedForest.  ``next_tokens`` is
    indexed by ORIGINAL point id; ids are never reused (tombstones keep
    theirs, compaction preserves them), so the table is append-only and
    stays valid across every mutation.
    """

    index: BallForest | SegmentedForest
    next_tokens: np.ndarray     # (next_id,) int32 — token following each key
    hidden_dim: int
    version: int = 0            # bumped on every mutation (cache invalidation)
    # Streaming block size for searches over this store (None = pipeline
    # default).  Deployment-level knob: smaller blocks cap the per-tick
    # peak intermediate bytes (O(block_rows * q)), larger blocks amortize
    # scan overhead — see core.search.resolve_block_rows.  Hooks read it
    # as their default; per-hook overrides win.
    block_rows: int | None = None
    # Threshold-triggered compaction runs a CostModel fit (and possibly a
    # full rebuild) synchronously inside grow()/evict(); serving
    # deployments that cannot absorb that pause on the request path set
    # False and call index.compact() from a maintenance tick instead.
    auto_compact: bool = True
    # Out-of-core residency (core/tiered.py): with a byte budget, lookups
    # run against a TieredPointStore snapshot — cold key blocks in host
    # RAM, fetched on envelope admission — so the value of n is capped by
    # host RAM, not HBM.  None keeps the store fully device-resident.
    resident_bytes: int | None = None
    prefetch_depth: int | None = None
    _tiered: object = dataclasses.field(default=None, init=False,
                                        repr=False)
    _tiered_version: int = dataclasses.field(default=-1, init=False,
                                             repr=False)

    @property
    def storage(self) -> str:
        """Key-table storage tier ("f32" | "int8" — see build_datastore)."""
        return self.index.storage

    def search_index(self):
        """The object lookups should search: the index itself, or — with
        a ``resident_bytes`` budget — a TieredPointStore snapshot of it,
        rebuilt lazily whenever :attr:`version` moves (the store freezes
        its snapshot at construction, so a grow/evict invalidates it the
        same way it invalidates the device value table)."""
        if self.resident_bytes is None:
            return self.index
        if self._tiered is None or self._tiered_version != self.version:
            from repro.core.tiered import TieredPointStore
            old, self._tiered = self._tiered, None
            if old is not None:
                old.close()
            self._tiered = TieredPointStore.from_index(
                self.index, resident_bytes=self.resident_bytes,
                prefetch_depth=self.prefetch_depth,
                block_rows=self.block_rows)
            self._tiered_version = self.version
        return self._tiered

    def _mutable(self) -> SegmentedForest:
        if not isinstance(self.index, SegmentedForest):
            self.index = SegmentedForest.from_forest(self.index)
        return self.index

    def grow(self, keys: np.ndarray, next_tokens: np.ndarray) -> np.ndarray:
        """Online ingestion: append (hidden, next-token) pairs; returns ids.

        One nearest-centroid pass against the sealed index — no rebuild on
        the insert itself.  The new keys are retrievable by the very next
        hook call (the snapshot row count changes, so that call compiles a
        fresh program; batch your grows).  With :attr:`auto_compact` the
        call that crosses the stale-fraction threshold additionally pays
        for the compaction inline.
        """
        keys = np.asarray(keys, np.float32)
        toks = np.asarray(next_tokens, np.int32)
        if keys.ndim != 2 or keys.shape[1] != self.hidden_dim:
            raise ValueError(
                f"expected (a, {self.hidden_dim}) keys, got {keys.shape}")
        if toks.shape != (keys.shape[0],):
            raise ValueError("one next-token per key required")
        store = self._mutable()
        if store.next_id != self.next_tokens.shape[0]:
            raise ValueError("datastore ids out of sync with value table")
        ids = store.insert(keys, auto_compact=self.auto_compact)
        self.next_tokens = np.concatenate([self.next_tokens, toks])
        self.version += 1
        return ids

    def evict(self, ids) -> int:
        """Retire keys (stale users, rolled-over corpora) by tombstone."""
        removed = self._mutable().delete(ids,
                                         auto_compact=self.auto_compact)
        if removed:
            self.version += 1
        return removed


def build_datastore(bundle, params, corpus_tokens: np.ndarray, *,
                    family: str = "squared_euclidean",
                    m: int | None = None, quantize: bool = False,
                    block_rows: int | None = None,
                    calibrate: bool = False, calibrate_k: int = 8,
                    resident_bytes: int | None = None,
                    prefetch_depth: int | None = None,
                    seed: int = 0) -> Datastore:
    """Teacher-forced pass over (num_seqs, seq_len) tokens -> datastore.

    Keys: hidden state at position t; values: token at t+1.

    ``quantize=True`` stores the keys in the int8 BallForest tier —
    ~4x smaller key table for large value stores, with retrieval still
    exact over the stored (decoded) keys; grows quantize their keys the
    same way (docs/quantization.md).  d_model-sized hidden states are
    exactly the "hundreds of dimensions, millions of keys" regime the
    memory win targets.

    ``calibrate=True`` fits the recall-calibration curve over held-out
    jittered keys at build time (core/calibrate.py), enabling
    ``KNNLMHook(target_recall=...)`` — approximate decode-time retrieval
    at a MEASURED recall level; ``calibrate_k`` should match the hook's
    ``k`` (default 8 matches the hook default).

    ``resident_bytes`` tiers the key table out-of-core (core/tiered.py):
    cold key blocks live in host RAM under that device-cache budget, so
    datastore capacity is bounded by host RAM instead of HBM;
    ``prefetch_depth`` sets the fetch double-buffer depth
    (docs/tiered_storage.md).
    """
    from repro.core.tiered import resolve_prefetch_depth, resolve_resident_bytes
    resident_bytes = resolve_resident_bytes(resident_bytes)
    prefetch_depth = resolve_prefetch_depth(prefetch_depth)
    num, s = corpus_tokens.shape
    pos = np.arange(s, dtype=np.int32)[None, :].repeat(num, 0)
    if getattr(bundle.cfg, "mrope_section", None):
        pos = np.repeat(pos[..., None], 3, -1)
    batch = {"tokens": jnp.asarray(corpus_tokens, jnp.int32),
             "positions": jnp.asarray(pos)}
    for name, (shape_fn, dtype, _ax) in bundle.extra_inputs.items():
        batch[name] = jnp.zeros(shape_fn(num, s), dtype)
    hidden, _ = jax.jit(bundle.forward_train)(params, batch)
    keys = np.asarray(hidden[:, :-1].reshape(-1, hidden.shape[-1]),
                      np.float32)
    vals = np.asarray(corpus_tokens[:, 1:].reshape(-1), np.int32)
    index = build_index(keys, family, m=m, quantize=quantize,
                        calibrate=calibrate, calibrate_k=calibrate_k,
                        seed=seed)
    if block_rows is None:
        # Pin the autotuned streaming block size once at build time (same
        # policy as serve.retrieval.register_tenant): hook batches are
        # small, so key the lookup on a typical decode-tick row count.
        from repro.launch import autotune
        block_rows = autotune.lookup_block_rows(
            max(index.n, 1), 8, storage=index.storage)
    return Datastore(index=index, next_tokens=vals,
                     hidden_dim=keys.shape[-1], block_rows=block_rows,
                     resident_bytes=resident_bytes,
                     prefetch_depth=prefetch_depth)


@dataclasses.dataclass
class KNNLMHook:
    """``logits_hook`` for serve.engine.Engine: Bregman-kNN interpolation.

    The engine passes the sampled slots' rows (logits (A, V), hidden
    (A, D) — active slots on decode ticks, admitted slots on the prefill
    path, never a dead slot's garbage row); the hook retrieves
    each row's k nearest datastore keys with BrePartition and mixes the
    neighbor next-token distribution into the LM distribution.
    """

    store: Datastore
    k: int = 8
    lam: float = 0.25
    temperature: float = 1.0
    approx_p: float | None = None   # paper §8 approximate mode (raw knob)
    # Calibrated alternative to approx_p: retrieve at a MEASURED recall
    # level by inverting the datastore's calibration curve (fit it with
    # build_datastore(calibrate=True)).  Mutually exclusive with approx_p;
    # uncalibrated stores fall back to p = target_recall with a one-time
    # warning (core/calibrate.py).
    target_recall: float | None = None
    budget: int | None = None       # pinned refine budget (stable jit cache)
    block_rows: int | None = None   # streaming block size (None -> store's)
    # Optional robustness front end (serve/retrieval.py).  When set, every
    # lookup routes through the service's admission gate + degradation
    # ladder under ``deadline_s``: the store is (re-)registered as tenant
    # ``service_tenant`` whenever ``store.version`` moves, and rows the
    # service degraded past approx (partial/shed) fall back to the pure LM
    # distribution — a slow or faulty datastore costs retrieval quality,
    # never decode liveness.  Unset, lookups call knn_batch directly (the
    # bare-metal path: no deadlines, but also no service in the loop).
    service: object = None          # RetrievalService | None
    service_tenant: str = "knnlm"
    deadline_s: float | None = None
    queries_served: int = 0
    # Structured budget-retry telemetry: total budget escalations taken
    # and the budget the most recent launch actually ran with.
    escalations: int = 0
    budget_final: int = 0
    # next_tokens cached on device (lazy, refreshed when the store mutates)
    _next_dev: Array | None = dataclasses.field(
        default=None, init=False, repr=False)
    _next_version: int = dataclasses.field(
        default=-1, init=False, repr=False)
    _svc_version: int = dataclasses.field(
        default=-1, init=False, repr=False)

    def _service_lookup(self, h: np.ndarray):
        """Route one lookup through the retrieval service.

        Returns ``(ids, dists, use_rows)`` or None for "serve pure LM".
        ``use_rows`` keeps exact/approx rows; partial and shed rows fall
        back to the LM distribution (a truncated neighbor set would bias
        the mixture — the same policy as the inexact-row gate below).
        """
        svc = self.service
        name = self.service_tenant
        if name not in svc.tenants or self._svc_version != self.store.version:
            # (Re-)register on every store mutation: the service revalidates
            # the live rows and refreshes its tenant record.  approx_p is
            # the tenant's raw §8 knob; target_recall rides each request and
            # inverts the store's calibration curve service-side — the two
            # are different quantities and must not be conflated.
            svc.register_tenant(name, self.store.index,
                                p_guarantee=self.approx_p,
                                resident_bytes=self.store.resident_bytes,
                                prefetch_depth=self.store.prefetch_depth)
            self._svc_version = self.store.version
        resp = svc.search_sync(name, h, self.k, deadline_s=self.deadline_s,
                               target_recall=self.target_recall)
        use = np.array([q in ("exact", "approx") for q in resp.row_quality])
        if not use.any():
            return None
        return resp.ids, resp.dists, use

    def __call__(self, logits: Array, hidden: Array | None) -> Array:
        if hidden is None:
            return logits
        # Eviction can shrink the store below k mid-serving; retrieval is
        # then impossible, so degrade to the pure LM distribution (the same
        # fallback the inexact-row gate uses) instead of raising.
        live = getattr(self.store.index, "live_n", self.store.index.n)
        if live < self.k:
            return logits
        h = jnp.asarray(hidden, jnp.float32)
        if self.service is not None:
            out = self._service_lookup(np.asarray(h))
            self.queries_served += int(h.shape[0])
            if out is None:
                return logits
            ids, dists, use = out
            ids = jnp.asarray(np.maximum(ids, 0))      # shed rows hold -1
            dists = jnp.asarray(np.where(use[:, None], dists, 0.0))
            use = jnp.asarray(use)
        else:
            # The engine hands the LIVE rows (A, D) at every sampling step —
            # active slots on decode ticks, admitted slots on the prefill
            # path; dead slots' garbage rows never reach retrieval — so each
            # step is ONE fused knn_search_batch program: one filter matmul,
            # one prune, one refine for all sampled slots.  Pinning the
            # budget keeps the refine shape stable; the batch axis still
            # varies with the live-slot count (bounded by the engine's slot
            # pool, so the jit cache holds at most `slots` programs per k).
            # Rare union overflows take the ladder's sized retry.
            res, stats = bp_search.knn_batch(
                self.store.search_index(), h, self.k, budget=self.budget,
                approx_p=self.approx_p, target_recall=self.target_recall,
                block_rows=(self.block_rows or self.store.block_rows),
                return_stats=True)
            self.queries_served += int(h.shape[0])
            self.escalations += stats.escalations
            self.budget_final = stats.budget_final
            # Grow-only budget adaptation: only when this step's unions
            # outgrew the effective budget (no pin is installed while the
            # default suffices — one program, no mid-serving recompile).  On
            # overflow the pin uses the shared fitted_budget sizing so it
            # lands on the same static shapes knn_batch's retries compile.
            # The pin is bounded: one pathological row (a stale slot's
            # hidden state, a degenerate union ~ n) must not permanently
            # inflate every future step's refine gather to (B, n, d) —
            # beyond the (power-of-two aligned) cap we accept the
            # occasional retry instead.
            default = bp_search.default_budget(self.store.index, self.k)
            needed = int(jnp.max(res.num_candidates))
            current = self.budget or default
            if needed > current:
                cap = bp_search.fitted_budget(self.store.index, self.k,
                                              8 * default)
                fitted = bp_search.fitted_budget(self.store.index, self.k,
                                                 needed)
                self.budget = max(current, min(fitted, cap))  # never shrink
            # Defense in depth: knn_batch's ladder ends exact, so inexact
            # rows shouldn't occur, but if one ever does its neighbors are
            # an arbitrary union prefix — serve the pure LM distribution
            # for it instead of a biased mixture.
            ids, dists, use = res.ids, res.dists, res.exact
        # Upload the value table once per store version, not per tick; a
        # grow/evict bumps store.version and forces a re-upload so appended
        # ids resolve and evicted ids (which never surface) age out.
        if self._next_dev is None or self._next_version != self.store.version:
            self._next_dev = jnp.asarray(self.store.next_tokens)
            self._next_version = self.store.version
        knn_tokens = self._next_dev[ids]                        # (B, k)
        w = jax.nn.softmax(-dists / self.temperature, axis=-1)  # (B, k)
        vocab = logits.shape[-1]
        p_knn = jax.vmap(
            lambda t, ww: jnp.zeros((vocab,), jnp.float32).at[t].add(ww)
        )(knn_tokens, w)
        p_lm = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        mix = (1.0 - self.lam) * p_lm + self.lam * p_knn
        mix = jnp.where(use[:, None], mix, p_lm)
        return jnp.log(jnp.maximum(mix, 1e-30))
