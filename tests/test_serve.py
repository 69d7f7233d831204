"""Serving engine + kNN-LM integration tests (reduced configs, CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.registry import build_model
from repro.serve.engine import Engine, EngineConfig, Request
from repro.serve.knnlm import KNNLMHook, build_datastore


@pytest.fixture(scope="module")
def bundle():
    return build_model(configs.get_reduced("starcoder2-3b"))


@pytest.fixture(scope="module")
def params(bundle):
    return bundle.init(jax.random.PRNGKey(0))


def _req(uid, length, vocab, new=4, seed=0):
    rng = np.random.default_rng(seed + uid)
    return Request(uid=uid, prompt=rng.integers(1, vocab, length),
                   max_new_tokens=new)


def test_engine_serves_batch(bundle, params):
    cfg = EngineConfig(slots=4, max_seq=64, prefill_len=16)
    eng = Engine(bundle, params, cfg)
    for uid in range(6):                      # more requests than slots
        eng.submit(_req(uid, 12, bundle.cfg.vocab_size))
    done = eng.run(max_ticks=100)
    assert len(done) == 6
    for r in done:
        assert len(r.output) == r.max_new_tokens
        assert all(0 <= t < bundle.cfg.vocab_size for t in r.output)


def test_engine_matches_offline_decode(bundle, params):
    """Engine greedy output == straight teacher-forced greedy decode."""
    vocab = bundle.cfg.vocab_size
    prompt = np.arange(1, 13) % vocab
    cfg = EngineConfig(slots=2, max_seq=64, prefill_len=12)
    eng = Engine(bundle, params, cfg)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
    done = eng.run(max_ticks=50)
    assert len(done) == 1

    # offline: repeated full forward + argmax (the slow oracle)
    toks = list(prompt)
    out = []
    for _ in range(5):
        batch = {
            "tokens": jnp.asarray([toks], jnp.int32),
            "positions": jnp.arange(len(toks), dtype=jnp.int32)[None],
        }
        hidden, _ = bundle.forward_train(params, batch)
        logits = bundle.logits(params, hidden[:, -1])
        nxt = int(jnp.argmax(logits, -1)[0])
        out.append(nxt)
        toks.append(nxt)
    assert done[0].output == out


def test_engine_slot_isolation(bundle, params):
    """Admitting new requests must not change a running request's output."""
    vocab = bundle.cfg.vocab_size
    prompt = (np.arange(1, 13) * 7) % vocab

    # run A alone
    cfg = EngineConfig(slots=2, max_seq=64, prefill_len=12)
    eng1 = Engine(bundle, params, cfg)
    eng1.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    alone = eng1.run(max_ticks=50)[0].output

    # run A while B and C arrive mid-flight
    eng2 = Engine(bundle, params, cfg)
    eng2.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    eng2.step()
    eng2.submit(_req(1, 12, vocab, new=6, seed=5))
    eng2.step()
    eng2.submit(_req(2, 12, vocab, new=6, seed=9))
    eng2.run(max_ticks=50)
    crowded = next(r for r in eng2.finished if r.uid == 0).output
    assert alone == crowded


def test_knnlm_hook_changes_distribution(bundle, params):
    corpus = np.random.default_rng(0).integers(
        1, bundle.cfg.vocab_size, (4, 24))
    store = build_datastore(bundle, params, corpus, m=4)
    assert store.index.n == 4 * 23
    hook = KNNLMHook(store=store, k=4, lam=0.5)
    logits = jnp.zeros((2, bundle.cfg.vocab_size))
    hidden = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, bundle.cfg.d_model)), jnp.float32)
    out = hook(logits, hidden)
    assert out.shape == logits.shape
    assert not np.allclose(np.asarray(out), np.asarray(logits))
    assert hook.queries_served == 2
    # still a (log-)distribution: logsumexp finite, probs sum to 1
    p = np.asarray(jnp.exp(jax.nn.log_softmax(out, -1)).sum(-1))
    np.testing.assert_allclose(p, 1.0, rtol=1e-4)


def test_knnlm_engine_end_to_end(bundle, params):
    vocab = bundle.cfg.vocab_size
    corpus = np.random.default_rng(0).integers(1, vocab, (4, 24))
    store = build_datastore(bundle, params, corpus, m=4)
    hook = KNNLMHook(store=store, k=4, lam=0.3)
    cfg = EngineConfig(slots=2, max_seq=48, prefill_len=12)
    eng = Engine(bundle, params, cfg, logits_hook=hook)
    eng.submit(_req(0, 12, vocab, new=4))
    done = eng.run(max_ticks=30)
    assert len(done) == 1 and len(done[0].output) == 4
    assert hook.queries_served >= 4


def test_knnlm_hook_routes_through_service(bundle, params):
    """service-routed lookups match the direct path when exact, and a
    shedding service degrades to the pure LM distribution, not an error."""
    from repro.serve.retrieval import RetrievalService, ServiceConfig

    vocab = bundle.cfg.vocab_size
    corpus = np.random.default_rng(0).integers(1, vocab, (4, 24))
    store = build_datastore(bundle, params, corpus, m=4)
    logits = jnp.zeros((2, vocab))
    hidden = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, bundle.cfg.d_model)), jnp.float32)

    direct = KNNLMHook(store=store, k=4, lam=0.5)
    svc = RetrievalService(ServiceConfig())
    routed = KNNLMHook(store=store, k=4, lam=0.5, service=svc,
                       deadline_s=60.0)
    np.testing.assert_allclose(np.asarray(routed(logits, hidden)),
                               np.asarray(direct(logits, hidden)),
                               rtol=1e-5, atol=1e-6)
    assert svc.counters["exact"] >= 1
    assert routed.service_tenant in svc.tenants

    # Hopeless deadline: the service sheds, the hook serves pure LM.
    svc.tenants[routed.service_tenant].cost.observe(10.0)
    routed.deadline_s = 0.001
    out = routed(logits, hidden)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))
    assert svc.counters["shed"] >= 1


def test_knnlm_hook_exposes_escalation_stats(bundle, params):
    corpus = np.random.default_rng(0).integers(
        1, bundle.cfg.vocab_size, (4, 24))
    store = build_datastore(bundle, params, corpus, m=4)
    hook = KNNLMHook(store=store, k=4, lam=0.5)
    hidden = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, bundle.cfg.d_model)), jnp.float32)
    hook(jnp.zeros((2, bundle.cfg.vocab_size)), hidden)
    assert hook.escalations >= 0
    assert hook.budget_final >= 4          # >= k: the launch's real budget
