"""Train substrate tests: optimizer, losses, sharded train step, checkpoint
elastic restart, straggler monitor, data-pipeline determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.common import ShapeSpec
from repro.data import pipeline as data_pipe
from repro.models.registry import build_model
from repro.train import checkpoint as ckpt
from repro.train import losses
from repro.train.optimizer import OptimizerConfig, init_state, apply_updates, schedule
from repro.train.straggler import StragglerConfig, StragglerMonitor
from repro.train.train_loop import (TrainConfig, init_train_state,
                                    make_train_step, state_shardings)

SMALL_SHAPE = ShapeSpec("smoke", seq_len=32, global_batch=4, kind="train")


def small_bundle():
    return build_model(configs.get_reduced("starcoder2-3b"))


def small_batch(bundle, step=0):
    cfg = data_pipe.TokenStreamConfig(
        vocab_size=bundle.cfg.vocab_size, seq_len=SMALL_SHAPE.seq_len,
        global_batch=SMALL_SHAPE.global_batch)
    return data_pipe.token_batch(cfg, step)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_schedule_warmup_cosine():
    cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.int32(s))) for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 5e-4) < 1e-9
    assert abs(lrs[2] - 1e-3) < 1e-9
    assert lrs[2] > lrs[3] > lrs[4]
    assert abs(lrs[4] - 1e-4) < 1e-6


def test_adamw_converges_quadratic():
    cfg = OptimizerConfig(peak_lr=0.1, warmup_steps=0, total_steps=200,
                          weight_decay=0.0, grad_clip_norm=1e9)
    params = {"w": jnp.ones((4, 4)) * 3.0}
    state = init_state(params)
    for _ in range(200):
        grads = {"w": 2.0 * params["w"]}
        params, state, stats = apply_updates(params, grads, state, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.05
    assert np.isfinite(float(stats["grad_norm"]))


def test_grad_clip():
    cfg = OptimizerConfig(grad_clip_norm=1.0, warmup_steps=0)
    params = {"w": jnp.zeros((3,))}
    state = init_state(params)
    _, _, stats = apply_updates(params, {"w": jnp.full((3,), 100.0)},
                                state, cfg)
    assert float(stats["grad_norm"]) > 100.0  # pre-clip norm is reported


# ---------------------------------------------------------------------------
# chunked loss
# ---------------------------------------------------------------------------

def test_chunked_xent_matches_dense():
    rng = np.random.default_rng(0)
    b, s, d, v = 2, 64, 16, 97
    hidden = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    loss_c, m = losses.chunked_cross_entropy(hidden, labels, table, chunk=16)
    logits = hidden @ table.T
    dense = jnp.mean(jax.nn.logsumexp(logits, -1)
                     - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    np.testing.assert_allclose(float(m["nll"]), float(dense), rtol=1e-5)
    assert float(loss_c) >= float(m["nll"])  # z-loss is non-negative


def test_chunked_xent_grads_match_dense():
    rng = np.random.default_rng(1)
    b, s, d, v = 2, 32, 8, 31
    hidden = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)

    g1 = jax.grad(lambda t: losses.chunked_cross_entropy(
        hidden, labels, t, chunk=8, z_weight=0.0)[0])(table)

    def dense(t):
        logits = hidden @ t.T
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0])

    g2 = jax.grad(dense)(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


# ---------------------------------------------------------------------------
# train step (host mesh)
# ---------------------------------------------------------------------------

def test_train_step_runs_and_improves():
    bundle = small_bundle()
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    tc = TrainConfig(microbatches=1,
                     opt=OptimizerConfig(peak_lr=3e-3, warmup_steps=5,
                                         total_steps=60))
    with mesh:
        state = init_train_state(bundle, mesh, jax.random.PRNGKey(0))
        step = make_train_step(bundle, mesh, tc, SMALL_SHAPE)
        first = None
        for i in range(30):
            state, metrics = step(state, small_batch(bundle, i % 4))
            if first is None:
                first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first - 0.5, (first, last)


def test_microbatch_accumulation_matches_full_batch():
    bundle = small_bundle()
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    batch = small_batch(bundle, 0)
    with mesh:
        s1 = init_train_state(bundle, mesh, jax.random.PRNGKey(0))
        s2 = jax.tree.map(jnp.copy, s1)
        step1 = make_train_step(bundle, mesh,
                                TrainConfig(microbatches=1), SMALL_SHAPE)
        step2 = make_train_step(bundle, mesh,
                                TrainConfig(microbatches=2), SMALL_SHAPE)
        n1, m1 = step1(s1, batch)
        n2, m2 = step2(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-4)
    # parameters after one update agree (accumulated grads == full grads)
    a = jax.tree.leaves(n1.params)[0]
    b = jax.tree.leaves(n2.params)[0]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3)


# ---------------------------------------------------------------------------
# checkpoint: atomicity, retention, elastic restore, bit-exact restart
# ---------------------------------------------------------------------------

@pytest.fixture
def ckpt_dir(tmp_path):
    return str(tmp_path / "ckpts")


def test_checkpoint_roundtrip(ckpt_dir):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(6, 2),
            "b": {"c": jnp.ones((3,), jnp.bfloat16),
                  "step": jnp.int32(7)}}
    ckpt.save_checkpoint(ckpt_dir, 3, tree, num_shards=3)
    assert ckpt.latest_step(ckpt_dir) == 3
    out = ckpt.restore_checkpoint(ckpt_dir, 3, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_retention(ckpt_dir):
    tree = {"a": jnp.zeros((4,))}
    for s in range(6):
        ckpt.save_checkpoint(ckpt_dir, s, tree, keep=2)
    assert ckpt.list_steps(ckpt_dir) == [4, 5]


def test_checkpoint_atomic_no_partial_visible(ckpt_dir):
    tree = {"a": jnp.zeros((4,))}
    ckpt.save_checkpoint(ckpt_dir, 1, tree)
    # simulate a crashed writer: stray tmp dir must be invisible
    os.makedirs(os.path.join(ckpt_dir, "step_000000009.tmp-dead"))
    assert ckpt.latest_step(ckpt_dir) == 1
    # and a finished dir without manifest is also invisible
    os.makedirs(os.path.join(ckpt_dir, "step_000000008"))
    assert ckpt.latest_step(ckpt_dir) == 1


def test_elastic_restore_across_mesh_shapes(ckpt_dir):
    """Save on an 8-way mesh, restore onto 4-way and back onto 8-way."""
    bundle = small_bundle()
    mesh8 = jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh8:
        state = init_train_state(bundle, mesh8, jax.random.PRNGKey(0))
    ckpt.save_checkpoint(ckpt_dir, 0, state, num_shards=8)

    # "different cluster": restore with fresh shardings resolved on a new mesh
    mesh4 = jax.make_mesh((1,), ("data",),
                          axis_types=(jax.sharding.AxisType.Auto,))
    sh = state_shardings(bundle, mesh4)
    structs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    with mesh4:
        restored = ckpt.restore_checkpoint(ckpt_dir, 0, structs, shardings=sh)
    for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(restored),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_restart_is_bit_exact(ckpt_dir):
    """Train 4 steps; restart from step-2 checkpoint; trajectories match."""
    bundle = small_bundle()
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    tc = TrainConfig(opt=OptimizerConfig(peak_lr=1e-3, warmup_steps=0,
                                         total_steps=10))
    with mesh:
        step = make_train_step(bundle, mesh, tc, SMALL_SHAPE)
        state = init_train_state(bundle, mesh, jax.random.PRNGKey(0))
        losses_a = []
        for i in range(4):
            if i == 2:
                ckpt.save_checkpoint(ckpt_dir, i, state)
            state, m = step(state, small_batch(bundle, i))
            losses_a.append(float(m["loss"]))

        structs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state_b = ckpt.restore_checkpoint(ckpt_dir, 2, structs)
        losses_b = []
        for i in range(2, 4):
            state_b, m = step(state_b, small_batch(bundle, i))
            losses_b.append(float(m["loss"]))
    np.testing.assert_allclose(losses_a[2:], losses_b, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------

def test_straggler_fires_on_slow_host():
    cfg = StragglerConfig(window=20, tolerance=1.5, patience=3,
                          warmup_steps=0)
    fired = []
    mon = StragglerMonitor(cfg, num_hosts=4,
                           mitigation=lambda ev: fired.append(ev))
    for step in range(30):
        times = [0.10, 0.11, 0.10, 0.10]
        if step >= 10:
            times[2] = 0.40            # host 2 goes bad
        mon.start_step()
        mon.end_step(times)
    assert fired and all(ev.host == 2 for ev in fired)
    assert mon.summary()["events"] >= 1


def test_straggler_quiet_on_uniform_times():
    mon = StragglerMonitor(StragglerConfig(warmup_steps=0), num_hosts=2)
    for _ in range(50):
        mon.start_step()
        mon.end_step([0.1, 0.1])
    assert mon.summary()["events"] == 0


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------

def test_token_batch_step_addressable():
    cfg = data_pipe.TokenStreamConfig(vocab_size=128, seq_len=16,
                                      global_batch=4, seed=3)
    a = data_pipe.token_batch(cfg, 7)
    b = data_pipe.token_batch(cfg, 7)
    c = data_pipe.token_batch(cfg, 8)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(a["tokens"])[:, 1:],
                                  np.asarray(a["labels"])[:, :-1])


def test_vector_datasets_match_table4():
    for _name, spec in data_pipe.PAPER_DATASETS.items():
        data = data_pipe.make_vectors(spec, scale=0.001)
        assert data.shape[1] == spec.d
        if spec.measure == "isd":
            assert data.min() > 0
        q = data_pipe.make_queries(spec, num=5, scale=0.001)
        assert q.shape == (5, spec.d)


def test_vector_datasets_same_in_every_process():
    """A seeded dataset is the same array in every process: the per-name
    seed must not come from str hash(), which PYTHONHASHSEED salts."""
    import hashlib
    import subprocess
    import sys
    code = ("import hashlib; from repro.data import pipeline as p; "
            "x = p.make_vectors(p.PAPER_DATASETS['deep'], scale=1e-4); "
            "print(hashlib.sha256(x.tobytes()).hexdigest())")
    here = data_pipe.make_vectors(data_pipe.PAPER_DATASETS["deep"],
                                  scale=1e-4)
    digests = set()
    for salt in ("1", "2"):
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.abspath(src))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True,
                             timeout=300)
        digests.add(out.stdout.split()[-1])
    assert digests == {hashlib.sha256(here.tobytes()).hexdigest()}
