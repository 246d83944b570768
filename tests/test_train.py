"""Training substrate: AdamW, schedules (incl. WSD), microbatch-grad
equivalence, int8 compression, data-pipeline determinism/sharding,
checkpoint atomicity + restart equality, straggler/NaN guards."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import Checkpointer
from repro.config import TrainConfig, reduced
from repro.configs import get_config
from repro.data import TokenPipeline
from repro.models.api import build_model, make_batch
from repro.optim import AdamW, make_schedule
from repro.train.train_step import _int8_roundtrip, make_train_step
from repro.train.trainer import Trainer


def test_adamw_optimizes_quadratic():
    tc = TrainConfig(learning_rate=0.1, weight_decay=0.0, grad_clip=0.0,
                     schedule="const", warmup_steps=1)
    opt = AdamW(tc)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.update(grads, state, params, 0.05)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_wsd_schedule_phases():
    tc = TrainConfig(learning_rate=1e-3, schedule="wsd", warmup_steps=10,
                     stable_steps=80, decay_steps=100)
    s = make_schedule(tc)
    assert float(s(5)) < 1e-3                       # warmup
    np.testing.assert_allclose(float(s(50)), 1e-3)  # stable plateau
    assert float(s(99)) < 0.2e-3                    # sharp decay
    assert float(s(200)) <= float(s(100)) + 1e-12


@pytest.mark.slow
def test_microbatch_grads_match_full_batch():
    cfg = reduced(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, "train", 4, 32, seed=0)
    tc1 = TrainConfig(microbatches=1, remat="none", grad_clip=0.0)
    tc4 = TrainConfig(microbatches=4, remat="none", grad_clip=0.0)
    opt = AdamW(tc1)
    s1 = opt.init(params)
    p1, _, m1 = make_train_step(model, tc1)(params, s1, batch)
    s2 = AdamW(tc4).init(params)
    p2, _, m2 = make_train_step(model, tc4)(params, s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
    assert max(jax.tree.leaves(d)) < 1e-4


@given(st.lists(st.floats(min_value=-100, max_value=100,
                          allow_nan=False), min_size=1, max_size=64))
@settings(max_examples=6, deadline=None)    # tier-1 profile; each example
def test_int8_compression_error_bound(xs):  # pays a fresh jit trace
    g = jnp.asarray(xs, jnp.float32)
    out = _int8_roundtrip(g)
    scale = max(abs(float(jnp.max(g))), abs(float(jnp.min(g)))) / 127.0
    assert float(jnp.max(jnp.abs(out - g))) <= scale * 0.5 + 1e-6


def test_pipeline_deterministic_and_sharded():
    cfg = reduced(get_config("qwen3-1.7b"))
    p1 = TokenPipeline(cfg, batch=8, seq=32, seed=5)
    p2 = TokenPipeline(cfg, batch=8, seq=32, seed=5)
    np.testing.assert_array_equal(p1.batch_at(3)["tokens"],
                                  p2.batch_at(3)["tokens"])
    assert not np.array_equal(p1.batch_at(3)["tokens"],
                              p1.batch_at(4)["tokens"])
    # host sharding: different hosts get different data, same shapes
    h0 = TokenPipeline(cfg, batch=8, seq=32, seed=5, host_index=0,
                       host_count=2)
    h1 = TokenPipeline(cfg, batch=8, seq=32, seed=5, host_index=1,
                       host_count=2)
    a, b = h0.batch_at(0)["tokens"], h1.batch_at(0)["tokens"]
    assert a.shape == (4, 32) == b.shape
    assert not np.array_equal(a, b)
    # labels are next-token shifted
    full = p1.batch_at(0)
    assert full["tokens"].shape == full["labels"].shape


def test_checkpoint_atomic_and_checksummed(tmp_path):
    ck = Checkpointer(str(tmp_path), async_write=False)
    state = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    ck.save(1, state)
    ck.save(2, jax.tree.map(lambda x: x * 2, state))
    # a torn write must be invisible to restore
    (tmp_path / "step_00000099.tmp").mkdir()
    restored, step = ck.restore(None, state)
    assert step == 2
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(10.0) * 2)
    # corruption detection
    import glob
    victim = sorted(glob.glob(str(tmp_path / "step_00000002" / "*.npy")))[0]
    with open(victim, "r+b") as f:
        f.seek(200)
        f.write(b"\xff\xff\xff")
    with pytest.raises(IOError):
        ck.restore(2, state)


@pytest.mark.slow
def test_trainer_restart_continues_identically(tmp_path):
    cfg = reduced(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)
    tc = TrainConfig(checkpoint_every=4, remat="none", learning_rate=1e-3,
                     warmup_steps=2, async_checkpoint=False)

    # uninterrupted 8-step run
    t_ref = Trainer(model, cfg, tc, batch=4, seq=32,
                    ckpt_dir=str(tmp_path / "ref"))
    t_ref.init_or_restore()
    m_ref = t_ref.train(8)

    # run 4 steps, "crash", restart, run 4 more
    d = str(tmp_path / "restart")
    t1 = Trainer(model, cfg, tc, batch=4, seq=32, ckpt_dir=d)
    t1.init_or_restore()
    t1.train(4)
    t2 = Trainer(model, cfg, tc, batch=4, seq=32, ckpt_dir=d)
    assert t2.init_or_restore() == 4
    m2 = t2.train(4)
    # data pipeline replays -> losses at steps 5..8 match exactly
    ref_tail = [s["loss"] for s in m_ref.steps[4:]]
    got_tail = [s["loss"] for s in m2.steps]
    np.testing.assert_allclose(got_tail, ref_tail, rtol=2e-4)


def test_nan_guard_skips_update(tmp_path):
    cfg = reduced(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)

    class Exploding:
        def __getattr__(self, k):
            return getattr(model, k)

        def loss(self, params, batch, **kw):
            return model.loss(params, batch, **kw) * jnp.nan

    tc = TrainConfig(checkpoint_every=100, remat="none")
    tr = Trainer(Exploding(), cfg, tc, batch=2, seq=16,
                 ckpt_dir=str(tmp_path))
    tr.init_or_restore()
    before = jax.tree.leaves(tr.params)[0]
    m = tr.train(2)
    assert m.skipped_steps == 2
