"""Optimizer, checkpoint/restart, fault tolerance, straggler, data
pipeline, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as CKPT
from repro.configs.base import ShapeConfig, get_config, reduced
from repro.data.pipeline import DataConfig, TokenPipeline, batch_at
from repro.launch.steps import (abstract_train_state, init_train_state,
                                make_train_step)
from repro.models.model_zoo import build_model
from repro.training import optimizer as OPT
from repro.training.train_loop import LoopConfig, StragglerMonitor, run


def _tiny_setup(tmp, arch="yi-9b", accum=1):
    cfg = reduced(get_config(arch))
    b = build_model(cfg)
    ocfg = OPT.OptConfig(lr=5e-3, warmup_steps=5, total_steps=200,
                         accum_steps=accum)
    state = init_train_state(b, ocfg, jax.random.key(0))
    step = jax.jit(make_train_step(b, ocfg, None))
    shape = ShapeConfig("t", 64, 2, "train")
    data = TokenPipeline(DataConfig(seed=3), cfg, shape)
    return b, state, step, data, cfg


def test_loss_decreases(tmp_path):
    _, state, step, data, _ = _tiny_setup(tmp_path)
    losses = []
    for _ in range(30):
        state, m = step(state, next(data))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_quantized_moments_track_fp32():
    cfg = OPT.OptConfig(lr=1e-2)
    cfg_q = OPT.OptConfig(lr=1e-2, quant_moments=True)
    p = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 32, 64)),
                          jnp.float32)}
    s, sq = OPT.init_state(cfg, p), OPT.init_state(cfg_q, p)
    pq = dict(p)
    for i in range(5):
        g = jax.tree.map(
            lambda x: 0.01 * jnp.asarray(
                np.random.default_rng(i).normal(size=x.shape), x.dtype), p)
        p, s, _ = OPT.apply_updates(cfg, p, g, s)
        pq, sq, _ = OPT.apply_updates(cfg_q, pq, g, sq)
    diff = float(jnp.max(jnp.abs(p["w"] - pq["w"])))
    scale = float(jnp.max(jnp.abs(p["w"])))
    assert diff < 0.05 * scale


def test_grad_accumulation_matches_full_batch(tmp_path):
    b, state, step1, data, cfg = _tiny_setup(tmp_path, accum=1)
    _, state2, step2, _, _ = _tiny_setup(tmp_path, accum=2)
    batch = next(data)
    s1, m1 = step1(state, batch)
    s2, m2 = step2(state2, batch)
    # same initial params => same grads => same updated params (within fp)
    d = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))),
                     s1["params"], s2["params"])
    assert max(jax.tree.leaves(d)) < 5e-3


def test_checkpoint_atomic_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.asarray(7, jnp.int32)}}
    CKPT.save(str(tmp_path), 5, tree)
    CKPT.save(str(tmp_path), 10, jax.tree.map(lambda x: x + 1, tree))
    assert CKPT.latest_step(str(tmp_path)) == 10
    got, step = CKPT.restore(str(tmp_path), tree)
    assert step == 10
    np.testing.assert_allclose(np.asarray(got["a"]),
                               np.asarray(tree["a"]) + 1)


def test_crash_restart_resumes_identically(tmp_path):
    ck = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=12, ckpt_every=4, ckpt_dir=ck,
                      async_ckpt=False)
    _, state0, step, data, cfg = _tiny_setup(tmp_path)
    # uninterrupted run
    sA, histA = run(step, state0, data, lcfg, resume=False)
    # crashed run: same init, fails at step 9 then resumes from step 8
    import shutil
    shutil.rmtree(ck, ignore_errors=True)
    _, state0b, stepb, datab, _ = _tiny_setup(tmp_path)
    with pytest.raises(RuntimeError):
        run(stepb, state0b, datab, lcfg, resume=False, crash_at=9)
    _, state0c, stepc, datac, _ = _tiny_setup(tmp_path)
    sB, histB = run(stepc, state0c, datac, lcfg, resume=True)
    assert histB["resumed_from"] == 8
    np.testing.assert_allclose(histA["loss"][8:], histB["loss"],
                               rtol=1e-4, atol=1e-5)


def test_async_checkpointer(tmp_path):
    ac = CKPT.AsyncCheckpointer(str(tmp_path))
    tree = {"x": jnp.ones((64, 64))}
    ac.save(1, tree)
    ac.save(2, jax.tree.map(lambda a: a * 2, tree))   # waits for save 1
    ac.wait()
    got, step = CKPT.restore(str(tmp_path), tree)
    assert step == 2
    np.testing.assert_allclose(np.asarray(got["x"]), 2.0)


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(window=8, threshold=3.0)
    for i in range(10):
        mon.observe(i, 0.1)
    assert mon.observe(10, 0.5)
    assert mon.events and mon.events[0]["step"] == 10
    assert not mon.observe(11, 0.12)


def test_data_pipeline_deterministic_and_elastic():
    d = DataConfig(seed=9, vocab_size=128)
    b1 = batch_at(d, 7, 4, 16)
    b2 = batch_at(d, 7, 4, 16)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # restartability: iterating to step 7 equals direct addressing
    cfg = reduced(get_config("yi-9b"))
    pipe = TokenPipeline(d, cfg, ShapeConfig("t", 16, 4, "train"),
                         start_step=7)
    b3 = next(pipe)
    d2 = DataConfig(seed=9, vocab_size=cfg.vocab_size)
    np.testing.assert_array_equal(
        np.asarray(b3["tokens"]),
        np.asarray(batch_at(d2, 7, 4, 16)["tokens"]))


def test_serving_engine_drains():
    from repro.serving.engine import ServingEngine, Request
    cfg = reduced(get_config("yi-9b"))
    b = build_model(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          b.init_params(jax.random.key(0)))
    eng = ServingEngine(b, params, slots=2, cache_len=64)
    rng = np.random.default_rng(0)
    for rid in range(3):
        eng.submit(Request(rid, rng.integers(0, 64, size=8,
                                             dtype=np.int32), max_new=4))
    eng.run_to_completion(max_ticks=64)
    assert all(r is None for r in eng.active)
