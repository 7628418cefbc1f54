"""Training infrastructure: loop convergence, checkpoint/restart, preemption,
cross-mesh resharding, gradient compression, pipeline state."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import (PipelineConfig, TokenPipeline,
                                 synthetic_token_source)
from repro.launch.train import build_state
from repro.models.layers import init_from_spec
from repro.models.transformer import model_spec
from repro.train.loop import LoopConfig, Trainer
from repro.train.optim import OptimConfig, init_opt_state
from repro.train.train_step import TrainConfig, cross_entropy, make_train_step


def _smoke_setup(tmp_path, steps=20, microbatches=1):
    cfg = get_config("llama3_2_3b").smoke()
    tcfg = TrainConfig(optim=OptimConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=steps),
                       microbatches=microbatches)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    src = synthetic_token_source(64, 32, cfg.vocab, seed=1)
    pipe = TokenPipeline(src, PipelineConfig(batch=4, seq=32, prefetch=0))
    state = build_state(cfg)
    loop = LoopConfig(total_steps=steps, ckpt_every=8, log_every=5,
                      ckpt_dir=str(tmp_path / "ck"))
    return cfg, step, pipe, state, loop


def test_loss_decreases(tmp_path):
    cfg, step, pipe, state, loop = _smoke_setup(tmp_path, steps=25)
    tr = Trainer(step, state, iter(pipe), loop, pipeline_state=pipe.state)
    tr.log = lambda m: None
    out = tr.run()
    losses = [m["loss"] for m in tr.metrics_history]
    assert out["steps"] == 25
    assert losses[-1] < losses[0]


def test_checkpoint_resume_continues(tmp_path):
    cfg, step, pipe, state, loop = _smoke_setup(tmp_path, steps=10)
    tr = Trainer(step, state, iter(pipe), loop, pipeline_state=pipe.state)
    tr.log = lambda m: None
    tr.run()
    mgr = CheckpointManager(loop.ckpt_dir)
    assert mgr.latest_step() == 10
    # resume into a new trainer; runs 5 more steps
    loop2 = LoopConfig(total_steps=15, ckpt_every=100,
                       ckpt_dir=loop.ckpt_dir)
    pipe2 = TokenPipeline(pipe.source, pipe.cfg)
    state2 = build_state(cfg, seed=99)     # would diverge unless restored
    tr2 = Trainer(step, state2, iter(pipe2), loop2)
    assert tr2.try_resume()
    assert tr2.step == 10
    out = tr2.run()
    assert out["steps"] == 15
    assert int(tr2.state["opt"]["step"]) == 15


def test_preemption_checkpoint(tmp_path):
    cfg, step, pipe, state, loop = _smoke_setup(tmp_path, steps=1000)
    tr = Trainer(step, state, iter(pipe), loop, pipeline_state=pipe.state)
    tr.log = lambda m: None
    # simulate a preemption signal after a few steps via the data stream
    raw = iter(pipe)

    def limited():
        for i, b in enumerate(raw):
            if i == 7:
                tr._preempted = True     # what the SIGTERM handler sets
            yield b
    tr.data = limited()
    out = tr.run()
    assert out["preempted"]
    mgr = CheckpointManager(loop.ckpt_dir)
    assert mgr.latest_step() == out["steps"]   # final ckpt written


def test_checkpoint_atomicity_ignores_uncommitted(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.ones((4,))}
    mgr.save(5, state)
    # fake a partial (crashed) save at a later step: no COMMIT file
    d = tmp_path / "step_000000009"
    (d / "arrays").mkdir(parents=True)
    (d / "manifest.json").write_text("{}")
    assert mgr.latest_step() == 5


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"w": jnp.ones((2,)) * s})
    assert mgr.all_steps() == [3, 4]


def test_cross_mesh_resharding_restore(tmp_path):
    """Elasticity: save unsharded, restore under a different device layout
    (1-device 'mesh' here; the sharding path is identical at any size)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    state = {"params": {"w": jnp.arange(16.0).reshape(4, 4)}}
    mgr.save(3, state)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"params": {"w": NamedSharding(mesh, P("data", None))}}
    step, restored, _ = mgr.restore(shardings=sh)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.arange(16.0).reshape(4, 4))
    assert restored["params"]["w"].sharding == sh["params"]["w"]


def test_pipeline_state_checkpoint_roundtrip(tmp_path):
    cfg, step, pipe, state, loop = _smoke_setup(tmp_path, steps=6)
    tr = Trainer(step, state, iter(pipe), loop, pipeline_state=pipe.state)
    tr.log = lambda m: None
    tr.run()
    mgr = CheckpointManager(loop.ckpt_dir)
    _, _, extras = mgr.restore()
    assert extras["pipeline"]["batch_index"] == pipe.state.batch_index
    assert extras["pipeline"]["epoch"] == pipe.state.epoch


def test_microbatched_step_matches_full_batch():
    """Grad accumulation must be loss/grad-equivalent to the full batch."""
    cfg = get_config("qwen2_5_3b").smoke()
    key = jax.random.PRNGKey(0)
    params = init_from_spec(model_spec(cfg), key)
    batch = {
        "tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab),
        "labels": jax.random.randint(key, (4, 16), 0, cfg.vocab),
    }
    outs = {}
    for mb in (1, 2):
        tcfg = TrainConfig(optim=OptimConfig(lr=1e-3, warmup_steps=1,
                                             total_steps=5),
                           microbatches=mb)
        step = make_train_step(cfg, tcfg)
        state = {"params": params, "opt": init_opt_state(params)}
        new_state, m = step(state, batch)
        outs[mb] = new_state["params"]["unembed"]
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[2]),
                               rtol=5e-3, atol=1e-5)


def test_gradient_compression_error_feedback():
    """int8 EF compression: single-step error bounded; residual carries the
    quantization error exactly."""
    from repro.distributed.compression import compress_tree
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(256, 8)) * 1e-3, jnp.float32)}
    deq, res = compress_tree(g, None)
    np.testing.assert_allclose(np.asarray(deq["w"] + res["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-8)
    # relative error of one shot is small
    rel = float(jnp.linalg.norm(deq["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02


def test_compressed_psum_shardmap():
    from functools import partial
    from repro.distributed.compression import compressed_psum
    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)

    @partial(jax.jit)
    def run(x):
        f = jax.shard_map(lambda v: compressed_psum(v[0], "d")[0][None],
                          mesh=mesh, in_specs=jax.sharding.PartitionSpec("d"),
                          out_specs=jax.sharding.PartitionSpec("d"))
        return f(x[None])
    out = run(x)[0]
    # int8 block quantization: error bounded by half a quant step (~scale/2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=0,
                               atol=0.02)


def test_sharded_vocab_ce_matches_gather():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 32, (2, 8)), jnp.int32)
    a = cross_entropy(logits, labels, "sharded_vocab")
    b = cross_entropy(logits, labels, "gather_logits")
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_ce_label_masking():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, 4, 8)), jnp.float32)
    labels = jnp.asarray([[1, 2, -100, -100]], jnp.int32)
    full = cross_entropy(logits[:, :2], labels[:, :2])
    masked = cross_entropy(logits, labels)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-6)
