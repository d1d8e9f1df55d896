"""End-to-end system tests: training convergence, fault tolerance
(checkpoint/restart exactness, crash recovery), TC-policy training, and
the serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.transprecision import PAPER_EDGE, TCPolicy
from repro.data.pipeline import make_pipeline
from repro.models import lm
from repro.optim import AdamWConfig
from repro.serve.engine import Request, ServeConfig, ServingEngine
from repro.train import Trainer, TrainerConfig
from repro.train.fault_tolerance import CrashBarrier, ElasticPlan, \
    HeartbeatMonitor


def tiny_cfg():
    return get_config("paper-edge", smoke=True)


def test_training_loss_decreases():
    """The synthetic stream has learnable structure; loss must fall."""
    cfg = tiny_cfg()
    tr = Trainer(cfg, TrainerConfig(steps=30, global_batch=8, seq_len=64,
                                    log_every=10),
                 AdamWConfig(lr=3e-3, total_steps=30, warmup_steps=3))
    out = tr.run()
    first = out["history"][0]["loss"]
    last = out["history"][-1]["loss"]
    assert last < first - 0.3, (first, last)


def test_checkpoint_restart_exact(tmp_path):
    """Train 6 steps straight vs 3 steps + crash + restore + 3 steps:
    the final losses must agree (deterministic pipeline + exact restore)."""
    cfg = tiny_cfg()
    opt = AdamWConfig(lr=1e-3, total_steps=6, warmup_steps=1)

    tr1 = Trainer(cfg, TrainerConfig(steps=6, global_batch=4, seq_len=32,
                                     log_every=1), opt)
    out1 = tr1.run()

    ckpt = str(tmp_path / "ck")
    tcfg = TrainerConfig(steps=6, global_batch=4, seq_len=32,
                         checkpoint_dir=ckpt, checkpoint_every=3,
                         async_checkpoint=False, log_every=1)
    tr2 = Trainer(cfg, tcfg, opt,
                  crash_barrier=CrashBarrier(crash_at_steps=[4]))
    with pytest.raises(CrashBarrier.SimulatedFault):
        tr2.run()
    assert tr2.ckpt.latest_step() == 3
    tr3 = Trainer(cfg, tcfg, opt)   # fresh process-equivalent; restores
    out3 = tr3.run()
    np.testing.assert_allclose(out3["metrics"]["loss"],
                               out1["metrics"]["loss"], rtol=1e-5)


def test_async_checkpoint_and_keep_k(tmp_path):
    cfg = tiny_cfg()
    tcfg = TrainerConfig(steps=9, global_batch=2, seq_len=16,
                         checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=2, checkpoint_keep=2,
                         async_checkpoint=True, log_every=100)
    tr = Trainer(cfg, tcfg, AdamWConfig(total_steps=9, warmup_steps=1))
    tr.run()
    tr.ckpt.wait()
    steps = tr.ckpt.steps()
    assert steps[-1] == 9
    assert len(steps) <= 2 + 1   # keep-k plus the final blocking save


def test_tc_policy_training_converges():
    """Training THROUGH the paper's P(8,2) policy (STE fake-quant) learns."""
    cfg = tiny_cfg()
    tr = Trainer(cfg, TrainerConfig(steps=30, global_batch=8, seq_len=64,
                                    log_every=10),
                 AdamWConfig(lr=3e-3, total_steps=30, warmup_steps=3),
                 policy=PAPER_EDGE)
    out = tr.run()
    assert out["history"][-1]["loss"] < out["history"][0]["loss"] - 0.2


def test_grad_wire_compression_matches_uncompressed_direction():
    """posit16 wire + error feedback must track the uncompressed run
    closely over a few steps (EF keeps compression unbiased over time)."""
    cfg = tiny_cfg()
    pol = TCPolicy(name="wire", grad_wire="posit16_2")
    t_plain = Trainer(cfg, TrainerConfig(steps=8, global_batch=4, seq_len=32,
                                         log_every=1),
                      AdamWConfig(lr=1e-3, total_steps=8, warmup_steps=1))
    t_wire = Trainer(cfg, TrainerConfig(steps=8, global_batch=4, seq_len=32,
                                        log_every=1),
                     AdamWConfig(lr=1e-3, total_steps=8, warmup_steps=1),
                     policy=pol)
    o1, o2 = t_plain.run(), t_wire.run()
    assert abs(o1["metrics"]["loss"] - o2["metrics"]["loss"]) < 0.15


def test_serving_engine_continuous_batching():
    cfg = tiny_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=64),
                        policy=PAPER_EDGE)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 5), max_new=6)
            for i in range(5)]   # 5 requests through 2 slots
    stats = eng.serve(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 6 for r in reqs)
    assert stats["prefills"] == 5


def test_serving_matches_forward_greedy():
    """Engine greedy decode == argmax of the training-path forward.
    f32 model: random-init bf16 logits are near-flat, so bf16 rounding
    differences between paths flip argmax ties spuriously."""
    import dataclasses
    cfg = dataclasses.replace(tiny_cfg(), dtype_name="float32")
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    prompt = np.arange(1, 9) % cfg.vocab
    eng = ServingEngine(cfg, params, ServeConfig(max_batch=1, max_len=64))
    req = Request(uid=0, prompt=prompt, max_new=4)
    eng.serve([req])
    # reference: iterative full forward
    toks = list(prompt)
    want = []
    for _ in range(4):
        logits, _ = lm.forward(params,
                               {"tokens": jnp.asarray([toks], jnp.int32)},
                               cfg)
        nxt = int(np.asarray(logits[0, -1, :cfg.vocab]).argmax())
        want.append(nxt)
        toks.append(nxt)
    assert req.out_tokens == want


def test_heartbeat_and_elastic_plan():
    mon = HeartbeatMonitor(n_hosts=4, dead_timeout_s=10, window=8)
    now = 1000.0
    for h in range(4):
        for s in range(8):
            mon.beat(h, s, 1.0 if h != 3 else 5.0, now=now)
    assert mon.stragglers() == [3]
    mon.beat(0, 9, 1.0, now=now + 100)
    dead = mon.dead_hosts(now=now + 100)
    assert set(dead) == {1, 2, 3}
    plan = ElasticPlan(global_batch=16, n_hosts=4)
    shards4 = [plan.shard_for(h) for h in range(4)]
    assert shards4[0] == slice(0, 4)
    plan2 = plan.resize(2)
    assert plan2.shard_for(1) == slice(8, 16)
    with pytest.raises(ValueError):
        ElasticPlan(global_batch=10, n_hosts=4)


def test_elastic_data_resharding_is_lossless():
    """Same step, different world sizes: union of host batches == global."""
    cfg = tiny_cfg()
    pipe = make_pipeline(cfg, global_batch=8, seq_len=16, seed=3)
    full = pipe.global_batch(step=5)["tokens"]
    for n_hosts in (1, 2, 4, 8):
        parts = [pipe.host_batch(5, h, n_hosts)["tokens"]
                 for h in range(n_hosts)]
        np.testing.assert_array_equal(np.concatenate(parts), full)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Entry points honour ``JAX_COMPILATION_CACHE_DIR`` and set no other
    directory; unset, the cache goes to the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cc.enable_compile_cache() == str(cc.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.REPO_CACHE_DIR)
    finally:                              # the tests never keep it on
        jax.config.update("jax_compilation_cache_dir", was)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(cc.REPO_CACHE_DIR) == os.path.join(repo, ".jax_cache")
