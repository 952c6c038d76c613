"""End-to-end system tests: train loop, checkpoint/resume, sharding rules,
optimizer, data determinism, HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import RunConfig, get_reduced
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.distributed.sharding import (DEFAULT_RULES, optim_rules,
                                        rules_for, spec_for)
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step
from repro.models import init_stack
from repro.optim import adamw


def _train(steps, ckpt_dir=None, resume=False, grad_compression=False,
           sched_steps=20):
    cfg = get_reduced("rdmabox-paper-100m")
    run = RunConfig(learning_rate=1e-3, total_steps=sched_steps,
                    warmup_steps=2, grad_compression=grad_compression)
    mesh = make_local_mesh(1, 1)
    with jax.set_mesh(mesh):
        jitted, _, (p_shard, o_shard) = build_train_step(cfg, run, mesh)
        params, _ = init_stack(jax.random.key(0), cfg)
        params = jax.device_put(params, p_shard)
        opt = jax.device_put(adamw.init(params, run), o_shard)
        start = 0
        ckpt = Checkpointer(ckpt_dir, keep=2) if ckpt_dir else None
        if resume and ckpt:
            r = ckpt.restore_latest((params, opt), (p_shard, o_shard))
            if r:
                start, (params, opt), _ = r
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 128, 4))
        losses = []
        for step in range(start, steps):
            params, opt, m = jitted(params, opt, data.batch_at(step))
            losses.append(float(m["loss"]))
            if ckpt and (step + 1) % 5 == 0:
                ckpt.save(step + 1, (params, opt))
        return losses, params


def test_training_reduces_loss():
    losses, _ = _train(20)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_grad_compression_still_trains():
    losses, _ = _train(15, grad_compression=True)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_checkpoint_resume_bitexact(tmp_path):
    """Crash/restart: resume must reproduce uninterrupted training."""
    _, p_full = _train(10, ckpt_dir=str(tmp_path / "a"))
    _train(5, ckpt_dir=str(tmp_path / "b"))                 # saves step 5
    _, p_resumed = _train(10, ckpt_dir=str(tmp_path / "b"), resume=True)
    fa = jax.tree.leaves(p_full)
    fb = jax.tree.leaves(p_resumed)
    for a, b in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_n(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    state = {"w": jnp.ones((4, 4))}
    for s in (1, 2, 3, 4):
        ck.save(s, state)
    assert ck.steps() == [3, 4]


def test_checkpoint_async_write_failure_raises_on_wait(tmp_path, monkeypatch):
    ck = Checkpointer(str(tmp_path))

    def disk_full(*_a, **_k):
        raise OSError("disk full")

    monkeypatch.setattr(np, "save", disk_full)
    ck.save(1, {"w": jnp.ones((2,))}, blocking=False)
    with pytest.raises(OSError, match="disk full"):
        ck.wait()
    ck.wait()                    # the error is reported once
    assert ck.steps() == []


def test_compile_cache_env_dir_wins_else_checkout(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_checkpoint_restores_dtypes(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = {"bf": jnp.ones((3,), jnp.bfloat16),
             "f32": jnp.ones((3,), jnp.float32) * 2,
             "i32": jnp.arange(3)}
    ck.save(1, state)
    back, _ = ck.restore(1, state)
    for k in state:
        assert back[k].dtype == state[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(state[k]))


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_spec_divisibility_fallback():
    mesh = make_local_mesh(1, 1)   # single device: everything degrades to P()
    s = spec_for((60, 128), ("experts", "embed"), mesh, rules_for())
    assert s == jax.sharding.PartitionSpec()


def test_optim_rules_shard_embed():
    r = optim_rules()
    assert r["embed"] == "data"
    assert DEFAULT_RULES["embed"] is None


def test_arch_overrides_apply():
    cfg = get_reduced("qwen2-moe-a2.7b")
    r = rules_for(cfg)
    assert r["experts"] is None and r["moe_ff"] == "model"


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    run = RunConfig(learning_rate=0.1, total_steps=100, warmup_steps=1,
                    weight_decay=0.0)
    params = {"w": jnp.ones((8,)) * 5}
    state = adamw.init(params, run)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}          # d/dw w²
        params, state, _ = adamw.update(grads, state, params, run)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_compression_error_feedback():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=512),
                          jnp.float32)}
    err = {"w": jnp.zeros(512)}
    deq, new_err = adamw.compress_grads(g, err)
    # int8 quantization error is bounded by scale/2 per element
    scale = float(jnp.abs(g["w"]).max()) / 127
    assert float(jnp.abs(new_err["w"]).max()) <= scale
    np.testing.assert_allclose(np.asarray(deq["w"] + new_err["w"]),
                               np.asarray(g["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_masked():
    d = SyntheticTokens(DataConfig(1000, 64, 4, seed=3))
    a, b = d.batch_at(7), d.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["targets"] == -100).any()
    assert a["tokens"].max() < 1000
    c = d.batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


# ---------------------------------------------------------------------------
# HLO analyzer (roofline engine)
# ---------------------------------------------------------------------------

def test_hlo_analyzer_loop_flops_exact():
    from repro.roofline.hlo_parse import analyze_text
    L, M, K = 7, 128, 256

    def f(x, ws):
        return jax.lax.scan(lambda x, w: (jnp.tanh(x @ w), None), x, ws)[0]

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((L, K, K), jnp.float32)).compile()
    costs = analyze_text(comp.as_text())
    assert abs(costs.flops - L * 2 * M * K * K) / (L * 2 * M * K * K) < 0.01
    # XLA's own cost_analysis undercounts the loop — ours must exceed it
    # (older JAX returns a one-element list of per-device cost dicts)
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    assert costs.flops > ca["flops"] * (L - 1)
