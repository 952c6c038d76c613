"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.models import (decode_step, forward, init_cache, init_stack,
                          loss_fn, prefill, splice_prefill)

KEY = jax.random.PRNGKey(0)


def make_inputs(cfg, B=2, S=64):
    if cfg.frontend:
        tokens = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32)
    else:
        tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    targets = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    return tokens, targets


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_train_step_smoke(arch):
    """One forward+backward on CPU: output shapes + finite loss + grads."""
    cfg = get_reduced(arch)
    params, specs = init_stack(KEY, cfg)
    tokens, targets = make_inputs(cfg)

    def lf(p):
        return loss_fn(p, tokens, targets, cfg)[0]

    loss, grads = jax.jit(jax.value_and_grad(lf))(params)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_smoke(arch):
    cfg = get_reduced(arch)
    params, _ = init_stack(KEY, cfg)
    B = 2
    cache = init_cache(cfg, B, max_len=32)
    tok = (jax.random.normal(KEY, (B, cfg.d_model), jnp.float32)
           if cfg.frontend else jnp.zeros((B,), jnp.int32))
    logits, cache = jax.jit(
        lambda p, c, t, i: decode_step(p, c, t, i, cfg)
    )(params, cache, tok, jnp.zeros((B,), jnp.int32))
    assert logits.shape == (B, cfg.padded_vocab)
    assert jnp.isfinite(logits).all(), f"{arch}: decode logits not finite"


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b",
                                  "mamba2-780m", "hymba-1.5b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode must reproduce the parallel forward logits —
    covers GQA, MLA (absorbed decode), SSD recurrence, and hybrid+SWA.

    For MoE archs, top-k routing is a *discontinuous* function: bf16
    accumulation differences between the batched and single-token paths can
    flip boundary experts, which is expected behaviour, not a numerics bug.
    The test pins top_k = num_experts (continuous gating, no drops) so it
    checks the attention/SSM/MLA numerics it is actually for."""
    from repro.configs import replace
    cfg = get_reduced(arch)
    if cfg.num_experts:
        cfg = replace(cfg, top_k=cfg.num_experts)
    params, _ = init_stack(jax.random.PRNGKey(1), cfg)
    B, S = 1, 24
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    full_logits, _ = forward(params, tokens, cfg)

    cache = init_cache(cfg, B, max_len=S)
    outs = []
    for t in range(S):
        logits, cache = decode_step(params, cache, tokens[:, t],
                                    jnp.full((B,), t, jnp.int32), cfg)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    a = np.asarray(full_logits, np.float32)
    b = np.asarray(dec, np.float32)
    # bf16 params + different contraction orders ⇒ loose tolerance
    denom = np.maximum(np.abs(a).max(), 1.0)
    assert np.abs(a - b).max() / denom < 0.05, f"{arch}: decode diverges"


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b",
                                  "hymba-1.5b"])
def test_decode_at_staggered_positions_matches_each_sequence_alone(arch):
    """Two sequences decoded together at different positions give the
    logits each gives decoded alone: each writes its new row at its own
    slot of the stacked cache (GQA, MLA's latent cache, and the ring
    buffer of a sliding window beside SSM state, with a window small
    enough that both rings wrap, at different slots)."""
    from repro.configs import replace
    cfg = get_reduced(arch)
    if cfg.num_experts:       # continuous gating: see the test above
        cfg = replace(cfg, top_k=cfg.num_experts)
    if cfg.window is not None:
        cfg = replace(cfg, window=8)
    params, _ = init_stack(jax.random.PRNGKey(1), cfg)
    LEAD, T = 5, 14                      # sequence 0 runs LEAD steps ahead
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, LEAD + T), 0,
                                cfg.vocab_size)
    step = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, cfg))

    def alone(b, n):
        cache, outs, lead = init_cache(cfg, 1, max_len=LEAD + T), [], None
        for t in range(n):
            if t == LEAD:
                lead = cache
            logits, cache = step(params, cache, tokens[b:b + 1, t],
                                 jnp.full((1,), t, jnp.int32))
            outs.append(np.asarray(logits[0], np.float32))
        return outs, lead

    first, lead = alone(0, LEAD + T)
    second, _ = alone(1, T)
    cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 1), lead,
                         init_cache(cfg, 1, max_len=LEAD + T))
    for t in range(T):
        logits, cache = step(params, cache, tokens[:, [LEAD + t, t]].diagonal(),
                             jnp.array([LEAD + t, t], jnp.int32))
        got = np.asarray(logits, np.float32)
        for b, want in ((0, first[LEAD + t]), (1, second[t])):
            err = np.abs(got[b] - want).max() / max(np.abs(want).max(), 1.0)
            assert err < 0.02, f"{arch}: sequence {b} at step {t}: {err}"


def test_serving_step_takes_the_cache_donated_and_writes_rows():
    """The serving step, lowered and compiled as ``serve.run`` does,
    aliases every cache leaf from its input to its output, and writes no
    whole layer's slab: no dynamic_update_slice of a cache-shaped array
    whose update spans the sequence axis."""
    import re

    from repro.launch import serve
    cfg = get_reduced("qwen1.5-0.5b")
    params, _ = init_stack(KEY, cfg)
    B, S = 2, 24
    cache = init_cache(cfg, B, max_len=S)
    lowered = serve.programs(cfg)[1].lower(
        params, cache, jnp.zeros((B,), jnp.int32), jnp.full((B,), 16, jnp.int32))
    leaves = jax.tree.leaves(cache)

    def dims(t):
        return tuple(int(d) for d in t.split("x")[:-1])

    for line in lowered.as_text().splitlines():
        m = re.search(r"stablehlo\.dynamic_update_slice .*: \(tensor<(\S+?)>, "
                      r"tensor<(\S+?)>", line)
        if m:
            into, update = dims(m.group(1)), dims(m.group(2))
            assert not (any(into == a.shape for a in leaves)
                        and update[2] == S), line.strip()
    compiled = lowered.compile()
    first = len(jax.tree.leaves(params))       # the cache's leaves follow
    aliased = {int(n) for n in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", compiled.as_text())}
    assert set(range(first, first + len(leaves))) <= aliased
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.nbytes for a in leaves)


@pytest.mark.parametrize("arch,window,P", [
    ("qwen1.5-0.5b", None, 32),
    ("deepseek-v2-lite-16b", None, 32),
    ("mamba2-780m", None, 16),
    ("hymba-1.5b", 8, 5),
    ("hymba-1.5b", 8, 16),
    ("hymba-1.5b", 8, 12),
], ids=["qwen1.5-0.5b", "deepseek-v2-lite-16b", "mamba2-780m",
        "hymba-1.5b-w8-p5", "hymba-1.5b-w8-p16", "hymba-1.5b-w8-p12"])
def test_prefill_then_decode_continues(arch, window, P):
    """A decode step after a prefill of ``P`` tokens, through
    ``splice_prefill``, gives the logits of the full forward pass: GQA
    and MLA rows, SSM state, and a sliding window's ring, whether the
    prompt fills part of it, all of it, or wraps it (P % window != 0)."""
    from repro.configs import replace
    cfg = get_reduced(arch)
    if cfg.num_experts:       # continuous gating, as in the tests above
        cfg = replace(cfg, top_k=cfg.num_experts)
    if window is not None:
        cfg = replace(cfg, window=window)
    params, _ = init_stack(KEY, cfg)
    B = 2
    tokens = jax.random.randint(KEY, (B, P + 1), 0, cfg.vocab_size)
    _, pcache = prefill(params, tokens[:, :P], cfg)
    cache = splice_prefill(init_cache(cfg, B, max_len=P + 8), pcache)
    logits, _ = decode_step(params, cache, tokens[:, P],
                            jnp.full((B,), P, jnp.int32), cfg)
    full_logits, _ = forward(params, tokens, cfg)
    a = np.asarray(full_logits[:, P], np.float32)
    b = np.asarray(logits, np.float32)
    assert np.abs(a - b).max() / max(np.abs(a).max(), 1.0) < 0.05


def test_loss_masks_negative_targets():
    cfg = get_reduced("qwen1.5-0.5b")
    params, _ = init_stack(KEY, cfg)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    l1, _ = loss_fn(params, tokens, targets, cfg)
    l2, _ = loss_fn(params, tokens, targets.at[:, :8].set(-100), cfg)
    assert jnp.isfinite(l2) and not jnp.allclose(l1, l2)


def test_param_count_analytic_close_to_actual():
    for arch in ("qwen1.5-0.5b", "mamba2-780m", "deepseek-v2-lite-16b"):
        cfg = get_reduced(arch)
        params, _ = init_stack(KEY, cfg)
        actual = sum(x.size for x in jax.tree.leaves(params))
        analytic = cfg.param_count()
        # padded vocab + small norms: within 20%
        assert abs(actual - analytic) / actual < 0.2, arch


@pytest.mark.parametrize("argv", [
    ["--arch", "qwen1.5-0.5b", "--spill"],
    ["--arch", "deepseek-v2-lite-16b", "--expert-parallel", "2"],
], ids=["qwen1.5-0.5b", "deepseek-v2-lite-16b-ep2"])
def test_served_tokens_match_a_plain_greedy_loop(argv, tmp_path, monkeypatch):
    """The tokens ``serve.run`` stacks, however late its loop reads them,
    are those of a plain loop over ``serve.programs`` that reads each
    step's token as soon as the step is called, in the same order."""
    import types

    from repro.launch import serve
    from repro.launch.mesh import make_local_mesh
    B, P, G = 3, 12, 7
    stacked = []

    def stack(arrays, *a, **kw):
        out = np.stack(arrays, *a, **kw)
        stacked.append(out)
        return out

    tapped_np = types.ModuleType(np.__name__)
    tapped_np.__dict__.update(vars(np), stack=stack)
    monkeypatch.setattr(serve, "np", tapped_np)
    # serve.run keeps its compile cache in the checkout unless told
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    out = serve.run(argv + ["--reduced", "--batch", str(B),
                            "--prompt-len", str(P), "--gen", str(G)])
    served = next(a for a in reversed(stacked) if a.shape == (B, G))

    cfg, params, prompts = out["cfg"], out["params"], out["prompts"]
    prefill_jit, step_jit, pick = serve.programs(cfg)
    want = []
    with jax.set_mesh(make_local_mesh(1, 1)):
        logits, pcache = prefill_jit(params, prompts)
        cache = splice_prefill(init_cache(cfg, B, max_len=P + G), pcache)
        tok = pick(logits, jnp.zeros((B,), jnp.int32))
        assert np.array_equal(tok, out["first_token"])
        cur = jnp.full((B,), P, jnp.int32)
        for _ in range(G):
            _, cache, tok, cur = step_jit(params, cache, tok, cur)
            want.append(np.asarray(tok))
    assert np.array_equal(served, np.stack(want, axis=1))
