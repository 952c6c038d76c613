"""The expert layer as DeepSeek-V2 publishes it, and the serving job's
share of an expert-parallel deployment: dropless routing over every
expert, the held share's partial output, YaRN, the leading dense layer,
and the routing counts a serving job records."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced, replace
from repro.models import init_stack
from repro.models import moe as moe_mod
from repro.models.layers import SpecTree, rope_freqs, swiglu, yarn_mscale

ARCH = "deepseek-v2-lite-16b"


def moe_params(cfg, key=jax.random.key(4)):
    return moe_mod.init_moe(key, cfg, SpecTree())


def tokens_in(cfg, n=24, key=jax.random.key(5)):
    return jax.random.normal(key, (2, n, cfg.d_model), jnp.float32).astype(
        jnp.bfloat16)


def dense_moe(p, x, cfg):
    """Every token through every held expert, weighted by its gate (0
    where the expert is not among its top k): no dispatch at all."""
    xt = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"], -1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    held = cfg.expert_offset + jnp.arange(cfg.experts_held)
    g = jnp.sum(gate[..., None] * (idx[..., None] == held), 1)   # (T, E)
    out = jax.vmap(lambda wi, wg, wo: swiglu(xt, wi, wg, wo))(
        p["wi"], p["wg"], p["wo"]).astype(jnp.float32)           # (E, T, M)
    y = jnp.einsum("etm,te->tm", out, g)
    if cfg.num_shared_experts:
        y = y + swiglu(xt, p["shared_wi"], p["shared_wg"],
                       p["shared_wo"]).astype(jnp.float32)
    return y


@pytest.mark.parametrize("ep", [2, 4])
def test_expert_shares_sum_to_the_uncut_layer(ep):
    """The partial outputs of all ranks, with the shared experts counted
    once, add up to the layer with every expert held; each rank holds
    the uncut layer's experts of its share, bit for bit."""
    full = get_reduced(ARCH)
    x = tokens_in(full)
    p_full = moe_params(full)
    y_full, _, held_full = moe_mod.moe_apply(p_full, x, full)
    shared = swiglu(x, p_full["shared_wi"], p_full["shared_wg"],
                    p_full["shared_wo"]).astype(jnp.float32)
    total = -(ep - 1) * shared
    n = full.num_experts // ep
    for rank in range(ep):
        cfg = replace(full, expert_parallel=ep, expert_rank=rank)
        p = moe_params(cfg)
        for w in ("wi", "wg", "wo"):
            assert np.array_equal(np.asarray(p[w]),
                                  np.asarray(p_full[w][rank * n:(rank + 1) * n]))
        y, _, held = moe_mod.moe_apply(p, x, cfg)
        assert held.shape == x.shape[:2] + (n,)
        assert np.array_equal(np.asarray(held),
                              np.asarray(held_full[..., rank * n:(rank + 1) * n]))
        total = total + y.astype(jnp.float32)
    ref = y_full.astype(jnp.float32)
    err = float(jnp.abs(total - ref).max() / jnp.abs(ref).max())
    # each partial is rounded to bf16 once before the sum
    assert err < 2e-2, err


@pytest.mark.parametrize("ep", [1, 2])
def test_dispatch_matches_every_token_through_every_held_expert(ep):
    cfg = replace(get_reduced(ARCH), expert_parallel=ep)
    p = moe_params(cfg)
    x = tokens_in(cfg)
    y, _, held = moe_mod.moe_apply(p, x, cfg)
    want = dense_moe(p, x, cfg)
    got = y.reshape(want.shape).astype(jnp.float32)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-2
    # every token sends top_k assignments, to distinct experts
    assert int(held.max()) <= 1
    if ep == 1:
        assert np.all(np.asarray(held.sum(-1)) == cfg.top_k)


def test_no_token_is_dropped_when_routing_is_skewed_onto_one_expert():
    """A router that sends every token to held expert 0 (and one more):
    each of the 48 tokens still gets expert 0's output, which a capacity
    of a few tokens an expert would have dropped."""
    cfg = replace(get_reduced(ARCH), expert_parallel=2, expert_rank=1)
    p = moe_params(cfg)
    first = cfg.expert_offset
    p["router"] = p["router"].at[:, first].add(50.0)
    x = jnp.abs(tokens_in(cfg))
    y, _, held = moe_mod.moe_apply(p, x, cfg)
    assert np.all(np.asarray(held[..., 0]) == 1)          # all 48 tokens
    want = dense_moe(p, x, cfg)
    got = y.reshape(want.shape).astype(jnp.float32)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-2


def test_gates_are_not_renormalized_where_the_config_says_so():
    cfg = get_reduced(ARCH)
    assert cfg.norm_topk_prob is False and cfg.routed_scaling == 1.0
    p = moe_params(cfg)
    x = tokens_in(cfg)
    y, _, _ = moe_mod.moe_apply(p, x, cfg)
    y_norm, _, _ = moe_mod.moe_apply(p, x, replace(cfg, norm_topk_prob=True))
    assert not np.allclose(np.asarray(y, np.float32),
                           np.asarray(y_norm, np.float32), atol=1e-3)
    # qwen2-moe keeps its renormalized gates
    assert get_config("qwen2-moe-a2.7b").norm_topk_prob is True


def test_yarn_frequencies_and_softmax_scale_follow_the_formulas():
    cfg = get_config(ARCH)
    y = cfg.rope_scaling
    d, theta = cfg.qk_rope_dim, cfg.rope_theta

    def dim(turns):
        return d * math.log(4096 / (2 * math.pi * turns)) / (2 * math.log(1e4))

    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (10, 23)
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = np.asarray(rope_freqs(d, theta, y))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        base[-1] / 40, rel=1e-6)

    from repro.models.mla import softmax_scale
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(mscale)
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert softmax_scale(cfg) / 192 ** -0.5 == pytest.approx(1.5896, abs=1e-4)


def test_published_layout_dense_first_layer_then_moe():
    cfg = get_config(ARCH)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.d_ff) == (27, 1, 10_944)
    assert (cfg.num_experts, cfg.top_k, cfg.num_shared_experts) == (64, 6, 2)
    assert cfg.norm_eps == 1e-6 and cfg.experts_held == 64
    shapes = jax.eval_shape(lambda k: init_stack(k, replace(
        cfg, expert_parallel=8))[0], jax.random.key(0))
    assert shapes["dense_blocks"]["mlp"]["wi"].shape == (1, 2048, 10_944)
    moe = shapes["blocks"]["moe"]
    assert moe["wi"].shape == (26, 8, 2048, 1408)
    assert moe["router"].shape == (26, 2048, 64)
    assert shapes["blocks"]["mla"]["wkv_a"].shape == (26, 2048, 576)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert 3.10e9 < n < 3.12e9


def test_qwen_weights_are_the_recipe_they_were():
    """qwen1.5-0.5b's weights for a seed are those its recipe drew before
    layers could differ in kind: a digest of every leaf (reduced sizes),
    recorded from the earlier recipe."""
    params, _ = init_stack(jax.random.key(7), get_reduced("qwen1.5-0.5b"))
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("a2e9a7b5898fce6dadc386e1c3fe423c"
                             "a9c15201d2d07ac2e1ae06c7b2ebc824")


@pytest.fixture(autouse=True)
def _scratch_compile_cache(tmp_path_factory, monkeypatch):
    # serve.run keeps its compile cache in the checkout unless told
    # otherwise; keep these CPU programs out of it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


def serve_job(argv):
    from repro import trace
    from repro.launch import serve
    out = serve.run(argv)
    return out, trace.jobs()[-1]


def counters(job):
    return {k: v for r in job.spans if r.counts for k, v in r.counts.items()}


def test_serving_job_counts_routing_and_the_latent_cache():
    B, P, G = 3, 16, 6
    out, job = serve_job(["--arch", ARCH, "--reduced", "--batch", str(B),
                          "--prompt-len", str(P), "--gen", str(G),
                          "--expert-parallel", "2"])
    cfg = out["cfg"]
    c = counters(job)
    assert (c["moe.experts_held"], c["moe.experts_routed"]) == (4, 8)
    assert c["mla.cache_bytes"] == cfg.num_layers * B * (P + G) * (
        cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
    moe_layers = cfg.num_layers - cfg.first_k_dense
    for phase, tokens in (("prefill", B * P), ("decode", B * G)):
        n = c[f"moe.assign_held.{phase}"]
        assert 0 < n < moe_layers * tokens * cfg.top_k
    assert 1 <= c["moe.expert_load_max"] <= B


def test_qwen_job_records_no_moe_or_mla_counter():
    _, job = serve_job(["--arch", "qwen1.5-0.5b", "--reduced", "--batch", "2",
                        "--prompt-len", "8", "--gen", "3"])
    assert not [k for k in counters(job) if k.startswith(("moe.", "mla."))]


@pytest.mark.parametrize("arch,ep", [(ARCH, 3), (ARCH, 0),
                                     ("qwen1.5-0.5b", 2)])
def test_expert_parallel_must_divide_the_routed_experts(arch, ep):
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.run(["--arch", arch, "--reduced", "--expert-parallel", str(ep)])


def test_kv_store_and_spill_arena_are_sized_from_the_job(monkeypatch):
    """batch · ceil(gen / page-tokens) pages in the host store; the spill
    arena holds them with replication, and never less than before."""
    from repro import box
    from repro.launch import serve
    seen = {}
    opened = box.open

    def spy(spec):
        seen["heap"] = spec.heap_pages
        session = opened(spec)
        kv_store = session.kv_store

        def sized(num_pages, **kw):
            seen["pages"] = num_pages
            return kv_store(num_pages=num_pages, **kw)

        session.kv_store = sized
        return session

    monkeypatch.setattr(serve.box, "open", spy)
    serve.run(["--arch", "qwen1.5-0.5b", "--reduced", "--batch", "3",
               "--prompt-len", "8", "--gen", "33", "--page-tokens", "16",
               "--spill"])
    assert seen == {"pages": 9, "heap": serve.KV_HEAP_PAGES}
    assert serve.prefill_groups(8, 1024) == 1
    assert serve.prefill_groups(64, 1024) == 8


def test_grouped_prefill_matches_one_pass(monkeypatch):
    from repro.launch import serve
    cfg = replace(get_reduced(ARCH), expert_parallel=2)
    params, _ = init_stack(jax.random.key(2), cfg)
    t = jax.random.randint(jax.random.key(3), (4, 16), 0, cfg.vocab_size)
    one = serve.programs(cfg)[0](params, t)
    monkeypatch.setattr(serve, "PREFILL_TOKENS", 32)      # 2 groups of 2
    assert serve.prefill_groups(4, 16) == 2
    grouped = serve.programs(cfg)[0](params, t)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(grouped)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_serving_step_updates_the_latent_cache_in_place():
    """The leading dense layer and the scanned MoE layers both write
    their rows into the donated cache: the compiled step aliases every
    cache leaf (latent rows and routing counts), and no layer's slab is
    rewritten."""
    import re

    from repro.launch import serve
    from repro.models import init_cache
    cfg = replace(get_reduced(ARCH), expert_parallel=2)
    params, _ = init_stack(jax.random.key(0), cfg)
    B, S = 2, 24
    cache = init_cache(cfg, B, max_len=S)
    lowered = serve.programs(cfg)[1].lower(
        params, cache, jnp.zeros((B,), jnp.int32),
        jnp.full((B,), 16, jnp.int32))
    leaves = jax.tree.leaves(cache)
    for line in lowered.as_text().splitlines():
        m = re.search(r"stablehlo\.dynamic_update_slice .*: \(tensor<(\S+?)>, "
                      r"tensor<(\S+?)>", line)
        if m:
            into = tuple(int(d) for d in m.group(1).split("x")[:-1])
            update = tuple(int(d) for d in m.group(2).split("x")[:-1])
            assert not (any(into == a.shape for a in leaves) and len(update)
                        > 2 and update[2] == S), line.strip()
    compiled = lowered.compile()
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.nbytes for a in leaves)
