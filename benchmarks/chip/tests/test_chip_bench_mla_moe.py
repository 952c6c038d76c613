"""The latent-attention MoE cell (DeepSeek-V2-Lite), rehearsed on the CPU
at reduced sizes: its configuration file, the plain reference against
the program, the serve entry's check, the work counts and the two
per-layer metrics that read the program's routing counters."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, spec

from benchmarks.chip import faults, harness, peaks, work_mla_moe
from benchmarks.chip.entries.common import _SIZES, config_mismatch

CELL = "deepseek-v2-lite-16b.serve-spill-1k-b64"
CONFIG = ROOT / "benchmarks/chip/configs/deepseek-v2-lite-16b.json"
# the program's --reduced model config, in the configuration file's keys
REDUCED = {"num_hidden_layers": 3, "hidden_size": 128, "vocab_size": 512,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "kv_lora_rank": 32, "qk_rope_head_dim": 16,
           "qk_nope_head_dim": 32, "v_head_dim": 32, "head_dim": 48,
           "intermediate_size": 256, "moe_intermediate_size": 64,
           "router_outputs": 8, "num_experts_per_tok": 2,
           "n_shared_experts": 1}
ARGS = {"reduced": True, "batch": 4, "prompt-len": 24, "gen": 12}


def config(ep=2):
    c = json.loads(CONFIG.read_text())
    c.update(REDUCED, n_routed_experts=8 // ep,
             deployment=dict(c["deployment"], expert_parallel=ep))
    return c


def cell(ep=2):
    w = harness.load_json(harness.HERE / "workloads" / f"{CELL}.json")
    w["args"].update(ARGS, **{"expert-parallel": ep})
    return harness.Cell(spec(), CELL, cell=w, config=config(ep))


def program_config(ep):
    from repro.configs import get_reduced, replace
    return replace(get_reduced("deepseek-v2-lite-16b"), expert_parallel=ep)


def test_configuration_file_states_the_model_and_its_cut():
    from repro.configs import get_config

    data = json.loads(CONFIG.read_text())
    # the keys the check's config_mismatch and the reference read
    for key in list(_SIZES.values()) + [
            "first_k_dense_replace", "moe_intermediate_size", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_scaling", "router_outputs", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
            "routed_scaling_factor", "torch_dtype", "init", "deployment"]:
        assert key in data, key
    assert config_mismatch(get_config(data["arch"]), data) == []
    assert data["reduced"] == ["n_routed_experts"]
    assert data["published"] == {"n_routed_experts": 64}
    ep = data["deployment"]["expert_parallel"]
    assert (ep, data["deployment"]["rank"]) == (8, 0)
    assert data["n_routed_experts"] * ep == data["router_outputs"] == 64
    # depth and vocabulary uncut
    assert (data["num_hidden_layers"], data["vocab_size"]) == (27, 102_400)
    args = harness.load_json(harness.HERE / "workloads" / f"{CELL}.json")
    assert args["args"]["expert-parallel"] == ep
    assert args["args"]["batch"] == 64


@pytest.mark.parametrize("ep", [1, 2])
def test_reference_weights_are_the_programs(ep):
    """The reference draws from the seed the weights the program holds,
    the held experts' share included, without taking them from it."""
    from repro.models import init_stack

    seed = 2**31 + 11
    params, _ = init_stack(jax.random.key(seed), program_config(ep))
    w = cell(ep).reference.init_weights(config(ep), jax.random.key(seed))
    pairs = [(params["embed"], w["embed"]), (params["unembed"], w["unembed"]),
             (params["final_norm"], w["final_norm"])]
    for blocks, layers in ((params["dense_blocks"], w["dense"]),
                           (params["blocks"], w["layers"])):
        mla = blocks["mla"]
        pairs += [(mla[k], layers[k]) for k in
                  ("wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo")]
        pairs += [(blocks["norm_mixer"], layers["norm_attn"]),
                  (blocks["norm_ffn"], layers["norm_ffn"])]
    mlp, moe = params["dense_blocks"]["mlp"], params["blocks"]["moe"]
    lw = w["layers"]
    pairs += [(mlp["wi"], w["dense"]["wi"]), (mlp["wg"], w["dense"]["wg"]),
              (mlp["wo"], w["dense"]["wf"]), (moe["router"], lw["router"]),
              (moe["wi"], lw["wi"]), (moe["wg"], lw["wg"]),
              (moe["wo"], lw["wf"]), (moe["shared_wi"], lw["shared_wi"]),
              (moe["shared_wg"], lw["shared_wg"]),
              (moe["shared_wo"], lw["shared_wf"])]
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))


# Logits error of the program (bf16 weights, activations and latent
# cache, sums in float32) against the float32 reference: max abs
# difference over max abs reference logit. The program reads ~0.01 at
# these sizes; the reference with int8-rounded operands reads 3-4x that,
# over 0.03, so 0.025 passes bf16 rounding and fails the next precision
# down. A top-k near tie that bf16 reorders would show as a jump well
# past it.
LOGITS_TOL = 0.025


def rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("ep", [1, 2])
def test_prefill_then_decode_through_the_cache_matches_the_reference(ep):
    """Prefill's last logits, then 8 decode steps teacher-forced through
    the latent cache, each against the reference's full forward pass
    given the same expert share."""
    from repro.launch import serve
    from repro.models import init_cache, init_stack

    cfg, c = program_config(ep), config(ep)
    ref = cell(ep).reference
    seed, B, P, G = 17, 2, 20, 9
    params, _ = init_stack(jax.random.key(seed), cfg)
    w = ref.init_weights(c, jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(1), (B, P + G), 0,
                                cfg.vocab_size)
    want = ref.logits(w, ref.hidden(w, tokens, c), c)          # (B, P+G, V)
    control = ref.logits(w, ref.hidden(w, tokens, c, "int8"), c, "int8")

    prefill, step, _ = serve.programs(cfg)
    logits, pcache = prefill(params, tokens[:, :P])
    cache = jax.tree.map(
        lambda full, part: full.at[:, :, :part.shape[2]].set(part)
        if full.shape != part.shape else part,
        init_cache(cfg, B, P + G), pcache)
    got = [logits[:, :cfg.vocab_size]]
    cur = jnp.full((B,), P, jnp.int32)
    for i in range(G - 1):
        logits, cache, _, cur = step(params, cache, tokens[:, P + i], cur)
        got.append(logits[:, :cfg.vocab_size])
    for i, g in enumerate(got):
        z = want[:, P - 1 + i]
        assert rel_err(g, z) < LOGITS_TOL, (i, rel_err(g, z))
    assert max(rel_err(control[:, P - 1 + i], want[:, P - 1 + i])
               for i in range(G)) > LOGITS_TOL


@pytest.fixture(scope="module")
def rehearsal():
    return cell(2)


def test_check_reads_correct_at_a_tiny_size(rehearsal):
    r = harness.measure(rehearsal, 2**31 + 11, 0.0, False)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["spill_mismatch"]["value"] == 0
    assert r["checks"]["config_mismatch"]["value"] == 0
    assert r["metrics"] == {} and r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["serve"]))
def test_planted_fault_fails_the_check(rehearsal, fault):
    r = harness.measure(rehearsal, 5, 0.0, False, warmup=False, fault=fault)
    assert r["correct"] is False, (fault, r["checks"])


def test_int8_control_reads_well_above_the_program(rehearsal):
    def read(**kw):
        r = harness.measure(rehearsal, 6, 0.0, False, warmup=False, **kw)
        return {k: v["value"] for k, v in r["checks"].items()}

    program, control = read(), read(quant="int8")
    assert control["decode_logits_err"] >= 2 * program["decode_logits_err"]


def test_work_of_one_layer_by_hand():
    c = json.loads(CONFIG.read_text())
    # wq 2048 x 16*192, wkv_a 2048 x 576, wk_b/wv_b 512 x 16*128 each,
    # wo 16*128 x 2048
    attn = (2048 * 3072 + 2048 * 576 + 2 * 512 * 2048 + 2048 * 2048)
    assert work_mla_moe.attention_params(c) == attn == 13_762_560
    assert work_mla_moe.expert_params(c) == 3 * 2048 * 1408
    one = dict(c, num_hidden_layers=1, first_k_dense_replace=0,
               vocab_size=0)
    # one MoE layer: attention, 2 shared experts, the 64-wide router
    shared, router = 2 * 8_650_752, 2048 * 64
    assert work_mla_moe.matmul_params(one) == attn + shared + router
    norms = 2 * 2048 + 512 + 2048
    assert work_mla_moe.weight_bytes(one, 8) == (
        (attn + shared + 8 * 8_650_752 + norms) * 2 + router * 4)
    # a decode step of 2 sequences holding 9 and 10 positions (the new
    # one included), 5 assignments to held experts
    w = work_mla_moe.decode_step(one, 2, np.array([10, 11]), 8, 5)
    assert w["flops"] == (2 * (attn + shared + router) * 2
                          + 2 * 16 * (2 * 512 + 64) * 21
                          + 2 * 8_650_752 * 5)
    assert w["bytes"] == (work_mla_moe.weight_bytes(one, 8)
                          + 576 * 2 * (21 - 2) + 576 * 2 * 2
                          + 2 * 2048 * 2)
    # prefill of 2 prompts of 4 tokens, 7 assignments: expanded heads,
    # causal pairs 4 * 5 / 2 per sequence
    p = work_mla_moe.prefill(one, 2, 4, 8, 7)
    assert p["flops"] == (2 * (attn + shared + router) * 8
                          + 2 * 2 * 16 * (128 + 64 + 128) * 10
                          + 2 * 8_650_752 * 7)
    assert p["bytes"] == (work_mla_moe.weight_bytes(one, 8)
                          + 576 * 2 * 8 + 8 * 2048 * 2)


def test_full_size_weights_read_a_decode_step():
    """About 5.8 GB of weights a step: 26 layers of 8 held experts, the
    dense layer, attention, shared experts and the unembedding."""
    c = json.loads(CONFIG.read_text())
    assert 5.7e9 < work_mla_moe.weight_bytes(c, 8) < 6.3e9


def reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py",
                               f"bench_metric_{name.replace('.', '_')}")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One job of the cell (reduced) and one of qwen's, as the traced
    jobs their readers would find, with two made-up decode programs."""
    from conftest import SERVE, reduced_cell

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        for name, c in (("mla_moe", cell(2)), ("qwen", reduced_cell(SERVE))):
            o, wall = harness.run_job(c.program(), harness.job_argv(c.args))
            out[name] = {
                "work": dict(c.entry.work(o, c.args), wall_s=wall),
                "config": c.config, "args": c.args,
                "peaks": peaks.peaks_for("TPU v5 lite"),
                "device0": {"ops": [], "modules": [
                    ("jit_serve_step(1)", 0, 1_000_000),
                    ("jit_serve_step(1)", 1_500_000, 2_500_000)]}}
    return out


def test_mfu_readers_read_the_routing_counters(traced):
    from repro import trace

    ctx = traced["mla_moe"]
    job = next(j for j in reversed(trace.jobs())
               if j.named("serve.prefill")
               and trace.seconds(j.named("serve.prefill")[0])
               == ctx["work"]["prefill_s"])
    held = work_mla_moe.job_count(job, "moe.experts_held")
    assign = work_mla_moe.job_count(job, "moe.assign_held.decode")
    assert held == 4 and assign > 0
    a, pk = ctx["args"], ctx["peaks"]
    least = sum(max(w["flops"] / pk["flops_bf16"],
                    w["bytes"] / pk["hbm_bytes_s"])
                for w in (work_mla_moe.decode_step(
                    ctx["config"], a["batch"],
                    np.full(a["batch"], a["prompt-len"] + i + 1), held,
                    assign / a["gen"]) for i in range(2)))
    assert reader("decode.mla_moe.mfu_pct").read(ctx) == pytest.approx(
        100 * least / 2.5e-3)
    assert reader("prefill.mla_moe.mfu_pct").read(ctx) > 0


@pytest.mark.parametrize("name", ["decode.mla_moe.mfu_pct",
                                  "prefill.mla_moe.mfu_pct"])
def test_mfu_readers_give_none_without_the_counters(traced, name):
    """A job without routing counters, as qwen's, or no job at all."""
    ctx = traced["qwen"]
    assert reader(name).read(ctx) is None
    ctx = dict(traced["mla_moe"], work=dict(traced["mla_moe"]["work"],
                                            prefill_s=-1.0))
    assert reader(name).read(ctx) is None
