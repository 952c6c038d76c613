#!/usr/bin/env python3
"""Bring-up smoke run of the system's main path on a TPU.

One process, phases in order; the first failure ends the run with a
non-zero exit and no result line:

  (a) device check: JAX must see a TPU. Nothing falls back to the CPU.
  (b) paged decode attention, compiled, at qwen1.5-0.5b widths over a
      pool of thousands of pages, on a contiguous and on a fragmented
      page table, against the pure-jnp reference.
  (c) serving: ``launch/serve.py``'s path at full qwen1.5-0.5b width with
      KV spill over the default 2-donor fabric. The first decode step's
      logits must match the reference forward on the same seeded weights,
      and every spilled KV page must come back byte-exact.
  (d) training: ``launch/train.py``'s path at full rdmabox-paper-100m
      width with optimizer-state offload. Losses must be finite, the first
      near ln(vocab), and the saved checkpoint must restore leaf-exact.

  python chip_smoke.py                # (a)-(d) on one chip
  python chip_smoke.py --four-chips   # (a), then only the ZeRO data-parallel
                                      # train step on 4 chips vs 1 chip

The last line of stdout is ``{"ok": true, "device": {...}}``; a summary
with every timing goes to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"
CKPT_ROOT = ROOT / "checkpoints" / "chip_smoke"   # fresh for every run

SERVE_ARGS = ["--arch", "qwen1.5-0.5b", "--batch", "8", "--prompt-len", "128",
              "--gen", "32", "--spill"]
TRAIN_ARGS = ["--arch", "rdmabox-paper-100m", "--batch", "8", "--seq", "512",
              "--log-every", "1"]
# first-step logits vs the parallel forward: bf16 weights and a different
# contraction order (tests/test_models.py::test_decode_matches_forward)
LOGITS_TOL = 0.05
# an untrained model's loss sits near ln(vocab); 1 nat covers the init's
# logit scale
INIT_LOSS_TOL = 1.0
# data-parallel vs one-chip losses: a few bf16 ulps of a ~10-nat loss
DP_LOSS_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def phase_device(need: int) -> dict:
    """(a) The run must land on TPUs, at least ``need`` of them."""
    from repro.launch.mesh import device_info
    device = device_info()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{device['platform']!r}); refusing to run elsewhere")
    if device["count"] < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU chips, found "
                         f"{device['count']}")
    return device


def _fragmented_and_contiguous(B, Pmax, P, lengths, T, rng):
    import numpy as np
    used = -(-lengths // T)
    contig = -np.ones((B, Pmax), np.int32)
    frag = -np.ones((B, Pmax), np.int32)
    scattered = rng.permutation(P)
    cursor = 0
    for b in range(B):
        contig[b, :used[b]] = np.arange(cursor, cursor + used[b])
        frag[b, :used[b]] = scattered[cursor:cursor + used[b]]
        cursor += used[b]
    return {"contiguous": contig, "fragmented": frag}


def phase_paged_attention(*, B=8, H=16, Kh=16, D=64, T=16, R=4, P=4096,
                          Pmax=256, interpret=False, repeats=20,
                          seed=0) -> dict:
    """(b) The compiled kernel against the reference, both page tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.ops import (
        descriptor_stats, paged_attention, paged_attention_blocks,
        plan_blocks)
    from repro.kernels.paged_attention.ref import paged_attention_ref

    rng = np.random.default_rng(seed)
    kq, kk = jax.random.split(jax.random.key(seed))
    # q scaled so that softmax is peaked and the output is O(1): a flat
    # softmax averages to ~0 and would hide errors under any tolerance
    q = 3.0 * jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    kv = jax.random.normal(kk, (P, T, 2, Kh, D), jnp.bfloat16)
    lengths_np = rng.integers(Pmax * T // 2, Pmax * T + 1, B).astype(np.int32)
    lengths = jnp.asarray(lengths_np)
    kv_slack = jnp.pad(kv, [(0, R - 1)] + [(0, 0)] * 4)  # R−1 slack pages
    ref_fn = jax.jit(paged_attention_ref)
    result = {"pool_pages": P, "batch": B, "pool_bytes": int(kv.nbytes)}
    tables = _fragmented_and_contiguous(B, Pmax, P, lengths_np, T, rng)
    for name, table in tables.items():
        starts, valid = (jnp.asarray(a) for a in plan_blocks(table, R))
        t0 = time.perf_counter()
        compiled = paged_attention_blocks.lower(
            q, kv_slack, starts, valid, lengths, pages_per_block=R,
            interpret=interpret).compile()
        compile_s = time.perf_counter() - t0
        if not interpret:
            check("tpu_custom_call" in compiled.as_text(),
                  f"paged attention ({name}) HLO holds no tpu_custom_call")
        out = compiled(q, kv_slack, starts, valid, lengths)
        out.block_until_ready()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            compiled(q, kv_slack, starts, valid, lengths).block_until_ready()
            times.append(time.perf_counter() - t0)
        steady_us = float(np.median(times) * 1e6)
        # the user-facing entry point plans and pads itself
        via_api = paged_attention(q, kv, table, lengths, pages_per_block=R,
                                  interpret=interpret)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_fn(q, kv, jnp.asarray(table), lengths),
                             np.float32)
        got = np.asarray(out, np.float32)
        rel = float(np.abs(got - ref).max() / np.abs(ref).max())
        stats = descriptor_stats(table, R)
        print(f"paged attention [{name}] compile {compile_s:.2f}s", flush=True)
        print(f"paged attention [{name}] steady {steady_us:.1f} us/call "
              f"(median of {repeats}), {stats['descriptors']} DMA descriptors "
              f"for {stats['pages']} pages, max rel err {rel:.2e}", flush=True)
        check(rel < 2e-2, f"paged attention ({name}) off the reference: "
                          f"max rel err {rel:.3e}")
        check(np.array_equal(np.asarray(via_api, np.float32), got),
              f"paged_attention() ({name}) differs from the planned call")
        result[name] = {"compile_s": compile_s, "steady_us": steady_us,
                        "max_rel_err": rel, **stats}
    return result


def phase_serving(args=SERVE_ARGS) -> dict:
    """(c) serve.py's path; first decode step vs the reference forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import forward

    out = serve.run(list(args))
    cfg = out["cfg"]
    tokens = jnp.concatenate([out["prompts"], out["first_token"][:, None]], 1)
    ref = jax.jit(lambda p, t: forward(p, t, cfg)[0][:, -1])(
        out["params"], tokens)
    ref = np.asarray(ref, np.float32)
    rel = float(np.abs(ref - out["first_logits"]).max()
                / max(np.abs(ref).max(), 1.0))
    print(f"serving: first decode step vs forward, max rel err {rel:.2e}",
          flush=True)
    check(np.isfinite(out["first_logits"]).all(), "decode logits not finite")
    check(rel < LOGITS_TOL, f"decode diverges from forward: {rel:.3e}")
    check(out["spill_exact"] is True, "spilled KV pages came back changed")
    return {"arch": cfg.name, "compile_s": out["compile_s"],
            "prefill_s": out["prefill_s"],
            "decode_tok_s": out["decode_tok_s"], "logits_rel_err": rel,
            "spill_exact": out["spill_exact"]}


def _fresh(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    return path


def phase_training(args=TRAIN_ARGS, steps=5) -> dict:
    """(d) train.py's path with offload; checkpoint round trip."""
    import jax
    import numpy as np

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch import train

    ckpt_dir = _fresh(CKPT_ROOT / "train")
    out = train.run(list(args) + ["--steps", str(steps), "--offload",
                                  "--ckpt-dir", str(ckpt_dir),
                                  "--ckpt-every", "3"])
    losses = out["losses"]
    ln_v = math.log(out["cfg"].vocab_size)
    print(f"training: losses {losses}", flush=True)
    check(len(losses) == steps, f"took {len(losses)} steps, not {steps}")
    check(all(math.isfinite(x) for x in losses), "loss not finite")
    check(abs(losses[0] - ln_v) < INIT_LOSS_TOL,
          f"first loss {losses[0]:.3f} far from ln(vocab) = {ln_v:.3f}")
    state = (out["params"], out["opt_state"])
    ckpt = Checkpointer(str(ckpt_dir))
    check(ckpt.steps()[-1] == steps, f"no checkpoint at step {steps}")
    back, _ = ckpt.restore(steps, state, out["shardings"])
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "restored checkpoint differs from the trained state")
    print(f"training: checkpoint step {steps} restored leaf-exact "
          f"({len(jax.tree.leaves(state))} leaves)", flush=True)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {"arch": out["cfg"].name, "losses": losses,
            "compile_s": out["compile_s"], "step_s": out["step_s"],
            "tok_s": out["tok_s"]}


def phase_four_chips(args=TRAIN_ARGS, steps=3, chips=4) -> dict:
    """ZeRO data-parallel train step on ``chips`` devices against the same
    seed and global batch on the first device alone."""
    import jax
    import numpy as np

    from repro.launch import train

    def train_on(data: int):
        return train.run(list(args) + [
            "--steps", str(steps), "--data", str(data),
            "--ckpt-dir", str(_fresh(CKPT_ROOT / f"data{data}")),
            "--ckpt-every", str(steps + 1)])

    one = train_on(1)
    ref_losses, one_step_s = one["losses"], one["step_s"]
    del one                                  # free the one-chip state
    out = train_on(chips)
    moments = jax.tree.leaves(out["opt_state"].m)
    used = set().union(*(leaf.sharding.device_set for leaf in moments))
    split = sum(leaf.addressable_shards[0].data.shape != leaf.shape
                for leaf in moments)
    print(f"four chips: moments on {len(used)} devices, {split}/"
          f"{len(moments)} leaves partitioned", flush=True)
    print(f"four chips: losses {out['losses']} vs one chip {ref_losses}",
          flush=True)
    check(len(used) == chips, f"moments span {len(used)} devices, not {chips}")
    check(split > 0, "no moment leaf is partitioned across the data axis")
    check(np.allclose(out["losses"], ref_losses, rtol=DP_LOSS_RTOL, atol=0),
          "data-parallel losses differ from the one-chip run")
    for c in (CKPT_ROOT / "data1", CKPT_ROOT / f"data{chips}"):
        shutil.rmtree(c, ignore_errors=True)
    return {"chips": chips, "losses": out["losses"],
            "one_chip_losses": ref_losses, "moment_devices": len(used),
            "partitioned_leaves": split, "compile_s": out["compile_s"],
            "step_s": out["step_s"], "one_chip_step_s": one_step_s}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel training check")
    opts = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    device = phase_device(need=4 if opts.four_chips else 1)
    summary = {"device": device}
    if opts.four_chips:
        summary["four_chips"] = phase_four_chips()
    else:
        summary["paged_attention"] = phase_paged_attention()
        summary["serving"] = phase_serving()
        summary["training"] = phase_training()
    OUT.mkdir(exist_ok=True)
    name = "chip_smoke_4.json" if opts.four_chips else "chip_smoke.json"
    (OUT / name).write_text(json.dumps(summary, indent=1))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
