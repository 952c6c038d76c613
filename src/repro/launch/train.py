"""End-to-end training driver.

Full substrate in one loop: sharded train step (pjit), deterministic data
pipeline, AdamW with ZeRO-sharded moments, async crash-safe checkpointing
with resume-from-latest, and (optionally) RDMAbox remote offload of the
checkpoint stream — the paper's remote paging system carrying real
training state. The step is compiled before the timed window; the device
it ran on is printed with the rates.

  PYTHONPATH=src python -m repro.launch.train --arch rdmabox-paper-100m \
      --steps 200 --batch 8 --seq 512 --reduced
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from repro import box
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import RunConfig, get_config, get_reduced
from repro.core.descriptors import PAGE_SIZE
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import device_info, make_local_mesh
from repro.launch.steps import build_train_step
from repro.models import init_stack
from repro.optim import adamw

OFFLOAD_DONORS = 3
OFFLOAD_REPLICATION = 2


def offload_spec(tree) -> box.ClusterSpec:
    """A donor fabric whose paging capacity holds ``tree``'s pages."""
    pages = sum(-(-leaf.nbytes // PAGE_SIZE) for leaf in jax.tree.leaves(tree))
    per_donor = -(-pages // OFFLOAD_DONORS) + 1024   # + stripe rounding slack
    return box.ClusterSpec(
        num_donors=OFFLOAD_DONORS, replication=OFFLOAD_REPLICATION,
        donor_pages=max(1 << 16, OFFLOAD_REPLICATION * per_donor))


def run(argv: Optional[Sequence[str]] = None) -> Dict:
    """Train; returns the device, per-step losses, timings, the final
    state with its shardings and the checkpoint directory."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rdmabox-paper-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="checkpoints",
                    help="resumes from the latest checkpoint found here")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--offload", action="store_true",
                    help="stream checkpoints through the RDMAbox engine")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    use_compile_cache()
    device = device_info()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    run_cfg = RunConfig(learning_rate=args.lr, total_steps=args.steps,
                        warmup_steps=max(10, args.steps // 10),
                        remat=args.remat,
                        grad_compression=args.grad_compression,
                        checkpoint_dir=args.ckpt_dir,
                        checkpoint_every=args.ckpt_every)
    mesh = make_local_mesh(args.data, args.model)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} on {device['platform']} "
          f"{device['kind']} x{device['count']}", flush=True)

    with jax.set_mesh(mesh):
        jitted, _, (p_shard, o_shard) = build_train_step(cfg, run_cfg, mesh)
        params, _ = init_stack(jax.random.key(run_cfg.seed), cfg)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(adamw.init(params, run_cfg), o_shard)

        ckpt = Checkpointer(run_cfg.checkpoint_dir,
                            keep=run_cfg.keep_checkpoints)
        start_step = 0
        restored = ckpt.restore_latest((params, opt_state),
                                       (p_shard, o_shard))
        if restored is not None:
            start_step, (params, opt_state), extra = restored
            print(f"resumed from step {start_step}")

        offload_mgr = None
        session = None
        if args.offload:
            session = box.open(offload_spec(opt_state.m))
            offload_mgr = session.tensors()

        data = SyntheticTokens(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            global_batch=args.batch, seed=run_cfg.seed))

        t0 = time.perf_counter()
        step_fn = jitted.lower(params, opt_state,
                               data.batch_at(start_step)).compile()
        compile_s = time.perf_counter() - t0
        print(f"compile train step: {compile_s:.2f}s", flush=True)

        losses = []
        step_times = []
        for step in range(start_step, args.steps):
            t0 = time.perf_counter()
            batch = data.batch_at(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])            # waits for the step
            step_times.append(time.perf_counter() - t0)
            losses.append(loss)
            if (step + 1) % args.log_every == 0 or step == start_step:
                print(f"step {step+1:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"step {step_times[-1]*1e3:.1f}ms", flush=True)
            if not np.isfinite(loss):
                raise FloatingPointError(f"loss diverged at step {step+1}")
            if ((step + 1) % run_cfg.checkpoint_every == 0
                    and step + 1 < args.steps):
                ckpt.save(step + 1, (params, opt_state),
                          extra={"data_step": step + 1}, blocking=False)
                if offload_mgr is not None:
                    offload_mgr.offload_tree("opt_m", opt_state.m, wait=False)
        ckpt.wait()
        ckpt.save(args.steps, (params, opt_state),
                  extra={"data_step": args.steps})
        # steady state leaves out the first step (first-call transfers)
        steady = step_times[1:] or step_times
        step_s = sum(steady) / max(len(steady), 1)
        tok_s = args.batch * args.seq / step_s if step_s else 0.0
        print(f"steady step {step_s*1e3:.1f}ms ({tok_s:,.0f} tok/s) over "
              f"{len(steady)} steps on {device['platform']} {device['kind']} "
              f"x{device['count']}", flush=True)
        if offload_mgr is not None:
            offload_mgr.flush()
            st = session.stats()
            nic = st["nic"][str(session.clients[0])]
            merge = st["client"]["0"]["box"]["merge"]
            print(f"offload: {nic['rdma_ops']} RDMA ops, "
                  f"{nic['bytes_on_wire']/1e6:.1f} MB on wire, "
                  f"merge drains {merge['drains']} for "
                  f"{merge['submitted']} requests")
            session.close()
        print("TRAINING DONE")
    return {"device": device, "cfg": cfg, "losses": losses,
            "compile_s": compile_s,
            "step_s": step_s, "tok_s": tok_s, "params": params,
            "opt_state": opt_state, "shardings": (p_shard, o_shard),
            "ckpt_dir": args.ckpt_dir, "final_step": args.steps}


def main() -> None:
    run()


if __name__ == "__main__":
    main()
