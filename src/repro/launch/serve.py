"""Batched serving driver: prefill → decode against the paged KV tier.

Runs the full serving path: contiguous-cache decode for the jitted model
step, while the host-side PagedKVCache (+ RDMAbox remote spill) manages
per-sequence KV pages with run-coalesced gathers — the paper's node-level
abstraction serving an LLM. Prefill and the decode step are compiled
before the timed windows; the device they ran on is printed with the
rates.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import box
from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import device_info, make_local_mesh
from repro.models import decode_step, init_cache, init_stack, prefill

# pages reserved per client for the KV spill arena (the heap slice of
# each donor region); the rest of the slice backs background paging
KV_HEAP_PAGES = 1024


def run(argv: Optional[Sequence[str]] = None) -> Dict:
    """Serve one batch; returns timings, the device, the first decode
    step's logits and what the reference check needs (params, prompts,
    the first generated token, the config)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--spill", action="store_true",
                    help="spill finished sequences' KV to remote memory")
    # fabric topology + degraded-mode scenario surface
    ap.add_argument("--donors", type=int, default=2,
                    help="donor nodes in the remote-memory fabric")
    ap.add_argument("--clients", type=int, default=1,
                    help="client endpoints sharing the donor fabric; "
                         "extra clients run a background paging workload "
                         "contending with the serving client")
    ap.add_argument("--replication", type=int, default=2)
    ap.add_argument("--link-latency-us", type=float, default=1.0,
                    help="per-link propagation delay (virtual us)")
    ap.add_argument("--link-gbps", type=float, default=None,
                    help="per-link bandwidth cap (default: NIC port only)")
    ap.add_argument("--straggler", type=str, default=None, metavar="NODE:X",
                    help="make donor NODE a straggler with latency xX")
    args = ap.parse_args(argv)

    fabric_flags = (args.straggler is not None or args.link_gbps is not None
                    or args.link_latency_us != 1.0 or args.donors != 2
                    or args.replication != 2 or args.clients != 1)
    if fabric_flags and not args.spill:
        ap.error("fabric flags (--donors/--clients/--replication/--link-*/"
                 "--straggler) only take effect with --spill")
    faults = None
    if args.straggler:
        try:
            node, factor = args.straggler.split(":")
            faults = [{"kind": "slow", "node": int(node),
                       "factor": float(factor)}]
        except ValueError:
            ap.error(f"--straggler expects NODE:FACTOR (e.g. 1:30), "
                     f"got {args.straggler!r}")

    use_compile_cache()
    device = device_info()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh(1, 1)
    B, S = args.batch, args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    out: Dict = {"device": device, "cfg": cfg}
    print(f"serve arch={cfg.name} on {device['platform']} {device['kind']} "
          f"x{device['count']}", flush=True)

    def pick(logits, tok):
        # greedy next token; embedding-frontend archs feed their input on
        if cfg.frontend:
            return tok
        return jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)

    def serve_step(p, c, t, i):
        logits, c = decode_step(p, c, t, i, cfg)
        return logits, c, pick(logits, t), i + 1

    with jax.set_mesh(mesh):
        params, _ = init_stack(jax.random.key(0), cfg)
        if cfg.frontend:
            prompts = jnp.asarray(
                rng.normal(size=(B, args.prompt_len, cfg.d_model)), jnp.bfloat16)
            tok = jnp.asarray(rng.normal(size=(B, cfg.d_model)), jnp.bfloat16)
        else:
            prompts = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)
            tok = jnp.zeros((B,), jnp.int32)
        cur = jnp.full((B,), args.prompt_len, jnp.int32)
        cache = init_cache(cfg, B, max_len=S)

        # compile outside the timed windows
        t0 = time.perf_counter()
        prefill_fn = jax.jit(lambda p, t: prefill(p, t, cfg)).lower(
            params, prompts).compile()
        step_fn = jax.jit(serve_step).lower(params, cache, tok, cur).compile()
        out["compile_s"] = time.perf_counter() - t0
        print(f"compile prefill+decode: {out['compile_s']:.2f}s", flush=True)

        # prefill gives last-token logits + a prompt-length cache; decode
        # needs a full-length cache: splice the prefill cache in.
        t0 = time.perf_counter()
        logits, pcache = prefill_fn(params, prompts)
        jax.block_until_ready(pcache)
        out["prefill_s"] = time.perf_counter() - t0

        def splice_leaf(full, part):
            # cache leaves are stacked (L, B, ...); match on trailing dims
            if full.shape == part.shape:
                return part.astype(full.dtype)
            if full.ndim >= 3 and part.ndim == full.ndim and \
                    part.shape[2] <= full.shape[2]:
                return full.at[:, :, :part.shape[2]].set(part.astype(full.dtype))
            return part.astype(full.dtype)

        cache = jax.tree.map(splice_leaf, cache, pcache)
        tok = pick(logits, tok)
        out.update(params=params, prompts=prompts, first_token=tok)
        print(f"prefill {args.prompt_len} tokens × {B} seqs in "
              f"{out['prefill_s']:.3f}s", flush=True)

        # host-side paged KV tier mirrors the device cache per sequence
        kv_features = 64
        paged = None
        session = None
        if args.spill:
            spec = box.ClusterSpec(
                num_donors=args.donors, donor_pages=1 << 14,
                replication=args.replication,
                num_clients=args.clients,
                heap_pages=min(KV_HEAP_PAGES,
                               (1 << 14) // args.clients // 2),
                link={"latency_us": args.link_latency_us,
                      "gbps": args.link_gbps},
                faults=faults)
            session = box.open(spec)
            paged = session.kv_store(num_pages=256,
                                     page_tokens=args.page_tokens,
                                     kv_features=kv_features)
            for b in range(B):
                paged.add_sequence(b)

        out_tokens = []
        first_logits = None
        t0 = time.perf_counter()
        for i in range(args.gen):
            logits, cache, tok, cur = step_fn(params, cache, tok, cur)
            if i == 0:
                first_logits = logits
            if not cfg.frontend:
                out_tokens.append(np.asarray(tok))
            if paged is not None:
                kv_rows = rng.normal(size=(B, kv_features)).astype(np.float32)
                for b in range(B):
                    paged.append_tokens(b, kv_rows[b : b + 1])
        jax.block_until_ready(cache)
        dt = time.perf_counter() - t0
        out["decode_tok_s"] = args.gen * B / dt
        if first_logits is not None:
            out["first_logits"] = np.asarray(first_logits, np.float32)
        print(f"decode {args.gen} steps × {B} seqs: "
              f"{out['decode_tok_s']:,.1f} tok/s on {device['platform']} "
              f"{device['kind']}", flush=True)
        if out_tokens:
            arr = np.stack(out_tokens, axis=1)
            print("sample continuation token ids:", arr[0, :16].tolist())
        if paged is not None:
            from repro.kernels.paged_attention.ops import descriptor_stats
            Pmax = max(len(v) for v in paged.tables.values())
            table = -np.ones((B, Pmax), np.int32)
            for b in range(B):
                table[b, : len(paged.tables[b])] = paged.tables[b]
            print("page-run coalescing:", descriptor_stats(table, 4))
            # extra clients contend for the shared donors while the
            # serving client spills/fetches — the multi-client scenario
            bg_threads = []
            bg_rates = {}
            if args.clients > 1:
                import threading

                def bg_pager(idx, n_pages=64):
                    pager = session.pager(idx)
                    # per-thread generator: np.random.Generator is not
                    # thread-safe, and these threads run concurrently
                    r = np.random.default_rng(idx)
                    buf = r.integers(0, 255, 4096).astype(np.uint8)
                    t0 = time.perf_counter()
                    for pid in range(n_pages):
                        pager.swap_out(pid, buf, wait=True)
                    bg_rates[idx] = n_pages / (time.perf_counter() - t0)

                bg_threads = [threading.Thread(target=bg_pager, args=(i,))
                              for i in range(1, args.clients)]
                for t in bg_threads:
                    t.start()
            # every sequence's pages round-trip through the donors and
            # must come back byte-for-byte
            before = [paged.gather(b).copy() for b in range(B)]
            for b in range(B):
                paged.spill(b)
            for b in range(B):
                paged.fetch(b)
            out["spill_exact"] = all(
                np.array_equal(paged.gather(b).view(np.uint8),
                               before[b].view(np.uint8)) for b in range(B))
            print(f"spill/fetch of {B} sequences byte-exact: "
                  f"{out['spill_exact']}", flush=True)
            for t in bg_threads:
                t.join()
            st = session.stats()
            serving_nic = st["nic"][str(session.clients[0])]
            merge = st["client"]["0"]["box"]["merge"]
            print(f"spill/fetch: {serving_nic['rdma_ops']} RDMA ops, "
                  f"merge drains {merge['drains']}")
            if bg_rates:
                print("background clients (pages/s under contention):",
                      {session.clients[i]: f"{r:,.0f}"
                       for i, r in sorted(bg_rates.items())})
                print("donor-side per-client service:",
                      st["fabric"]["service"])
            session.close()
        print("SERVING DONE")
    return out


def main() -> None:
    out = run()
    if out.get("spill_exact") is False:
        raise SystemExit("spilled KV pages came back changed")


if __name__ == "__main__":
    main()
