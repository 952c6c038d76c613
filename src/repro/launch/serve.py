"""Batched serving driver: prefill → decode, beside a remote KV tier.

The jitted model step decodes against the contiguous stacked cache on
the device (``models.init_cache``), into which the prefill's cache is
spliced. With ``--spill``, the host-side KV tier (``box.KVStore`` over
RDMAbox) takes one random 64-wide row per sequence and step, not the
model's K and V, and every sequence's pages are spilled to the donors
and fetched back at the job's end. Prefill and the decode step are
compiled before the timed windows; the device they ran on is printed
with the rates. Each layer boundary of a job is a span of
``repro.trace``; the job's summary of its spans and counters is printed
at its end.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import collections
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import box, trace
from repro.configs import get_config, get_reduced, replace
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import device_info, make_local_mesh
from repro.models import (decode_step, init_cache, init_stack, prefill,
                          splice_prefill)

# pages reserved per client for the KV spill arena (the heap slice of
# each donor region), at least; the rest of the slice backs background
# paging
KV_HEAP_PAGES = 1024
# the most prompt tokens one prefill pass takes: a larger batch is
# prefilled in groups of whole sequences, which bounds its transients
PREFILL_TOKENS = 8192
# how many steps later than its own a decode step's token is read: one
# keeps the next step queued on the device while the host waits on a
# token, and two measured no faster on a TPU v5e
READ_LAG = 1
# the KV tier's counters (its snapshot) that a job's record takes
KV_COUNTERS = ("rows_appended", "pages_spilled", "bytes_spilled",
               "pages_fetched", "bytes_fetched")


def prefill_groups(batch: int, prompt_len: int) -> int:
    """How many groups of whole sequences a batch is prefilled in: the
    fewest that keep a group within ``PREFILL_TOKENS``."""
    return next((g for g in range(1, batch + 1)
                 if batch % g == 0 and batch // g * prompt_len
                 <= PREFILL_TOKENS), batch)


def programs(cfg) -> Tuple[Callable, Callable, Callable]:
    """A serving job's jitted prefill and decode step, and its greedy
    pick. The prefill runs a batch of more than ``PREFILL_TOKENS`` prompt
    tokens in groups of sequences (``prefill_groups``), one after the
    other, each writing its rows of the logits and the cache. The step
    takes the cache donated (argument 1): it writes each layer's new row
    into it in place and hands it back."""
    def pick(logits, tok):
        # greedy next token; embedding-frontend archs feed their input on
        if cfg.frontend:
            return tok
        return jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)

    def serve_prefill(p, t):
        groups = prefill_groups(*t.shape[:2])
        if groups == 1:
            return prefill(p, t, cfg)
        n = t.shape[0] // groups
        part = jax.eval_shape(lambda x: prefill(p, x, cfg), t[:n])

        def whole(s, axis):        # the batch's buffer of a group's leaf
            return jnp.zeros(s.shape[:axis] + t.shape[:1]
                             + s.shape[axis + 1:], s.dtype)

        def group(i, out):
            logits, cache = prefill(
                p, jax.lax.dynamic_slice_in_dim(t, i * n, n), cfg)
            put = jax.lax.dynamic_update_slice_in_dim
            # the logits are (B, V), cache leaves stacked (L, B, ...)
            return (put(out[0], logits, i * n, 0),
                    jax.tree.map(lambda a, c: put(a, c, i * n, 1),
                                 out[1], cache))

        return jax.lax.fori_loop(
            0, groups, group,
            (whole(part[0], 0), jax.tree.map(lambda s: whole(s, 1), part[1])))

    def serve_step(p, c, t, i):
        logits, c = decode_step(p, c, t, i, cfg)
        return logits, c, pick(logits, t), i + 1

    return (jax.jit(serve_prefill), jax.jit(serve_step, donate_argnums=(1,)),
            pick)


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
    ap.add_argument("--expert-parallel", type=int, default=1, metavar="N",
                    help="chips that divide each MoE layer's routed experts; "
                         "this one holds its 1/N share (rank 0) and adds "
                         "only those experts' outputs")
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
    ep = args.expert_parallel
    if ep < 1 or (ep > 1 and (not cfg.uses_moe or cfg.num_experts % ep)):
        ap.error(f"--expert-parallel {ep} does not divide the "
                 f"{cfg.num_experts} routed experts of {cfg.name}")
    cfg = replace(cfg, expert_parallel=ep)
    mesh = make_local_mesh(1, 1)
    B, S = args.batch, args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    out: Dict = {"device": device, "cfg": cfg}
    print(f"serve arch={cfg.name} on {device['platform']} {device['kind']} "
          f"x{device['count']}", flush=True)

    prefill_jit, step_jit, pick = programs(cfg)

    # the job's spans (repro.trace) time its windows: prefill_s,
    # compile_s and decode_tok_s are read from them
    with trace.job("serve.job") as root, jax.set_mesh(mesh):
        with trace.span("serve.init"):
            params, _ = init_stack(jax.random.key(0), cfg)
            if cfg.frontend:
                prompts = jnp.asarray(
                    rng.normal(size=(B, args.prompt_len, cfg.d_model)),
                    jnp.bfloat16)
                tok = jnp.asarray(rng.normal(size=(B, cfg.d_model)),
                                  jnp.bfloat16)
            else:
                prompts = jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (B, args.prompt_len)),
                    jnp.int32)
                tok = jnp.zeros((B,), jnp.int32)
            cur = jnp.full((B,), args.prompt_len, jnp.int32)
            cache = init_cache(cfg, B, max_len=S)

        # compile outside the timed windows
        with trace.span("serve.compile") as compiling:
            prefill_fn = prefill_jit.lower(params, prompts).compile()
            step_fn = step_jit.lower(params, cache, tok, cur).compile()
            # the input bytes the compiled step hands on to its output:
            # the whole cache where it updates the cache in place
            memory = step_fn.memory_analysis()
            if memory is not None:
                compiling.add("step_alias_bytes", memory.alias_size_in_bytes)
            if cfg.uses_moe:
                compiling.add("moe.experts_held", cfg.experts_held)
                compiling.add("moe.experts_routed", cfg.num_experts)
            if "mla" in cache:
                compiling.add("mla.cache_bytes", sum(
                    a.nbytes for a in jax.tree.leaves(cache["mla"])))
        out["compile_s"] = trace.seconds(compiling)
        print(f"compile prefill+decode: {out['compile_s']:.2f}s", flush=True)

        # prefill gives last-token logits + a prompt-length cache; decode
        # needs a full-length cache: splice the prefill cache in.
        with trace.span("serve.prefill") as prefilling:
            logits, pcache = prefill_fn(params, prompts)
            jax.block_until_ready(pcache)
        out["prefill_s"] = trace.seconds(prefilling)

        with trace.span("serve.splice"):
            if "moe" in pcache:          # read after the decode window
                held_prefill = jnp.sum(pcache["moe"]["assign"])
            cache = splice_prefill(cache, pcache)
            tok = pick(logits, tok)
        out.update(params=params, prompts=prompts, first_token=tok)
        print(f"prefill {args.prompt_len} tokens × {B} seqs in "
              f"{out['prefill_s']:.3f}s", flush=True)

        # host-side paged KV tier mirrors the device cache per sequence
        kv_features = 64
        paged = None
        session = None
        if args.spill:
            # every sequence's decoded rows, in whole pages
            kv_pages = B * -(-args.gen // args.page_tokens)
            with trace.span("box.open"):
                spec = box.ClusterSpec(
                    num_donors=args.donors, donor_pages=1 << 14,
                    replication=args.replication,
                    num_clients=args.clients,
                    heap_pages=min(max(KV_HEAP_PAGES,
                                       kv_pages * args.replication),
                                   (1 << 14) // args.clients // 2),
                    link={"latency_us": args.link_latency_us,
                          "gbps": args.link_gbps},
                    faults=faults)
                session = box.open(spec)
                paged = session.kv_store(num_pages=kv_pages,
                                         page_tokens=args.page_tokens,
                                         kv_features=kv_features)
                for b in range(B):
                    paged.add_sequence(b)

        # a step's token is read READ_LAG steps later, once the next
        # step is dispatched; the last step reads all that are left
        out_tokens = []
        unread: collections.deque = collections.deque()
        reads_ready = 0
        first_logits = None
        with trace.span("serve.decode") as decoding:
            for i in range(args.gen):
                with trace.span("serve.decode.step"):
                    with trace.span("serve.decode.dispatch"):
                        logits, cache, tok, cur = step_fn(params, cache, tok,
                                                          cur)
                        if not cfg.frontend:
                            tok.copy_to_host_async()
                    if i == 0:
                        first_logits = logits
                    if not cfg.frontend:
                        unread.append(tok)
                        keep = READ_LAG if i < args.gen - 1 else 0
                        with trace.span("serve.decode.token_read"):
                            while len(unread) > keep:
                                done = unread.popleft()
                                reads_ready += done.is_ready()
                                out_tokens.append(np.asarray(done))
                    if paged is not None:
                        kv_rows = rng.normal(
                            size=(B, kv_features)).astype(np.float32)
                        for b in range(B):
                            paged.append_tokens(b, kv_rows[b : b + 1])
            jax.block_until_ready(cache)
            if not cfg.frontend:
                decoding.add("decode.reads_ready", reads_ready)
                decoding.add("decode.read_lag", READ_LAG)
        out["decode_tok_s"] = args.gen * B / trace.seconds(decoding)
        if "moe" in cache:
            # the routing the device counted, read once the window is over
            counts = jax.device_get(cache["moe"])
            prefilled = int(held_prefill)
            root.add("moe.assign_held.prefill", prefilled)
            root.add("moe.assign_held.decode",
                     int(counts["assign"].sum()) - prefilled)
            root.add("moe.expert_load_max", int(counts["load_max"].max()))
        if first_logits is not None:
            out["first_logits"] = np.asarray(first_logits, np.float32)
        print(f"decode {args.gen} steps × {B} seqs: "
              f"{out['decode_tok_s']:,.1f} tok/s on {device['platform']} "
              f"{device['kind']}", flush=True)
        if out_tokens:
            arr = np.stack(out_tokens, axis=1)
            print("sample continuation token ids:", arr[0, :16].tolist())
        if paged is not None:
            # extra clients contend for the shared donors while the
            # serving client spills/fetches — the multi-client scenario
            bg_threads = []
            if args.clients > 1:
                import threading

                def bg_pager(idx, n_pages=64):
                    pager = session.pager(idx)
                    # per-thread generator: np.random.Generator is not
                    # thread-safe, and these threads run concurrently
                    r = np.random.default_rng(idx)
                    buf = r.integers(0, 255, 4096).astype(np.uint8)
                    for pid in range(n_pages):
                        pager.swap_out(pid, buf, wait=True)

                bg_threads = [threading.Thread(target=bg_pager, args=(i,))
                              for i in range(1, args.clients)]
                for t in bg_threads:
                    t.start()
            # every sequence's pages round-trip through the donors and
            # must come back byte-for-byte
            with trace.span("serve.spill_check"):
                before = [paged.gather(b).copy() for b in range(B)]
                for b in range(B):
                    paged.spill(b)
                for b in range(B):
                    paged.fetch(b)
                out["spill_exact"] = all(
                    np.array_equal(paged.gather(b).view(np.uint8),
                                   before[b].view(np.uint8))
                    for b in range(B))
            print(f"spill/fetch of {B} sequences byte-exact: "
                  f"{out['spill_exact']}", flush=True)
            for t in bg_threads:
                t.join()
            # the session opened in this job, so its counters are the
            # job's own: the job's record takes them on its root span
            st = session.stats()
            root.add("rdma_ops",
                     st["nic"][str(session.clients[0])]["rdma_ops"])
            root.add("merge_drains", st["client"]["0"]["box"]["merge"]["drains"])
            for key in KV_COUNTERS:
                root.add(f"kv.{key}", st["kv"]["0"][key])
            for donor, served in st["fabric"]["service"].items():
                for client, v in served.items():
                    root.add(f"donor{donor}.client{client}.ops", v["ops"])
            with trace.span("box.close"):
                session.close()
    for name, row in trace.summary(root.job).items():
        print(f"span {name}: " + " ".join(
            f"{k}={v:.6g}" for k, v in row.items()))
    print("SERVING DONE")
    return out


def main() -> None:
    out = run()
    if out.get("spill_exact") is False:
        raise SystemExit("spilled KV pages came back changed")


if __name__ == "__main__":
    main()
