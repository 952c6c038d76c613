"""Decode step's share of the chip's roofline, for a latent-attention MoE
configuration.

The least time the chip could take for the traced decode steps (the
larger of their needed FLOPs over peak FLOP/s and their needed bytes over
peak HBM bandwidth, counted by ``work_mla_moe.decode_step``) over the
time from the first step's start to the last step's end. The held
experts (``moe.experts_held``) and the decode's assignments to them
(``moe.assign_held.decode``, spread evenly over the steps) are the
traced job's counters: the job whose ``serve.prefill`` span, in seconds,
equals ``ctx["work"]["prefill_s"]`` (``spans.traced_job``). Where no job
matches, or the job has no such counters, the metric is None."""

import numpy as np

from benchmarks.chip import spans, work_mla_moe, xplane


def read(ctx):
    steps = xplane.programs(ctx["device0"]["modules"], "serve_step")
    job = spans.traced_job(ctx)
    if not steps or job is None:
        return None
    held = work_mla_moe.job_count(job, "moe.experts_held")
    assign = work_mla_moe.job_count(job, "moe.assign_held.decode")
    if held is None or assign is None:
        return None
    a, cfg, pk = ctx["args"], ctx["config"], ctx["peaks"]
    least = 0.0
    for i in range(len(steps)):
        w = work_mla_moe.decode_step(
            cfg, a["batch"], np.full(a["batch"], a["prompt-len"] + i + 1),
            held, assign / a["gen"])
        least += max(w["flops"] / pk["flops_bf16"],
                     w["bytes"] / pk["hbm_bytes_s"])
    span = (steps[-1][2] - steps[0][1]) / 1e9
    return 100.0 * least / span
