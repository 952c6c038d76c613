"""Prefill's share of the chip's roofline, for a latent-attention MoE
configuration: the needed prefill work (``work_mla_moe.prefill``: every
layer for every prompt token, causal attention with keys and values
expanded per head, routed FLOPs from the assignments to held experts,
the unembedding of the last position only), at peak, over the prefill
time that ``serve.run`` returns for the traced job (its ``serve.prefill``
span). The held experts (``moe.experts_held``) and the prefill's
assignments (``moe.assign_held.prefill``) are that job's counters; where
the job is not found (``spans.traced_job``) or lacks them, the metric is
None."""

from benchmarks.chip import spans, work_mla_moe


def read(ctx):
    seconds = ctx["work"].get("prefill_s")
    job = spans.traced_job(ctx)
    if not seconds or job is None:
        return None
    held = work_mla_moe.job_count(job, "moe.experts_held")
    assign = work_mla_moe.job_count(job, "moe.assign_held.prefill")
    if held is None or assign is None:
        return None
    a, pk = ctx["args"], ctx["peaks"]
    w = work_mla_moe.prefill(ctx["config"], a["batch"], a["prompt-len"],
                             held, assign)
    least = max(w["flops"] / pk["flops_bf16"], w["bytes"] / pk["hbm_bytes_s"])
    return 100.0 * least / seconds
