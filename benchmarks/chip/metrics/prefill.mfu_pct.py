"""Prefill's share of the chip's roofline: the needed prefill work
(``work.prefill``: every layer for every prompt token, causal attention,
the unembedding of the last position only), at peak, over the prefill
time that ``serve.run`` returns for the traced job (host clock around the
jitted prefill, ended by a block on its cache)."""

from benchmarks.chip import work


def read(ctx):
    seconds = ctx["work"].get("prefill_s")
    if not seconds:
        return None
    a, pk = ctx["args"], ctx["peaks"]
    w = work.prefill(ctx["config"], a["batch"], a["prompt-len"])
    least = max(w["flops"] / pk["flops_bf16"], w["bytes"] / pk["hbm_bytes_s"])
    return 100.0 * least / seconds
