"""The KV tier's host time per decode step, in microseconds: the sum of
the traced job's ``kv.append`` spans (``PagedKVCache.append_tokens``, one
per sequence and step) over its ``serve.decode.step`` spans.

The traced job is found among the jobs the program recorded in this
process as the one whose ``serve.prefill`` span, in seconds, equals
``ctx["work"]["prefill_s"]`` (``spans.traced_job``); where none does, or
the program records no spans, the metric is None."""

from benchmarks.chip import spans


def read(ctx):
    job = spans.traced_job(ctx)
    steps = job.named("serve.decode.step") if job is not None else []
    if not steps:
        return None
    return spans.total_ns(job.named("kv.append")) / len(steps) / 1e3
