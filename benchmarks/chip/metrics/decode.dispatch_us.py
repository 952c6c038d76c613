"""Mean host time of one decode step's dispatch, in microseconds: the
program's ``serve.decode.dispatch`` span, from the call of the compiled
step until it returns, averaged over the traced job's decode steps.

The traced job is found among the jobs the program recorded in this
process as the one whose ``serve.prefill`` span, in seconds, equals
``ctx["work"]["prefill_s"]`` (``spans.traced_job``); where none does, or
the program records no spans, the metric is None."""

from benchmarks.chip import spans


def read(ctx):
    job = spans.traced_job(ctx)
    steps = job.named("serve.decode.dispatch") if job is not None else []
    if not steps:
        return None
    return spans.total_ns(steps) / len(steps) / 1e3
