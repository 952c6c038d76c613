"""Seconds the process's first job (the warm-up) spent lowering and
compiling its programs, or loading them from the compile cache: its
``serve.compile`` span.

It is read only where the traced job is found among the jobs the
program recorded in this process, as the one whose ``serve.prefill``
span, in seconds, equals ``ctx["work"]["prefill_s"]``
(``spans.traced_job``); where none does, or the program records no
spans, the metric is None."""

from benchmarks.chip import spans


def read(ctx):
    if spans.traced_job(ctx) is None:
        return None
    from repro import trace

    compiled = trace.jobs()[0].named("serve.compile")
    return trace.seconds(compiled[0]) if compiled else None
