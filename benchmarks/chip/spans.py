"""The program's own spans, for the per-layer metrics that read them.

A metric reader gets the traced job's work (``ctx["work"]``), not the
host plane of its trace, so it reads the spans the program kept in this
process (``repro.trace``). The traced job is the recorded job whose
``serve.prefill`` span, in seconds, equals ``ctx["work"]["prefill_s"]``:
``serve.run`` returns its ``prefill_s`` from that span through
``repro.trace.seconds``, and the entry's ``work`` copies it unchanged, so
the two are the same float. A program without the recorder, or a
recorder with no such job, gives None.
"""

from __future__ import annotations

from typing import Dict


def traced_job(ctx: Dict):
    """The recorded job that ``ctx["work"]`` describes, or None."""
    try:
        from repro import trace
    except ImportError:
        return None
    want = ctx["work"].get("prefill_s")
    for job in reversed(trace.jobs()):
        prefill = job.named("serve.prefill")
        if prefill and trace.seconds(prefill[0]) == want:
            return job
    return None


def total_ns(spans) -> int:
    return sum(s.end_ns - s.start_ns for s in spans)
