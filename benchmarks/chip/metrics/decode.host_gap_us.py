"""Mean device-idle gap between consecutive decode-step programs, in
microseconds: the host's token read, the KV tier's ``append_tokens`` and
the next dispatch all fall in it."""

from benchmarks.chip import xplane


def read(ctx):
    gap = xplane.mean_gap_ns(
        xplane.programs(ctx["device0"]["modules"], "serve_step"))
    return None if gap is None else gap / 1e3
