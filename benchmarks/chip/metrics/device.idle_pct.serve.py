"""Share of the decode loop in which no operation runs on the device:
1 - (union of device op intervals) / (first decode step's start to the
last one's end)."""

from benchmarks.chip import xplane


def read(ctx):
    steps = xplane.programs(ctx["device0"]["modules"], "serve_step")
    if len(steps) < 2:
        return None
    return 100.0 * xplane.span_idle_share(ctx["device0"]["ops"],
                                          steps[0][1], steps[-1][2])
